"""Compile-only rehearsals of the main-path Pallas kernels for a TPU v5e.

Each kernel is compiled, not run, for one chip of a described (not
attached) v5e at qwen2-0.5b's widths (14 query heads over 2 KV heads, head
dim 64, d_model 896, d_ff 4864), or at DeepSeek-V3's (d_model 7168, 8
held experts of width 2048, 128 latent heads over a 512 + 64 cache), and
must lower to a Mosaic ``tpu_custom_call``.  This catches what interpret mode cannot: block
shapes the TPU lowering refuses, and VMEM over-use.  The whole jitted
paged decode step is compiled too, at MiniCPM-2B's head layout and at
DeepSeek-V3's latent one, to see that it updates its donated page pools in
place: no layout copy or second pool, which a CPU compile cannot show.

The topology is described only inside the module fixture (loading the TPU
compiler while a module is imported would give pytest-xdist workers
different test sets); keep every such test in this one file.
"""
import dataclasses
import functools

import jax
import jax.numpy as jnp
import pytest

from repro.configs import get_config
from repro.kernels.branch_gemm.kernel import branch_gemm_pallas
from repro.kernels.branch_gemm.ops import select_tiles
from repro.kernels.decode_attention.kernel import decode_attention_pallas
from repro.kernels.flash_attention.kernel import flash_attention_pallas
from repro.kernels.moe_gemm.kernel import moe_mlp_pallas
from repro.kernels.moe_gemm.ops import select_tiles as moe_tiles
from repro.kernels.paged_decode.kernel import paged_decode_attention_pallas

B, H, KVH, D, D_MODEL, D_FF = 4, 14, 2, 64, 896, 4864
SEQ, CACHE, PAGE = 128, 256, 128
LAYERS = 3


@pytest.fixture(scope="module")
def one_chip():
    from jax.experimental import compilation_cache, topologies
    from jax.sharding import SingleDeviceSharding

    cache_on = jax.config.jax_enable_compilation_cache
    with pytest.MonkeyPatch.context() as mp:
        mp.setenv("TPU_LOG_DIR", "disabled")   # no compiler logs on disk
        try:
            topo = topologies.get_topology_desc(platform="tpu",
                                                topology_name="v5e:2x2")
        except Exception as e:
            pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
        # a described chip's executable is written to the persistent cache
        # but cannot be read back without one: keep these compiles out
        jax.config.update("jax_enable_compilation_cache", False)
        compilation_cache.compilation_cache.reset_cache()
        try:
            yield SingleDeviceSharding(topo.devices[0])
        finally:
            jax.config.update("jax_enable_compilation_cache", cache_on)


def _stacked_gemm(f):
    # two [2048, 896] @ [896, f] branches, the benchmark's batch 8 x 256,
    # at the tiles the kernel's rule picks (the whole of K: 11.9 MiB of
    # VMEM at f = 4864, under the default scoped limit)
    m = 8 * 256
    bm, bf = select_tiles(m, D_MODEL, f)
    fn = functools.partial(branch_gemm_pallas, bm=bm, bf=bf, interpret=False)
    return fn, [((2, m, D_MODEL), jnp.bfloat16),
                ((2, D_MODEL, f), jnp.bfloat16)]


def _branch_gemm():
    # the captured gate||up wave
    return _stacked_gemm(D_FF)


def _branch_gemm_kv():
    # the captured k||v wave: F = 2 KV heads x head dim 64
    return _stacked_gemm(KVH * D)


def _flash_attention():
    fn = functools.partial(flash_attention_pallas, causal=True, window=0,
                           bq=128, bk=128, interpret=False)
    return fn, [((B, H, SEQ, D), jnp.bfloat16),
                ((B, KVH, SEQ, D), jnp.bfloat16),
                ((B, KVH, SEQ, D), jnp.bfloat16)]


def _decode_attention():
    fn = functools.partial(decode_attention_pallas, bk=CACHE, interpret=False)
    return fn, [((B, H, D), jnp.bfloat16),
                ((B, KVH, CACHE, D), jnp.bfloat16),
                ((B, KVH, CACHE, D), jnp.bfloat16),
                ((B, CACHE), jnp.int32)]


def _paged_decode():
    maxp = CACHE // PAGE
    pages = 1 + B * maxp
    fn = functools.partial(paged_decode_attention_pallas, scale=D ** -0.5,
                           interpret=False)
    return fn, [((B, H, D), jnp.bfloat16),
                ((LAYERS, pages, KVH, D, PAGE), jnp.bfloat16),
                ((LAYERS, pages, KVH, D, PAGE), jnp.bfloat16),
                ((B * maxp,), jnp.int32),
                ((B,), jnp.int32),
                ((B,), jnp.int32),
                ((), jnp.int32)]


def _moe_gemm(rows):
    # the held experts' SwiGLU at the tiles the rule picks for ``rows``
    # tokens a step (the kernel's blocks within the 16 MiB scoped VMEM)
    e, d, f = 8, 7168, 2048
    bc, bf = moe_tiles(rows, d, f)
    c = -(-rows // bc) * bc
    fn = functools.partial(moe_mlp_pallas, bc=bc, bf=bf, interpret=False)
    return fn, [((e, c, d), jnp.bfloat16), ((e, d, f), jnp.bfloat16),
                ((e, d, f), jnp.bfloat16), ((e, f, d), jnp.bfloat16),
                ((e,), jnp.int32)]


def _moe_gemm_decode():
    return _moe_gemm(16)


def _moe_gemm_prefill():
    return _moe_gemm(8192)


def _paged_mla_decode():
    # the absorbed latent attention: 16 rows, 128 heads over one latent KV
    # head, Dk 512 + 64, Dv 512, 73 pages of 128
    b, h, maxp = 16, 128, 73
    pages = 1 + b * maxp
    def fn(q, pool, bt, starts, lengths, layer):
        return paged_decode_attention_pallas(
            q, pool, None, bt, starts, lengths, layer, scale=192 ** -0.5,
            dv=512, interpret=False)
    return fn, [((b, h, 576), jnp.bfloat16),
                ((LAYERS, pages, 1, 576, PAGE), jnp.bfloat16),
                ((b * maxp,), jnp.int32),
                ((b,), jnp.int32),
                ((b,), jnp.int32),
                ((), jnp.int32)]


@pytest.mark.parametrize("case", [_branch_gemm, _branch_gemm_kv,
                                  _flash_attention, _decode_attention,
                                  _paged_decode, _moe_gemm_decode,
                                  _moe_gemm_prefill, _paged_mla_decode],
                         ids=lambda c: c.__name__.lstrip("_"))
def test_kernel_compiles_for_v5e(one_chip, case):
    fn, args = case()
    shapes = [jax.ShapeDtypeStruct(s, dt, sharding=one_chip)
              for s, dt in args]
    compiled = jax.jit(fn).lower(*shapes).compile()
    assert "tpu_custom_call" in compiled.as_text()


def _minicpm_heads():
    # 36 KV heads (MHA) of dim 64 at MiniCPM-2B's widths, 2 layers
    return dataclasses.replace(get_config("minicpm-2b"), n_layers=2), 2, 6


def _deepseek_latent():
    # 128 heads over one latent KV head, K over 512 + 64, V over 512;
    # dense FFN layers (the held experts are compiled above).  The cell's
    # 16 slots of 73 pages: a layer's pool (172 MB) then outweighs the
    # step's other temporaries (the per-layer weight slices, ~77 MB)
    cfg = get_config("deepseek-v3-671b")
    return dataclasses.replace(cfg, n_layers=2, family="dense", moe=None,
                               dense_prefix=0), 16, 73


@pytest.mark.parametrize("case", [_minicpm_heads, _deepseek_latent],
                         ids=lambda c: c.__name__.lstrip("_"))
def test_paged_decode_step_updates_its_pools_in_place(one_chip, monkeypatch,
                                                      case):
    """The engine's jitted paged decode step, kernels on, compiled whole for
    a v5e: every page pool leaf is aliased from its donated input to its
    output, and the step's temporaries are smaller than one layer's pool
    (so no op copies, transposes or concatenates a pool)."""
    from repro.kernels.paged_decode import ops
    from repro.models import Model
    from repro.serving.engine import _donated_paged_decode_fn

    monkeypatch.setattr(ops, "interpret_mode", lambda: False)
    cfg, slots, maxp = case()
    model = Model(cfg, use_kernels=True)

    def spec(x):
        return jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=one_chip)

    params = jax.tree.map(spec, jax.eval_shape(model.init, jax.random.key(0)))
    pools = jax.tree.map(spec, jax.eval_shape(
        lambda: model.init_paged_caches(1 + slots * maxp, PAGE)))
    ints = [jax.ShapeDtypeStruct(s, jnp.int32, sharding=one_chip)
            for s in ((slots,), (slots, maxp), (slots,))]
    compiled = _donated_paged_decode_fn(model).lower(
        params, pools, ints[0], ints[1], ints[2]).compile()
    assert "tpu_custom_call" in compiled.as_text()
    leaves = jax.tree.leaves(pools)
    pool_bytes = sum(x.size * x.dtype.itemsize for x in leaves)
    mem = compiled.memory_analysis()
    assert mem.alias_size_in_bytes == pool_bytes
    layer_bytes = pool_bytes // cfg.n_layers
    assert mem.temp_size_in_bytes < layer_bytes, (mem.temp_size_in_bytes,
                                                  layer_bytes)
