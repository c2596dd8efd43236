"""Compile-only rehearsals of the main-path Pallas kernels for a TPU v5e.

Each kernel is compiled, not run, for one chip of a described (not
attached) v5e at qwen2-0.5b's widths (14 query heads over 2 KV heads, head
dim 64, d_model 896, d_ff 4864), or at DeepSeek-V3's (d_model 7168, 8
held experts of width 2048, 128 latent heads over a 512 + 64 cache), and
must lower to a Mosaic ``tpu_custom_call``.  This catches what interpret mode cannot: block
shapes the TPU lowering refuses, and VMEM over-use.

The topology is described only inside the module fixture (loading the TPU
compiler while a module is imported would give pytest-xdist workers
different test sets); keep every such test in this one file.
"""
import functools

import jax
import jax.numpy as jnp
import pytest

from repro.kernels.branch_gemm.kernel import branch_gemm_pallas
from repro.kernels.branch_gemm.ops import select_tiles
from repro.kernels.decode_attention.kernel import decode_attention_pallas
from repro.kernels.flash_attention.kernel import flash_attention_pallas
from repro.kernels.moe_gemm.kernel import moe_mlp_pallas
from repro.kernels.moe_gemm.ops import select_tiles as moe_tiles
from repro.kernels.paged_decode.kernel import paged_decode_attention_pallas

B, H, KVH, D, D_MODEL, D_FF = 4, 14, 2, 64, 896, 4864
SEQ, CACHE, PAGE = 128, 256, 128


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
                ((pages, KVH, PAGE, D), jnp.bfloat16),
                ((pages, KVH, PAGE, D), jnp.bfloat16),
                ((B * maxp,), jnp.int32),
                ((B,), jnp.int32),
                ((B,), jnp.int32)]


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
    fn = functools.partial(paged_decode_attention_pallas,
                           scale=192 ** -0.5, interpret=False)
    return fn, [((b, h, 576), jnp.bfloat16),
                ((pages, 1, PAGE, 576), jnp.bfloat16),
                ((pages, 1, PAGE, 512), jnp.bfloat16),
                ((b * maxp,), jnp.int32),
                ((b,), jnp.int32),
                ((b,), jnp.int32)]


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
