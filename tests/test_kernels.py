"""Per-kernel shape/dtype sweeps asserting allclose against the ref oracle
(interpret=True on CPU; same code path targets TPU)."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

rng = np.random.default_rng(0)


def _rand(shape, dtype, scale=1.0):
    return jnp.asarray(rng.standard_normal(shape) * scale, dtype)


def _assert_close(a, b, rtol, atol):
    np.testing.assert_allclose(np.asarray(a, np.float32),
                               np.asarray(b, np.float32), rtol=rtol, atol=atol)


# ------------------------------------------------------------- branch_gemm
@pytest.mark.parametrize("n,m,k,f", [(1, 8, 128, 128), (3, 16, 256, 128),
                                     (4, 32, 128, 256)])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_branch_gemm(n, m, k, f, dtype):
    from repro.kernels.branch_gemm.ops import branch_gemm
    from repro.kernels.branch_gemm.ref import branch_gemm_ref
    x = _rand((n, m, k), dtype, 0.1)
    w = _rand((n, k, f), dtype, 0.1)
    tol = 2e-2 if dtype == jnp.bfloat16 else 1e-5
    _assert_close(branch_gemm(x, w), branch_gemm_ref(x, w), tol, tol)


# K = 640 and F = 384 are multiples of 128 but not powers of two: the rule
# takes K whole and F = 3 x 128 whole, where the old halving rule fell to
# 128-wide tiles; the explicit tiles split M and F
@pytest.mark.parametrize("n,m,k,f,tiles", [
    (2, 24, 640, 384, None),
    (2, 16, 640, 384, (8, 128)),
    (3, 40, 384, 640, (8, 128)),
])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_branch_gemm_tiles_match_ref(n, m, k, f, tiles, dtype):
    from repro.kernels.branch_gemm.kernel import branch_gemm_pallas
    from repro.kernels.branch_gemm.ops import select_tiles
    from repro.kernels.branch_gemm.ref import branch_gemm_ref
    x = _rand((n, m, k), dtype, 0.1)
    w = _rand((n, k, f), dtype, 0.1)
    if tiles is None:
        tiles = select_tiles(m, k, f, x.dtype.itemsize)
        assert tiles == (m, f)
    bm, bf = tiles
    got = branch_gemm_pallas(x, w, bm=bm, bf=bf, interpret=True)
    assert got.dtype == x.dtype
    tol = 2e-2 if dtype == jnp.bfloat16 else 1e-5
    _assert_close(got, branch_gemm_ref(x, w), tol, tol)


@pytest.mark.parametrize("f,tiles", [(4864, (2048, 256)), (128, (2048, 128))])
def test_branch_gemm_tile_rule_at_qwen2_widths(f, tiles):
    """qwen2-0.5b's stacked groups at batch 8 x 256 (gate||up, k||v): blocks
    of the whole of K = 896, double-buffered, plus the fp32 result fit the
    v5e's default scoped VMEM; the next column tile up does not."""
    from repro.kernels.branch_gemm.ops import (VMEM_BUDGET, grid_steps,
                                               select_tiles, vmem_bytes)
    m, k = 8 * 256, 896
    assert VMEM_BUDGET == 16 * 1024 * 1024
    bm, bf = select_tiles(m, k, f)
    assert (bm, bf) == tiles
    assert m % bm == 0 and bm % 8 == 0 and f % bf == 0 and bf % 128 == 0
    assert (vmem_bytes(bm, bf, k, 2)
            == 2 * 2 * (bm * k + k * bf + bm * bf) + 4 * bm * bf)
    assert vmem_bytes(bm, bf, k, 2) <= VMEM_BUDGET
    # one grid step per output block: no K axis
    assert grid_steps(2, m, k, f) == 2 * (m // bm) * (f // bf)
    if f == 4864:   # the next column tile dividing F is 2432 = 19 x 128
        assert vmem_bytes(bm, 2432, k, 2) > VMEM_BUDGET


def test_branch_gemm_too_deep_for_vmem_runs_the_reference(monkeypatch):
    """Off the lattice, or with K so deep that no blocks of the whole of K
    fit VMEM, the rule picks no tiles and the wrapper runs the einsum ref."""
    from repro.kernels.branch_gemm import ops
    assert ops.select_tiles(12, 128, 128) is None   # M not a multiple of 8
    assert ops.select_tiles(8, 100, 128) is None    # K not a multiple of 128
    # K = 2^16 at 4-byte items: even 8 x 128 blocks overflow the budget
    k = 2 ** 16
    assert ops.vmem_bytes(8, 128, k, 4) > ops.VMEM_BUDGET
    assert ops.select_tiles(8, k, 128, 4) is None
    assert ops.grid_steps(2, 8, k, 128, 4) == 0
    assert ops.select_tiles(8, k, 128, 2) is None
    assert ops.select_tiles(8, k // 4, 128, 2) == (8, 128)
    monkeypatch.setattr(ops, "branch_gemm_pallas", None)   # never launched
    x = _rand((1, 8, k), jnp.float32, 0.01)
    w = _rand((1, k, 128), jnp.float32, 0.01)
    _assert_close(ops.branch_gemm(x, w), ops.branch_gemm_ref(x, w), 0, 0)


# --------------------------------------------------------- flash_attention
@pytest.mark.parametrize("s,t,h,kvh,d", [(128, 128, 4, 2, 32),
                                         (256, 256, 4, 4, 64),
                                         (128, 256, 8, 2, 16)])
@pytest.mark.parametrize("window", [0, 48])
def test_flash_attention(s, t, h, kvh, d, window):
    from repro.kernels.flash_attention.ops import flash_attention
    from repro.kernels.flash_attention.ref import flash_attention_ref
    q = _rand((2, h, s, d), jnp.float32)
    k = _rand((2, kvh, t, d), jnp.float32)
    v = _rand((2, kvh, t, d), jnp.float32)
    got = flash_attention(q, k, v, causal=True, window=window, bq=64, bk=64)
    ref = flash_attention_ref(q, k, v, causal=True, window=window)
    _assert_close(got, ref, 2e-3, 2e-3)


def test_flash_attention_bf16():
    from repro.kernels.flash_attention.ops import flash_attention
    from repro.kernels.flash_attention.ref import flash_attention_ref
    q = _rand((1, 4, 128, 32), jnp.bfloat16)
    k = _rand((1, 2, 128, 32), jnp.bfloat16)
    v = _rand((1, 2, 128, 32), jnp.bfloat16)
    _assert_close(flash_attention(q, k, v, bq=64, bk=64),
                  flash_attention_ref(q, k, v), 3e-2, 3e-2)


# -------------------------------------------------------- decode_attention
@pytest.mark.parametrize("t,h,kvh,d", [(256, 4, 2, 32), (512, 8, 8, 64),
                                       (384, 4, 1, 16)])
def test_decode_attention(t, h, kvh, d):
    from repro.kernels.decode_attention.ops import decode_attention
    from repro.kernels.decode_attention.ref import decode_attention_ref
    q = _rand((2, h, d), jnp.float32)
    k = _rand((2, kvh, t, d), jnp.float32)
    v = _rand((2, kvh, t, d), jnp.float32)
    valid = jnp.asarray(np.arange(t)[None] <= np.array([t // 3, t - 1])[:, None])
    got = decode_attention(q, k, v, valid, bk=128)
    ref = decode_attention_ref(q, k, v, valid)
    _assert_close(got, ref, 2e-3, 2e-3)


# ------------------------------------------------------------------ rwkv6
@pytest.mark.parametrize("t,ct", [(32, 8), (64, 16), (24, 8)])
def test_rwkv6(t, ct):
    from repro.kernels.rwkv6.ops import rwkv6
    from repro.kernels.rwkv6.ref import rwkv6_ref
    b, h, k = 2, 2, 16
    r, kk, vv = [_rand((b, h, t, k), jnp.float32) for _ in range(3)]
    w = jnp.asarray(rng.uniform(0.8, 0.999, (b, h, t, k)), jnp.float32)
    u = _rand((h, k), jnp.float32)
    s0 = _rand((b, h, k, k), jnp.float32)
    o1, s1 = rwkv6(r, kk, vv, w, u, s0, ct=ct)
    o2, s2 = rwkv6_ref(r, kk, vv, w, u, s0)
    _assert_close(o1, o2, 1e-4, 1e-4)
    _assert_close(s1, s2, 1e-4, 1e-4)


# --------------------------------------------------------------- moe_gemm
@pytest.mark.parametrize("e,c,d,f", [(2, 8, 128, 128), (4, 16, 128, 256)])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_moe_gemm(e, c, d, f, dtype):
    from repro.kernels.moe_gemm.ops import moe_mlp
    from repro.kernels.moe_gemm.ref import moe_mlp_ref
    buf = _rand((e, c, d), dtype, 0.1)
    g = _rand((e, d, f), dtype, 0.05)
    u = _rand((e, d, f), dtype, 0.05)
    dn = _rand((e, f, d), dtype, 0.05)
    tol = 3e-2 if dtype == jnp.bfloat16 else 1e-4
    _assert_close(moe_mlp(buf, g, u, dn), moe_mlp_ref(buf, g, u, dn), tol, tol)


@pytest.mark.parametrize("c", [1, 16, 100, 1024, 8192])
def test_moe_gemm_tiles_fit_vmem_at_deepseek_width(c):
    """At DeepSeek-V3's d 7168 and expert width 2048 the rule's blocks fit
    the 16 MiB scoped VMEM, the row tile covers the rows in as few tiles as
    the budget allows, and rows pad to a multiple of it."""
    from repro.kernels.branch_gemm.ops import VMEM_BUDGET
    from repro.kernels.moe_gemm.ops import select_tiles, vmem_bytes
    bc, bf = select_tiles(c, 7168, 2048)
    assert bc % 8 == 0 and 2048 % bf == 0 and bf % 128 == 0
    assert vmem_bytes(bc, bf, 7168, 2) <= VMEM_BUDGET
    c8 = -(-c // 8) * 8
    # no larger row tile fits at the narrowest column tile
    assert bc >= c8 or vmem_bytes(bc + 8, 128, 7168, 2) > VMEM_BUDGET \
        or -(-c8 // (bc + 8)) == -(-c8 // bc)
    # the old rule (all of d, bf 256) does not fit
    assert vmem_bytes(bc, 256, 7168, 2) > VMEM_BUDGET


def test_moe_gemm_skips_dead_rows_and_records_its_fallback():
    from repro.kernels.moe_gemm.ops import moe_mlp
    from repro.kernels.moe_gemm.ref import moe_mlp_ref
    from repro.runtime.guard import kernel_log
    e, c, d, f = 4, 24, 128, 256
    buf = _rand((e, c, d), jnp.float32, 0.1)
    g, u = _rand((e, d, f), jnp.float32, 0.05), _rand((e, d, f), jnp.float32, 0.05)
    dn = _rand((e, f, d), jnp.float32, 0.05)
    rows = jnp.asarray([0, 24, 5, 0], jnp.int32)
    buf = buf * (jnp.arange(c)[None, :, None] < rows[:, None, None])
    want = moe_mlp_ref(buf, g, u, dn)
    from repro.kernels.moe_gemm.kernel import moe_mlp_pallas
    # one row tile (the rule's pick), and 3 of 8 rows each: dead experts,
    # and dead tiles after an expert's live ones
    for got in (moe_mlp(buf, g, u, dn, rows),
                moe_mlp_pallas(buf, g, u, dn, rows, bc=8, bf=128)):
        for ex, r in enumerate(rows.tolist()):  # live rows only are defined
            _assert_close(got[ex, :r], want[ex, :r], 1e-4, 1e-4)
    assert kernel_log().count("moe_gemm") == 0
    # off the 128 lattice: the reference, recorded
    _assert_close(moe_mlp(buf[..., :96], g[:, :96], u[:, :96], dn[..., :96]),
                  moe_mlp_ref(buf[..., :96], g[:, :96], u[:, :96],
                              dn[..., :96]), 1e-5, 1e-5)
    assert kernel_log().count("moe_gemm") == 1
    ev = [x for x in kernel_log().events if x.site == "moe_gemm"][-1]
    assert ev.action == "pallas->ref" and "d=96" in ev.reason


# ---------------------------------------------------------------- rmsnorm
@pytest.mark.parametrize("shape", [(16, 128), (2, 8, 256), (32, 384)])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_rmsnorm(shape, dtype):
    from repro.kernels.rmsnorm.ops import rmsnorm
    from repro.kernels.rmsnorm.ref import rmsnorm_ref
    x = _rand(shape, dtype)
    sc = _rand(shape[-1:], dtype)
    tol = 3e-2 if dtype == jnp.bfloat16 else 1e-5
    _assert_close(rmsnorm(x, sc), rmsnorm_ref(x, sc), tol, tol)


# --------------------------------------- chunked attention (jnp flash twin)
@pytest.mark.parametrize("s,window", [(96, None), (96, 24), (100, 17)])
def test_chunked_attention_matches_naive(s, window):
    from repro.models.attention import _sdpa, causal_window_mask, chunked_attention
    b, h, kvh, d, dv = 2, 4, 2, 16, 24
    q = _rand((b, s, h, d), jnp.float32)
    k = _rand((b, s, kvh, d), jnp.float32)
    v = _rand((b, s, kvh, dv), jnp.float32)
    pos = jnp.arange(s)
    ref = _sdpa(q, k, v, causal_window_mask(pos, pos, window))
    got = chunked_attention(q, k, v, causal=True, window=window,
                            q_chunk=32, kv_chunk=16)
    _assert_close(got, ref, 1e-5, 1e-5)


def test_chunked_attention_grads_match_naive():
    from repro.models.attention import _sdpa, causal_window_mask, chunked_attention
    b, s, h, kvh, d = 2, 64, 4, 2, 16
    q = _rand((b, s, h, d), jnp.float32)
    k = _rand((b, s, kvh, d), jnp.float32)
    v = _rand((b, s, kvh, d), jnp.float32)
    pos = jnp.arange(s)
    w = jnp.asarray(rng.standard_normal((d,)), jnp.float32)

    def loss_naive(q, k, v):
        return (_sdpa(q, k, v, causal_window_mask(pos, pos, None)) * w).sum()

    def loss_chunk(q, k, v):
        return (chunked_attention(q, k, v, q_chunk=16, kv_chunk=32) * w).sum()

    g1 = jax.grad(loss_naive, argnums=(0, 1, 2))(q, k, v)
    g2 = jax.grad(loss_chunk, argnums=(0, 1, 2))(q, k, v)
    for a, b_ in zip(g1, g2):
        _assert_close(a, b_, 1e-4, 1e-4)
