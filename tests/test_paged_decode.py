"""Paged decode attention: Pallas kernel parity vs the gather-einsum ref,
vs dense decode attention, the MLA absorbed variant, and the structured
fallback ladder recording.  Pools are a layer stack in the kernel's layout,
[L, P, KVH, D, ps]; the kernel reads one layer of it through its index."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels.paged_decode.kernel import paged_decode_attention_pallas
from repro.kernels.paged_decode.ops import (paged_decode_attention,
                                            paged_mla_decode_attention)
from repro.kernels.paged_decode.ref import paged_decode_attention_ref
from repro.runtime.guard import kernel_log

# on-lattice interpret-mode geometry: grid = b*kvh*maxp = 2*2*2 = 8 <= limit
B, H, KVH, DK, DV, PS, NPAGES, MAXP, L = 2, 4, 2, 8, 8, 128, 6, 2, 3


def _rand(seed=0, dtype=jnp.float32, kvh=KVH, dk=DK, dv=DV, ps=PS):
    ks = jax.random.split(jax.random.PRNGKey(seed), 3)
    q = jax.random.normal(ks[0], (B, H, dk), dtype)
    k = jax.random.normal(ks[1], (L, NPAGES, kvh, dk, ps), dtype)
    v = jax.random.normal(ks[2], (L, NPAGES, kvh, dv, ps), dtype)
    bt = jnp.array([[1, 3], [2, 5]], jnp.int32)
    return q, k, v, bt


def _pallas(q, k, v, bt, starts, lengths, layer=0, scale=DK ** -0.5, dv=None):
    return paged_decode_attention_pallas(
        q, k, v, bt.reshape(-1), starts, lengths, jnp.int32(layer),
        scale=float(scale), dv=dv, interpret=True)


def test_pallas_matches_ref():
    q, k, v, bt = _rand()
    lengths = jnp.array([2 * PS - 40, PS + 3], jnp.int32)   # ragged
    ref = paged_decode_attention_ref(q, k, v, bt, lengths)
    out = _pallas(q, k, v, bt, jnp.zeros_like(lengths), lengths)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               atol=2e-6, rtol=2e-6)


@pytest.mark.parametrize("layer", [1, L - 1])
def test_pallas_reads_the_given_layer(layer):
    """The kernel reads layer ``layer`` of the stack through its index map,
    as the reference does from that layer's pages alone."""
    q, k, v, bt = _rand(seed=8)
    lengths = jnp.array([2 * PS - 7, PS + 50], jnp.int32)
    out = _pallas(q, k, v, bt, jnp.zeros_like(lengths), lengths, layer)
    ref = paged_decode_attention_ref(q, k, v, bt, lengths, layer=layer)
    alone = paged_decode_attention_ref(q, k[layer:layer + 1],
                                       v[layer:layer + 1], bt, lengths)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               atol=2e-6, rtol=2e-6)
    np.testing.assert_allclose(np.asarray(ref), np.asarray(alone))
    other = paged_decode_attention_ref(q, k, v, bt, lengths, layer=0)
    assert not np.allclose(np.asarray(out), np.asarray(other))


def test_pallas_windowed_starts():
    q, k, v, bt = _rand(seed=1)
    lengths = jnp.array([2 * PS, PS + 60], jnp.int32)
    starts = jnp.array([PS + 10, 17], jnp.int32)            # window lower bound
    ref = paged_decode_attention_ref(q, k, v, bt, lengths, starts)
    out = _pallas(q, k, v, bt, starts, lengths)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               atol=2e-6, rtol=2e-6)
    # a fully-masked LEADING page must not poison the online softmax
    starts2 = jnp.array([PS + 10, PS], jnp.int32)
    ref2 = paged_decode_attention_ref(q, k, v, bt, lengths, starts2)
    out2 = _pallas(q, k, v, bt, starts2, lengths)
    np.testing.assert_allclose(np.asarray(out2), np.asarray(ref2),
                               atol=2e-6, rtol=2e-6)


def test_gqa_window_starts_at_a_later_layer_through_the_wrapper():
    """GQA (2 query heads a KV head) with window lower bounds, at layer 2,
    through the engine-layout wrapper: the kernel, no fallback."""
    q, k, v, bt = _rand(seed=9)
    lengths = jnp.array([2 * PS - 3, PS + 90], jnp.int32)
    starts = jnp.array([PS - 20, 41], jnp.int32)
    before = kernel_log().count("paged_decode")
    out = paged_decode_attention(q, k, v, bt, lengths, starts, layer=2)
    assert kernel_log().count("paged_decode") == before
    ref = paged_decode_attention_ref(q, k, v, bt, lengths, starts, layer=2)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               atol=2e-6, rtol=2e-6)


def test_wrapper_routes_pallas_on_lattice():
    q, k, v, bt = _rand(seed=2)
    lengths = jnp.array([100, 200], jnp.int32)
    before = kernel_log().count("paged_decode")
    out = paged_decode_attention(q, k, v, bt, lengths)
    ref = paged_decode_attention_ref(q, k, v, bt, lengths,
                                     jnp.zeros_like(lengths))
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               atol=2e-6, rtol=2e-6)
    assert kernel_log().count("paged_decode") == before   # no fallback fired


def test_paged_matches_dense_decode_attention():
    """Gathering the pages into a dense cache and running the dense decode
    kernel must agree with attending through the block table."""
    from repro.kernels.decode_attention.ref import decode_attention_ref

    q, k, v, bt = _rand(seed=3)
    lengths = jnp.array([2 * PS, PS + 31], jnp.int32)
    paged = paged_decode_attention_ref(q, k, v, bt, lengths)
    kd = jnp.moveaxis(k[0, bt], 4, 2).reshape(B, MAXP * PS, KVH, DK)
    vd = jnp.moveaxis(v[0, bt], 4, 2).reshape(B, MAXP * PS, KVH, DV)
    valid = jnp.arange(MAXP * PS)[None, :] < lengths[:, None]
    dense = decode_attention_ref(q, jnp.swapaxes(kd, 1, 2),
                                 jnp.swapaxes(vd, 1, 2), valid)
    np.testing.assert_allclose(np.asarray(paged), np.asarray(dense),
                               atol=2e-5, rtol=2e-5)


def test_bf16_parity_loose():
    q, k, v, bt = _rand(seed=4, dtype=jnp.bfloat16)
    lengths = jnp.array([2 * PS - 5, PS], jnp.int32)
    ref = paged_decode_attention_ref(q, k, v, bt, lengths)
    out = _pallas(q, k, v, bt, jnp.zeros_like(lengths), lengths)
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(ref, np.float32),
                               atol=3e-2, rtol=3e-2)


def test_mla_variant_matches_manual_absorption():
    rank, rope, nope = 16, 8, 8
    ks = jax.random.split(jax.random.PRNGKey(7), 5)
    qn = jax.random.normal(ks[0], (B, H, nope), jnp.float32)
    qp = jax.random.normal(ks[1], (B, H, rope), jnp.float32)
    lat = jax.random.normal(ks[2], (L, NPAGES, 1, rank + rope, PS),
                            jnp.float32)
    wkb = jax.random.normal(ks[4], (rank, H, nope), jnp.float32)
    bt = jnp.array([[1, 2], [3, 4]], jnp.int32)
    lengths = jnp.array([2 * PS, PS + 9], jnp.int32)
    scale = (nope + rope) ** -0.5
    out = paged_mla_decode_attention(qn, qp, lat, wkb, bt, lengths, scale,
                                     layer=1)
    assert out.shape == (B, H, rank)
    q_lat = jnp.einsum("bhd,rhd->bhr", qn, wkb,
                       preferred_element_type=jnp.float32).astype(qn.dtype)
    q_cat = jnp.concatenate([q_lat, qp], axis=-1)
    # K the whole latent row, V its ckv lanes, as two separate pools
    ref = paged_decode_attention_ref(q_cat, lat, lat[..., :rank, :], bt,
                                     lengths, None, scale, layer=1)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               atol=2e-5, rtol=2e-5)


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_mla_kernel_reads_k_over_576_lanes_and_v_over_512_from_one_pool(
        dtype):
    """DeepSeek's latent widths: the kernel reads one [L, P, 1, 576, ps]
    pool as K over all 576 latent dims and as V over the first 512."""
    rank, rope, h = 512, 64, 8
    ks = jax.random.split(jax.random.PRNGKey(17), 2)
    q = jax.random.normal(ks[0], (B, h, rank + rope), dtype)
    lat = jax.random.normal(ks[1], (2, NPAGES, 1, rank + rope, PS), dtype)
    bt = jnp.array([[4, 1], [5, 2]], jnp.int32)
    lengths = jnp.array([PS + 77, 2 * PS], jnp.int32)
    scale = 192 ** -0.5
    out = _pallas(q, lat, None, bt, jnp.zeros_like(lengths), lengths, 1,
                  scale, dv=rank)
    assert out.shape == (B, h, rank)
    ref = paged_decode_attention_ref(q, lat, lat[..., :rank, :], bt, lengths,
                                     None, scale, layer=1)
    tol = 2e-5 if dtype == jnp.float32 else 3e-2
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(ref, np.float32), atol=tol, rtol=tol)


def test_off_lattice_fallback_is_recorded():
    """ps % 128 != 0 routes to the ref AND lands on the kernel ladder log."""
    ps = 16
    q, k, v, bt = _rand(seed=5, ps=ps)
    lengths = jnp.array([20, 30], jnp.int32)
    before = kernel_log().count("paged_decode")
    out = paged_decode_attention(q, k, v, bt, lengths)
    assert kernel_log().count("paged_decode") == before + 1
    ev = [e for e in kernel_log().events if e.site == "paged_decode"][-1]
    assert ev.action == "pallas->ref"
    assert "off-lattice" in ev.reason
    ref = paged_decode_attention_ref(q, k, v, bt, lengths,
                                     jnp.zeros_like(lengths))
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref))


def test_dense_decode_off_lattice_fallback_recorded():
    """The pre-existing silent dense decode fallback (t%128 / d%8) now
    reports through the kernel ladder log."""
    from repro.kernels.decode_attention.ops import decode_attention

    b, h, t, d = 2, 4, 48, 8                             # t % 128 != 0
    ks = jax.random.split(jax.random.PRNGKey(11), 3)
    q = jax.random.normal(ks[0], (b, h, d), jnp.float32)
    k = jax.random.normal(ks[1], (b, h, t, d), jnp.float32)
    v = jax.random.normal(ks[2], (b, h, t, d), jnp.float32)
    valid = jnp.ones((b, t), bool)
    before = kernel_log().count("decode_attention")
    decode_attention(q, k, v, valid)
    assert kernel_log().count("decode_attention") == before + 1
    ev = [e for e in kernel_log().events
          if e.site == "decode_attention"][-1]
    assert ev.action == "pallas->ref" and "off-lattice" in ev.reason


def test_interpret_grid_guard_routes_ref_silently():
    """Above INTERPRET_GRID_LIMIT the wrapper uses the ref without a
    degradation event (a route decision, not a failure)."""
    from repro.kernels import INTERPRET_GRID_LIMIT

    maxp = INTERPRET_GRID_LIMIT // (B * KVH) + 1
    npages = maxp + 1
    ks = jax.random.split(jax.random.PRNGKey(13), 3)
    q = jax.random.normal(ks[0], (B, H, DK), jnp.float32)
    k = jax.random.normal(ks[1], (1, npages, KVH, DK, PS), jnp.float32)
    v = jax.random.normal(ks[2], (1, npages, KVH, DV, PS), jnp.float32)
    bt = jnp.broadcast_to(jnp.arange(1, maxp + 1, dtype=jnp.int32)[None],
                          (B, maxp))
    lengths = jnp.array([maxp * PS, PS], jnp.int32)
    before = kernel_log().count("paged_decode")
    out = paged_decode_attention(q, k, v, bt, lengths)
    assert kernel_log().count("paged_decode") == before
    ref = paged_decode_attention_ref(q, k, v, bt, lengths,
                                     jnp.zeros_like(lengths))
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref))
