"""Every §Perf optimization flag must preserve numerics exactly
(the hillclimb trades memory/collectives, never correctness)."""
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_config
from repro.models import make_model


@pytest.fixture
def clean_env():
    keys = ["REPRO_CACHE_UPDATE", "REPRO_CHUNKED_CE", "REPRO_CAUSAL_SKIP",
            "REPRO_WINDOW_SLICE_DECODE", "REPRO_KV_QUANT"]
    saved = {k: os.environ.pop(k, None) for k in keys}
    yield
    for k, v in saved.items():
        if v is None:
            os.environ.pop(k, None)
        else:
            os.environ[k] = v


def test_chunked_ce_matches(clean_env):
    cfg = get_config("llama3.2-1b", smoke=True)
    m = make_model(cfg)
    params = m.init(jax.random.key(0))
    batch = {"tokens": jnp.arange(32).reshape(2, 16) % cfg.vocab_size,
             "labels": jnp.ones((2, 16), jnp.int32)}
    l0, _ = m.loss(params, batch)
    os.environ["REPRO_CHUNKED_CE"] = "1"
    l1, _ = m.loss(params, batch)
    assert abs(float(l0) - float(l1)) < 1e-3


def test_scatter_cache_matches(clean_env):
    cfg = get_config("qwen2-0.5b", smoke=True)
    m = make_model(cfg)
    params = m.init(jax.random.key(0))
    logits, caches, _ = m.prefill(params, {"tokens": jnp.ones((2, 8), jnp.int32)},
                               cache_len=16)
    tok = jnp.argmax(logits, -1).astype(jnp.int32)
    pos = jnp.full((2,), 8, jnp.int32)
    d0, _ = m.decode(params, tok, caches, pos)
    os.environ["REPRO_CACHE_UPDATE"] = "scatter"
    d1, _ = m.decode(params, tok, caches, pos)
    np.testing.assert_allclose(np.asarray(d0, np.float32),
                               np.asarray(d1, np.float32), rtol=1e-3, atol=1e-3)


def test_mla_scatter_cache_matches(clean_env):
    cfg = get_config("deepseek-v3-671b", smoke=True)
    m = make_model(cfg)
    params = m.init(jax.random.key(0))
    logits, caches, _ = m.prefill(params, {"tokens": jnp.ones((2, 8), jnp.int32)},
                               cache_len=16)
    tok = jnp.argmax(logits, -1).astype(jnp.int32)
    pos = jnp.full((2,), 8, jnp.int32)
    d0, _ = m.decode(params, tok, caches, pos)
    os.environ["REPRO_CACHE_UPDATE"] = "scatter"
    d1, _ = m.decode(params, tok, caches, pos)
    np.testing.assert_allclose(np.asarray(d0, np.float32),
                               np.asarray(d1, np.float32), rtol=1e-3, atol=1e-3)


def test_causal_skip_matches(clean_env):
    from repro.models.attention import chunked_attention
    rng = np.random.default_rng(0)
    q = jnp.asarray(rng.standard_normal((2, 96, 4, 16)), jnp.float32)
    k = jnp.asarray(rng.standard_normal((2, 96, 2, 16)), jnp.float32)
    v = jnp.asarray(rng.standard_normal((2, 96, 2, 16)), jnp.float32)
    a0 = chunked_attention(q, k, v, causal=True, window=24,
                           q_chunk=16, kv_chunk=16)
    os.environ["REPRO_CAUSAL_SKIP"] = "1"
    a1 = chunked_attention(q, k, v, causal=True, window=24,
                           q_chunk=16, kv_chunk=16)
    np.testing.assert_allclose(np.asarray(a0), np.asarray(a1),
                               rtol=1e-5, atol=1e-5)


def test_kv_quant_decode_close(clean_env):
    """O8 int8 latent cache: decode logits within 5% of full precision."""
    cfg = get_config("deepseek-v3-671b", smoke=True)
    m = make_model(cfg)
    params = m.init(jax.random.key(0))
    inp = {"tokens": (jnp.arange(16).reshape(2, 8) * 7) % cfg.vocab_size}
    logits, caches, _ = m.prefill(params, inp, cache_len=16)
    tok = jnp.argmax(logits, -1).astype(jnp.int32)
    pos = jnp.full((2,), 8, jnp.int32)
    d0, _ = m.decode(params, tok, caches, pos)

    os.environ["REPRO_KV_QUANT"] = "1"
    quant = []
    for ck, rk in caches:
        scale = jnp.maximum(jnp.max(jnp.abs(ck), axis=-1), 1e-6) / 127.0
        q = jnp.clip(jnp.round(ck / scale[..., None]), -127, 127).astype(jnp.int8)
        quant.append((q, scale.astype(jnp.float16), rk))
    d1, new_cache = m.decode(params, tok, quant, pos)
    assert new_cache[0][0].dtype == jnp.int8
    rel = float(jnp.max(jnp.abs(d0.astype(jnp.float32) - d1.astype(jnp.float32)))
                ) / (float(jnp.max(jnp.abs(d0))) + 1e-9)
    assert rel < 0.05, rel


def test_window_slice_decode_matches(clean_env):
    cfg = get_config("hymba-1.5b", smoke=True)
    m = make_model(cfg)
    params = m.init(jax.random.key(0))
    inp = {"tokens": jnp.arange(32).reshape(2, 16) % cfg.vocab_size}
    logits, caches, _ = m.prefill(params, inp, cache_len=64 + cfg.meta_tokens)
    tok = jnp.argmax(logits, -1).astype(jnp.int32)
    pos = jnp.full((2,), 16, jnp.int32)
    d0, _ = m.decode(params, tok, caches, pos)
    os.environ["REPRO_WINDOW_SLICE_DECODE"] = "1"
    d1, _ = m.decode(params, tok, caches, pos)
    np.testing.assert_allclose(np.asarray(d0, np.float32),
                               np.asarray(d1, np.float32), rtol=2e-2, atol=2e-2)
