"""Per-architecture smoke tests: reduced same-family config, one forward /
train / decode step on CPU, asserting output shapes and no NaNs — as
mandated by the assignment.  One test per assigned architecture."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_config, list_archs
from repro.models import make_model

B, S = 2, 16


def _batch(cfg):
    batch = {"tokens": jnp.zeros((B, S), jnp.int32),
             "labels": jnp.ones((B, S), jnp.int32)}
    if cfg.family == "encdec":
        batch["frames"] = jnp.ones(
            (B, cfg.frontend.n_tokens, cfg.frontend.feat_dim), cfg.dtype)
    if cfg.family == "vlm":
        batch["extra_embeds"] = jnp.ones(
            (B, cfg.frontend.n_tokens, cfg.frontend.feat_dim), cfg.dtype)
    return batch


@pytest.mark.parametrize("arch", list_archs())
def test_arch_smoke(arch):
    cfg = get_config(arch, smoke=True)
    model = make_model(cfg)
    params = model.init(jax.random.key(0))
    batch = _batch(cfg)

    # train step objective
    loss, metrics = model.loss(params, batch, jax.random.key(1))
    assert jnp.isfinite(loss), f"{arch}: non-finite loss"
    assert float(loss) > 0

    # prefill + one decode step
    inputs = {k: v for k, v in batch.items() if k != "labels"}
    extra = cfg.frontend.n_tokens if cfg.family == "vlm" else 0
    cache_len = S + 8 + cfg.meta_tokens + extra
    logits, caches, _ = model.prefill(params, inputs, cache_len=cache_len)
    assert logits.shape == (B, cfg.vocab_size)
    assert bool(jnp.isfinite(logits).all()), f"{arch}: non-finite prefill logits"

    pos = jnp.full((B,), S + extra, jnp.int32)
    tok = jnp.argmax(logits, -1).astype(jnp.int32)
    logits2, caches2 = model.decode(params, tok, caches, pos)
    assert logits2.shape == (B, cfg.vocab_size)
    assert bool(jnp.isfinite(logits2).all()), f"{arch}: non-finite decode logits"


@pytest.mark.parametrize("arch", ["llama3.2-1b", "qwen2-0.5b", "rwkv6-1.6b"])
def test_decode_matches_teacher_forcing(arch):
    """Decoding token-by-token must reproduce the teacher-forced logits."""
    from repro.models import transformer as tf
    cfg = get_config(arch, smoke=True)
    model = make_model(cfg)
    params = model.init(jax.random.key(0))
    toks = jax.random.randint(jax.random.key(2), (1, 8), 0, cfg.vocab_size)

    # teacher-forced full forward
    full_logits, _, _ = tf.lm_forward(params, toks, cfg)

    # prefill on the first 4, then decode the rest one at a time
    cache_len = 16 + cfg.meta_tokens
    logits, caches, _ = model.prefill(params, {"tokens": toks[:, :4]},
                                   cache_len=cache_len)
    np.testing.assert_allclose(np.asarray(logits[0]),
                               np.asarray(full_logits[0, 3]),
                               rtol=2e-2, atol=2e-2)
    for i in range(4, 8):
        pos = jnp.asarray([i], jnp.int32)
        logits, caches = model.decode(params, toks[:, i], caches, pos)
        np.testing.assert_allclose(np.asarray(logits[0]),
                                   np.asarray(full_logits[0, i]),
                                   rtol=2e-2, atol=2e-2,
                                   err_msg=f"{arch} step {i}")


def test_param_count_matches_analytic():
    """ModelConfig.n_params() within 15% of the real initialized count."""
    from repro.utils.tree import tree_param_count
    for arch in ("llama3.2-1b", "qwen2-0.5b", "glm4-9b"):
        cfg = get_config(arch, smoke=True)
        params = make_model(cfg).init(jax.random.key(0))
        real = tree_param_count(params)
        est = cfg.n_params()
        assert abs(real - est) / real < 0.15, (arch, real, est)


def test_moe_active_params_smaller_than_total():
    cfg = get_config("kimi-k2-1t-a32b")
    assert cfg.n_active_params() < cfg.n_params() / 5
    # sanity: the real K2 is ~1T total / ~32B active
    assert 0.6e12 < cfg.n_params() < 1.6e12
    assert 15e9 < cfg.n_active_params() < 60e9
