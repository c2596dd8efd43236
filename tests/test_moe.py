"""MoE dispatch: sort-based path ≡ dense one-hot oracle; capacity; routing."""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs.base import ModelConfig, MoEConfig
from repro.models.ffn import init_moe, moe_ffn_dense, moe_ffn_sort, route


def _cfg(n_experts=8, top_k=2, cf=2.0, aux_free=False):
    return ModelConfig(
        name="moe-test", family="moe", n_layers=1, d_model=32, n_heads=2,
        n_kv_heads=2, d_ff=64, vocab_size=64,
        moe=MoEConfig(n_experts=n_experts, top_k=top_k, d_expert=16,
                      n_shared=1, capacity_factor=cf,
                      router_aux_free=aux_free),
        dtype=jnp.float32)


@pytest.mark.parametrize("aux_free", [False, True])
@pytest.mark.parametrize("top_k", [1, 2, 4])
def test_sort_equals_dense_dispatch(top_k, aux_free):
    """The production sort-based dispatch must match the one-hot oracle."""
    cfg = _cfg(top_k=top_k, cf=8.0, aux_free=aux_free)  # cf big → no drops
    p = init_moe(jax.random.key(0), cfg)
    x = jax.random.normal(jax.random.key(1), (2, 8, 32), jnp.float32)
    y1, aux1 = moe_ffn_dense(p, x, cfg)
    y2, aux2 = moe_ffn_sort(p, x, cfg)
    np.testing.assert_allclose(np.asarray(y1), np.asarray(y2),
                               rtol=1e-4, atol=1e-4)
    np.testing.assert_allclose(np.asarray(aux1["load"]),
                               np.asarray(aux2["load"]), rtol=1e-6, atol=1e-6)


def test_capacity_drops_tokens():
    """With tiny capacity the two paths still agree and outputs shrink."""
    cfg_small = _cfg(cf=0.25)
    p = init_moe(jax.random.key(0), cfg_small)
    x = jax.random.normal(jax.random.key(1), (2, 16, 32), jnp.float32)
    y_small, _ = moe_ffn_dense(p, x, cfg_small)
    y_sort, _ = moe_ffn_sort(p, x, cfg_small)
    np.testing.assert_allclose(np.asarray(y_small), np.asarray(y_sort),
                               rtol=1e-4, atol=1e-4)
    cfg_big = _cfg(cf=8.0)
    y_big, _ = moe_ffn_dense(p, x, cfg_big)
    # dropping must change (reduce) routed mass for some tokens
    assert float(jnp.abs(y_big - y_small).max()) > 1e-6


def test_router_weights_normalized():
    cfg = _cfg(top_k=4)
    p = init_moe(jax.random.key(0), cfg)
    x = jax.random.normal(jax.random.key(1), (16, 32), jnp.float32)
    w, idx, aux = route(p["router"], x, cfg.moe)
    np.testing.assert_allclose(np.asarray(w.sum(-1)), np.ones(16),
                               rtol=1e-5, atol=1e-5)
    # top-k indices distinct per token
    for row in np.asarray(idx):
        assert len(set(row.tolist())) == len(row)
    assert float(aux["aux_loss"]) > 0


def test_aux_free_bias_steers_routing():
    """DeepSeek-V3 aux-free balancing: raising one expert's bias must
    attract more tokens to it without changing combine weights' source."""
    cfg = _cfg(aux_free=True, top_k=1, n_experts=4)
    p = init_moe(jax.random.key(0), cfg)
    x = jax.random.normal(jax.random.key(1), (64, 32), jnp.float32)
    _, idx0, _ = route(p["router"], x, cfg.moe)
    count0 = int((idx0 == 0).sum())
    p["router"]["b"] = p["router"]["b"].at[0].add(10.0)
    _, idx1, _ = route(p["router"], x, cfg.moe)
    count1 = int((idx1 == 0).sum())
    assert count1 > count0


def test_moe_grads_flow():
    cfg = _cfg()
    p = init_moe(jax.random.key(0), cfg)
    x = jax.random.normal(jax.random.key(1), (2, 8, 32), jnp.float32)

    def loss(p):
        y, _ = moe_ffn_sort(p, x, cfg)
        return (y ** 2).mean()

    g = jax.grad(loss)(p)
    gnorm = sum(float(jnp.abs(l).sum()) for l in jax.tree_util.tree_leaves(g))
    assert np.isfinite(gnorm) and gnorm > 0
