"""Primitive layers: norms, linear, embedding, RoPE.

Pure-functional: ``init_*`` returns a param pytree (dict), ``apply`` style
functions take (params, x).  All matmuls accumulate in fp32
(``preferred_element_type``) and keep activations in ``cfg.dtype``.
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp

from ..utils import shard


def init_linear(key, d_in: int, d_out: int, bias: bool = False, dtype=jnp.bfloat16,
                scale: float | None = None):
    scale = scale if scale is not None else d_in ** -0.5
    p = {"w": (jax.random.normal(key, (d_in, d_out), jnp.float32) * scale).astype(dtype)}
    if bias:
        p["b"] = jnp.zeros((d_out,), dtype)
    return p


def linear(p, x):
    y = jnp.einsum("...i,io->...o", x, p["w"],
                   preferred_element_type=jnp.float32).astype(x.dtype)
    if "b" in p:
        y = y + p["b"]
    return y


def init_norm(d: int, kind: str = "rmsnorm", dtype=jnp.bfloat16):
    p = {"scale": jnp.ones((d,), dtype)}
    if kind == "layernorm":
        p["bias"] = jnp.zeros((d,), dtype)
    return p


def rmsnorm(p, x, eps: float = 1e-6):
    xf = x.astype(jnp.float32)
    var = jnp.mean(xf * xf, axis=-1, keepdims=True)
    y = xf * jax.lax.rsqrt(var + eps)
    return (y * p["scale"].astype(jnp.float32)).astype(x.dtype)


def layernorm(p, x, eps: float = 1e-5):
    xf = x.astype(jnp.float32)
    mu = jnp.mean(xf, axis=-1, keepdims=True)
    var = jnp.var(xf, axis=-1, keepdims=True)
    y = (xf - mu) * jax.lax.rsqrt(var + eps)
    y = y * p["scale"].astype(jnp.float32) + p["bias"].astype(jnp.float32)
    return y.astype(x.dtype)


def apply_norm(p, x, kind: str = "rmsnorm"):
    return layernorm(p, x) if kind == "layernorm" else rmsnorm(p, x)


def init_embedding(key, vocab: int, d: int, dtype=jnp.bfloat16):
    return {"table": (jax.random.normal(key, (vocab, d), jnp.float32) * 0.02).astype(dtype)}


def embed(p, ids):
    y = jnp.take(p["table"], ids, axis=0)
    return shard(y, "batch", "seq", "embed")


def unembed(p, x):
    """Logits head (optionally tied): [..., d] -> [..., vocab] in fp32."""
    return jnp.einsum("...d,vd->...v", x, p["table"],
                      preferred_element_type=jnp.float32)


# -- RoPE ---------------------------------------------------------------------

def rope_freqs(d_head: int, theta: float, scaling=None):
    """Inverse frequencies [d_head/2]; with ``scaling`` (a ``YaRNConfig``)
    YaRN's blend: the extrapolated frequencies above the high correction
    dim, the interpolated ones (divided by ``factor``) below the low one,
    and a linear ramp between (DeepseekV3YarnRotaryEmbedding)."""
    extra = 1.0 / (theta ** (jnp.arange(0, d_head, 2, dtype=jnp.float32) / d_head))
    if scaling is None:
        return extra
    low, high = yarn_correction_range(scaling, d_head, theta)
    high = high + 0.001 if low == high else high
    ramp = jnp.clip((jnp.arange(d_head // 2, dtype=jnp.float32) - low)
                    / (high - low), 0.0, 1.0)
    return extra / scaling.factor * ramp + extra * (1.0 - ramp)


def yarn_correction_range(scaling, d_head: int, theta: float) -> tuple[int, int]:
    """The dims between which YaRN ramps from extrapolation to
    interpolation, for ``beta_fast`` and ``beta_slow`` rotations over the
    original context."""
    def dim(rotations):
        return (d_head * math.log(scaling.original_max_position_embeddings
                                  / (rotations * 2 * math.pi))
                / (2 * math.log(theta)))
    return (max(math.floor(dim(scaling.beta_fast)), 0),
            min(math.ceil(dim(scaling.beta_slow)), d_head - 1))


def yarn_mscale(factor: float, mscale: float) -> float:
    """YaRN's attention temperature: 0.1 * mscale * ln(factor) + 1."""
    return 1.0 if factor <= 1 else 0.1 * mscale * math.log(factor) + 1.0


def apply_rope(x, positions, theta: float = 1e4, scaling=None):
    """x: [..., seq, heads, d_head]; positions: [..., seq] (int).  With
    ``scaling`` (YaRN) the frequencies are blended and cos/sin carry
    mscale(mscale) / mscale(mscale_all_dim)."""
    d = x.shape[-1]
    freqs = rope_freqs(d, theta, scaling)              # [d/2]
    angles = positions[..., None].astype(jnp.float32) * freqs  # [..., seq, d/2]
    cos = jnp.cos(angles)[..., None, :]                # [..., seq, 1, d/2]
    sin = jnp.sin(angles)[..., None, :]
    if scaling is not None:
        m = (yarn_mscale(scaling.factor, scaling.mscale)
             / yarn_mscale(scaling.factor, scaling.mscale_all_dim))
        if m != 1.0:
            cos, sin = cos * m, sin * m
    x1, x2 = jnp.split(x.astype(jnp.float32), 2, axis=-1)
    out = jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], axis=-1)
    return out.astype(x.dtype)


def gelu(x):
    return jax.nn.gelu(x, approximate=True)
