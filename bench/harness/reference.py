"""Building blocks of the plain references in ``architectures/``: float32
matmuls at ``highest`` precision, and the control's float8 rounding.
Nothing here imports the program.

``mode`` is ``"f32"`` (the reference) or ``"fp8"`` (every matmul operand
rounded to float8 e4m3 with a scale per row of the contraction, the
control: the precision below the configurations' bfloat16).  Attention
runs over one sequence with queries in blocks, so a reference fits beside
nothing else on the chip.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

HI = jax.lax.Precision.HIGHEST
F8_MAX = 448.0          # largest finite float8_e4m3fn
Q_BLOCK = 1024          # query rows per attention block


def q8(x, axis):
    """Round ``x`` to float8 e4m3 with one scale per slice along ``axis``
    (the contraction axis), back in float32."""
    s = jnp.max(jnp.abs(x), axis=axis, keepdims=True) / F8_MAX
    s = jnp.where(s > 0, s, 1.0)
    return (x / s).astype(jnp.float8_e4m3fn).astype(jnp.float32) * s


def mm(eq, a, b, mode, a_axis, b_axis):
    if mode == "fp8":
        a, b = q8(a, a_axis), q8(b, b_axis)
    return jnp.einsum(eq, a, b, precision=HI)


def rms(x, scale, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * scale


def rope(x, pos, theta):
    """x [S, H, D]; rotate-half convention, frequencies theta^(-2i/D)."""
    d = x.shape[-1]
    inv = theta ** (-jnp.arange(0, d, 2, dtype=jnp.float32) / d)
    ang = pos[:, None].astype(jnp.float32) * inv          # [S, D/2]
    cos, sin = jnp.cos(ang)[:, None], jnp.sin(ang)[:, None]
    x1, x2 = x[..., : d // 2], x[..., d // 2:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def linear(h, p, mode):
    """``h @ p["w"] (+ p["b"])`` with ``w`` [d_in, d_out]."""
    y = mm("sk,kn->sn", h, p["w"].astype(jnp.float32), mode, -1, 0)
    if "b" in p:
        y = y + p["b"].astype(jnp.float32)
    return y


def attention(q, k, v, mode):
    """Causal GQA. q [S,H,D], k/v [S,KVH,D] -> [S, H*D]."""
    s, h, d = q.shape
    kvh = k.shape[1]
    g = h // kvh
    out = []
    for q0 in range(0, s, Q_BLOCK):
        qb = q[q0:q0 + Q_BLOCK].reshape(-1, kvh, g, d)      # [b,KVH,G,D]
        sc = mm("bkgd,tkd->kgbt", qb, k, mode, -1, -1) * d ** -0.5
        qpos = q0 + jnp.arange(qb.shape[0])
        mask = jnp.arange(s)[None, :] <= qpos[:, None]     # [b, T]
        sc = jnp.where(mask[None, None], sc, -jnp.inf)
        p = jax.nn.softmax(sc, axis=-1)
        o = mm("kgbt,tkd->bkgd", p, v, mode, -1, 0)
        out.append(o.reshape(qb.shape[0], h * d))
    return jnp.concatenate(out, 0)


def pad_to(n: int, block: int) -> int:
    return -(-n // block) * block
