"""Architecture ``dense_lm``: a dense decoder LM (RMSNorm, rotary
positions, grouped-query causal attention, SwiGLU, tied or untied head;
MiniCPM's embedding, residual and logit scales).

An architecture file gives the harness everything that depends on the
model's structure, and nothing that depends on a cell:

- ``dims(name, config)``: the sizes, from a configuration file;
- ``program_config(dims)``: the program's ``ModelConfig``, refusing what
  the program cannot run;
- ``export(mcfg, batch, seq, params)``: the program's operator graph of
  one forward, for graph cells;
- ``logits(params, tokens, positions, dims, mode)``: the plain reference,
  written from the published description.  It imports nothing of the
  program and reads only weights the benchmark made, which arrive in the
  program's layout (``embed.table``, ``stacks[0]`` stacked over layers,
  ``final_norm``), the program's interface;
- the counts from shapes that the metric readers use.

``mode`` is ``"f32"`` (float32 at ``highest`` precision, the reference)
or ``"fp8"`` (the control, see ``harness/reference.py``).
"""
from __future__ import annotations

import functools
from dataclasses import dataclass

import jax
import jax.numpy as jnp
import numpy as np

from harness.counts import BF16
from harness.reference import attention, linear, mm, pad_to, rms, rope

# The program's RMSNorm epsilon is fixed (models/layers.py: rmsnorm).
PROGRAM_RMS_EPS = 1e-6


@dataclass(frozen=True)
class Dims:
    name: str
    layers: int
    d_model: int
    heads: int
    kv_heads: int
    head_dim: int
    d_ff: int
    vocab: int
    qkv_bias: bool
    tied: bool
    rope_theta: float
    rms_eps: float
    residual_scale: float
    emb_scale: float
    logit_scale: float
    max_positions: int


def dims(name: str, config: dict) -> Dims:
    """Sizes as run: the file's published keys, with its ``departures``
    (keys the program runs at another value) laid over them."""
    c = {**config, **config.get("departures", {})}
    if c.get("hidden_act", "silu") != "silu":
        raise ValueError(f"{name}: hidden_act must be silu (SwiGLU)")
    layers = int(c["num_hidden_layers"])
    d = int(c["hidden_size"])
    heads = int(c["num_attention_heads"])
    scale_depth = c.get("scale_depth")
    base = c.get("dim_model_base")
    return Dims(
        name=name, layers=layers, d_model=d, heads=heads,
        kv_heads=int(c.get("num_key_value_heads", heads)),
        head_dim=int(c.get("head_dim", d // heads)),
        d_ff=int(c["intermediate_size"]), vocab=int(c["vocab_size"]),
        qkv_bias=bool(c.get("attention_bias", False)),
        tied=bool(c.get("tie_word_embeddings", False)),
        rope_theta=float(c.get("rope_theta", 10000.0)),
        rms_eps=float(c.get("rms_norm_eps", PROGRAM_RMS_EPS)),
        residual_scale=(float(scale_depth) / layers ** 0.5
                        if scale_depth is not None else 1.0),
        emb_scale=float(c.get("scale_emb", 1.0)),
        logit_scale=(float(base) / d if base is not None else 1.0),
        max_positions=int(c["max_position_embeddings"]))


def program_config(dm: Dims, dtype=jnp.bfloat16):
    """The program's ``ModelConfig`` for these sizes.  Raises where the
    sizes ask for arithmetic the program does not have."""
    from repro.configs.base import ModelConfig

    if dm.emb_scale != 1.0 or dm.logit_scale != 1.0:
        raise ValueError(f"{dm.name}: the program applies no embedding or "
                         "logit scale; list scale_emb 1 and dim_model_base "
                         "= hidden_size under departures")
    if dm.rms_eps != PROGRAM_RMS_EPS:
        raise ValueError(f"{dm.name}: the program's RMSNorm epsilon is "
                         f"{PROGRAM_RMS_EPS}; the file runs {dm.rms_eps}")
    return ModelConfig(
        name=dm.name, family="dense", n_layers=dm.layers, d_model=dm.d_model,
        n_heads=dm.heads, n_kv_heads=dm.kv_heads, d_ff=dm.d_ff,
        vocab_size=dm.vocab,
        d_head=dm.head_dim if dm.head_dim != dm.d_model // dm.heads else None,
        qkv_bias=dm.qkv_bias, tie_embeddings=dm.tied,
        rope_theta=dm.rope_theta, max_seq_len=dm.max_positions,
        residual_scale=dm.residual_scale, dtype=dtype, source="bench")


def export(mcfg, batch: int, seq: int, params):
    from repro.models.opgraph_export import build_lm_opgraph
    return build_lm_opgraph(mcfg, batch=batch, seq=seq, params=params)


# --- the reference -----------------------------------------------------

@functools.partial(jax.jit, static_argnames=("dims", "mode"))
def final_hidden(params, tokens, *, dims, mode):
    """tokens [S] int32 -> final normed hidden states [S, d] float32."""
    f32 = jnp.float32
    x = params["embed"]["table"][tokens].astype(f32) * dims.emb_scale
    pos = jnp.arange(tokens.shape[0])
    rs = dims.residual_scale

    def layer(x, p):
        p = jax.tree_util.tree_map(lambda a: a.astype(f32), p)
        a = p["attn"]
        h = rms(x, p["norm1"]["scale"], dims.rms_eps)
        q = linear(h, a["wq"], mode).reshape(-1, dims.heads, dims.head_dim)
        k = linear(h, a["wk"], mode).reshape(-1, dims.kv_heads, dims.head_dim)
        v = linear(h, a["wv"], mode).reshape(-1, dims.kv_heads, dims.head_dim)
        q, k = rope(q, pos, dims.rope_theta), rope(k, pos, dims.rope_theta)
        x = x + linear(attention(q, k, v, mode), a["wo"], mode) * rs
        h = rms(x, p["norm2"]["scale"], dims.rms_eps)
        f = p["ffn"]
        u = jax.nn.silu(linear(h, f["gate"], mode)) * linear(h, f["up"], mode)
        return x + linear(u, f["down"], mode) * rs, None

    x, _ = jax.lax.scan(layer, x, params["stacks"][0])
    return rms(x, params["final_norm"]["scale"].astype(f32), dims.rms_eps)


@functools.partial(jax.jit, static_argnames=("dims", "mode"))
def logits_at(params, hidden, *, dims, mode):
    """hidden [N, d] float32 -> logits [N, V] float32."""
    head = params["embed" if dims.tied else "head"]["table"].astype(jnp.float32)
    return mm("nd,vd->nv", hidden, head, mode, -1, -1) * dims.logit_scale


def logits(params, tokens, positions, dims, mode="f32"):
    """Logits [len(positions), V] of one sequence ``tokens`` (a list or
    array of ids) at ``positions``.  The sequence is padded at its end to a
    multiple of 512, which a causal model never reads back, so that few
    lengths compile."""
    toks = np.zeros(pad_to(len(tokens), 512), np.int32)
    toks[: len(tokens)] = np.asarray(tokens, np.int32)
    h = final_hidden(params, jnp.asarray(toks), dims=dims, mode=mode)
    idx = np.zeros(pad_to(len(positions), 128), np.int32)
    idx[: len(positions)] = np.asarray(positions, np.int32)
    out = logits_at(params, h[jnp.asarray(idx)], dims=dims, mode=mode)
    return out[: len(positions)]


# --- counts from shapes (see harness/counts.py for the conventions) ----

def layer_matmul_params(dm) -> int:
    """Weights one token multiplies by in one layer (no norms, biases)."""
    q = dm.heads * dm.head_dim
    kv = dm.kv_heads * dm.head_dim
    return dm.d_model * (q + 2 * kv) + q * dm.d_model + 3 * dm.d_model * dm.d_ff


def param_count(dm) -> int:
    """Every parameter: embedding (+ untied head), layers, final norm."""
    q = dm.heads * dm.head_dim
    kv = dm.kv_heads * dm.head_dim
    per_layer = layer_matmul_params(dm) + 2 * dm.d_model
    if dm.qkv_bias:
        per_layer += q + 2 * kv
    emb = dm.vocab * dm.d_model * (1 if dm.tied else 2)
    return emb + dm.layers * per_layer + dm.d_model


def kv_bytes_per_token(dm) -> int:
    return dm.layers * 2 * dm.kv_heads * dm.head_dim * BF16


def attention_flops(dm, pairs: int) -> int:
    """Score and context matmuls over ``pairs`` (query, key) pairs, all
    layers: 2 FLOPs x head_dim for each of QK^T and PV, per head."""
    return dm.layers * dm.heads * 4 * dm.head_dim * pairs


def dense_flops(dm, tokens: int) -> int:
    """Every weight matmul, the head included, for ``tokens`` tokens."""
    return 2 * tokens * (dm.layers * layer_matmul_params(dm)
                         + dm.d_model * dm.vocab)


def forward_flops(dm, batch: int, seq: int) -> int:
    """One causal forward over ``batch`` rows of ``seq`` tokens, with the
    head at every position (the captured graph returns all logits)."""
    return (dense_flops(dm, batch * seq)
            + attention_flops(dm, batch * seq * (seq + 1) // 2))


def prefill_flops(dm, n: int) -> int:
    """Admission prefill of one ``n``-token prompt; the head runs at every
    position in the program, but only the last one is needed."""
    return (2 * n * dm.layers * layer_matmul_params(dm)
            + 2 * dm.d_model * dm.vocab + attention_flops(dm, n * (n + 1) // 2))


def decode_token_flops(dm, ctx: int) -> int:
    """One decoded token attending over ``ctx`` positions (itself
    included)."""
    return dense_flops(dm, 1) + attention_flops(dm, ctx)


def paged_decode_call(dm, contexts) -> tuple[int, int]:
    """(FLOPs, bytes) of one layer's paged-decode attention over the
    batch: q and out of every row, and the K and V of each row's
    ``contexts[i]`` positions."""
    rows = len(contexts)
    pairs = int(sum(contexts))
    flops = dm.heads * 4 * dm.head_dim * pairs
    nbytes = BF16 * (2 * rows * dm.heads * dm.head_dim
                     + 2 * pairs * dm.kv_heads * dm.head_dim)
    return flops, nbytes
