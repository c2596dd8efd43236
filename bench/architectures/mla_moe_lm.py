"""Architecture ``mla_moe_lm``: a DeepSeek-V3-style decoder (multi-head
latent attention with YaRN rotary scaling; leading dense-FFN layers, then
mixture-of-experts layers with a shared expert and sigmoid, group-limited
routing; untied head), run as one chip's share of an expert-parallel
deployment: the layer holds some of the routed experts and computes their
part of the result, the router keeps every expert.

The interface is ``dense_lm.py``'s: ``dims``, ``program_config``,
``logits`` (the plain reference, ``mode`` "f32" or "fp8", with the
positions whose held-expert routing sits on a tie left out of the check:
see ``logits``) and the counts from shapes.  The reference is written from the published description
(arXiv:2412.19437 and the model's published modelling code) and imports
nothing of the program.  It reads only the weights the benchmark made, in
the program's layout: ``embed.table``, ``head.table``, ``stacks[0]`` (the
dense layers) and ``stacks[1]`` (the MoE layers) stacked over layers,
``final_norm``.  Its attention is MLA in the non-absorbed form: queries
from ``W_qb`` of the normed ``W_qa x``, keys and values up-projected per
head from the normed latent by ``W_kb`` and ``W_vb``, one rope key shared
by every head.  The program attends in the absorbed form over the latent
cache; the two are the same function.
"""
from __future__ import annotations

import functools
import math
from dataclasses import dataclass

import jax
import jax.numpy as jnp
import numpy as np

from harness.counts import BF16
from harness.main import log
from harness.reference import linear, mm, pad_to, rms

# The program's RMSNorm epsilon is fixed (models/layers.py: rmsnorm).
PROGRAM_RMS_EPS = 1e-6
Q_ROWS = 128            # query rows per attention block: 128 heads x 9k keys
# How far from a routing tie a held expert's pick must lie to count as
# decided (see ``logits``).  On one v5e, every held-expert pick on which
# the bf16 program left the reference, over 12 seeds of the cell's longest
# request, lay within 0.006 of a tie at its first layer
# (scripts/routing_ties.py).
ROUTING_DELTA = 0.006


@dataclass(frozen=True)
class Dims:
    name: str
    layers: int
    dense_layers: int
    d_model: int
    heads: int
    q_rank: int
    kv_rank: int
    nope: int
    rope: int
    v_dim: int
    d_ff: int
    d_expert: int
    n_experts: int          # the router's width: every routed expert
    experts_held: int       # routed experts whose weights this chip holds
    expert_offset: int
    top_k: int
    n_group: int
    topk_group: int
    routed_scale: float
    n_shared: int
    vocab: int
    rope_theta: float
    yarn: tuple             # factor, original positions, beta_fast,
                            # beta_slow, mscale, mscale_all_dim
    rms_eps: float
    max_positions: int

    @property
    def moe_layers(self) -> int:
        return self.layers - self.dense_layers


def dims(name: str, config: dict) -> Dims:
    """Sizes as run: the file's published keys, with its ``departures``
    laid over them.  ``n_routed_experts`` counts the experts held here; the
    router's width is ``expert_parallel.router_experts``."""
    c = {**config, **config.get("departures", {})}
    ep = c["expert_parallel"]
    ys = c["rope_scaling"]
    problems = []
    if c.get("hidden_act") != "silu":
        problems.append("hidden_act must be silu (SwiGLU)")
    if c.get("scoring_func") != "sigmoid" or c.get("topk_method") != "noaux_tc":
        problems.append("routing must be sigmoid scores with noaux_tc selection")
    if not c.get("norm_topk_prob"):
        problems.append("norm_topk_prob must be true")
    if c.get("moe_layer_freq", 1) != 1:
        problems.append("every layer after the dense ones must be MoE")
    if int(c.get("num_nextn_predict_layers", 0)):
        problems.append("multi-token prediction layers are not run; list "
                        "num_nextn_predict_layers 0 under departures")
    if c.get("quantization_config"):
        problems.append("the program runs bfloat16 weights; list "
                        "quantization_config null under departures")
    if ys.get("type") != "yarn":
        problems.append("rope_scaling must be yarn")
    if c.get("attention_bias") or c.get("tie_word_embeddings"):
        problems.append("no attention bias and an untied head")
    if problems:
        raise ValueError(f"{name}: " + "; ".join(problems))
    return Dims(
        name=name, layers=int(c["num_hidden_layers"]),
        dense_layers=int(c["first_k_dense_replace"]),
        d_model=int(c["hidden_size"]), heads=int(c["num_attention_heads"]),
        q_rank=int(c["q_lora_rank"]), kv_rank=int(c["kv_lora_rank"]),
        nope=int(c["qk_nope_head_dim"]), rope=int(c["qk_rope_head_dim"]),
        v_dim=int(c["v_head_dim"]), d_ff=int(c["intermediate_size"]),
        d_expert=int(c["moe_intermediate_size"]),
        n_experts=int(ep["router_experts"]),
        experts_held=int(c["n_routed_experts"]),
        expert_offset=int(ep["held_offset"]),
        top_k=int(c["num_experts_per_tok"]), n_group=int(c["n_group"]),
        topk_group=int(c["topk_group"]),
        routed_scale=float(c["routed_scaling_factor"]),
        n_shared=int(c["n_shared_experts"]), vocab=int(c["vocab_size"]),
        rope_theta=float(c["rope_theta"]),
        yarn=(float(ys["factor"]), int(ys["original_max_position_embeddings"]),
              float(ys["beta_fast"]), float(ys["beta_slow"]),
              float(ys["mscale"]), float(ys["mscale_all_dim"])),
        rms_eps=float(c["rms_norm_eps"]),
        max_positions=int(c["max_position_embeddings"]))


def program_config(dm: Dims, dtype=jnp.bfloat16):
    """The program's ``ModelConfig`` for these sizes.  Raises where the
    sizes ask for what the program does not have."""
    from repro.configs.base import MLAConfig, ModelConfig, MoEConfig, YaRNConfig

    if dm.rms_eps != PROGRAM_RMS_EPS:
        raise ValueError(f"{dm.name}: the program's RMSNorm epsilon is "
                         f"{PROGRAM_RMS_EPS}; the file runs {dm.rms_eps}")
    if dm.n_experts % dm.n_group or dm.n_experts // dm.n_group < 2:
        raise ValueError(f"{dm.name}: {dm.n_experts} experts do not fall "
                         f"into {dm.n_group} groups of at least 2")
    if not 0 <= dm.expert_offset <= dm.n_experts - dm.experts_held:
        raise ValueError(f"{dm.name}: experts held lie outside the router")
    if not 0 < dm.dense_layers < dm.layers:
        raise ValueError(f"{dm.name}: needs dense and MoE layers both")
    factor, orig, fast, slow, ms, ms_all = dm.yarn
    return ModelConfig(
        name=dm.name, family="moe", n_layers=dm.layers, d_model=dm.d_model,
        n_heads=dm.heads, n_kv_heads=dm.heads, d_ff=dm.d_ff,
        vocab_size=dm.vocab,
        mla=MLAConfig(q_lora_rank=dm.q_rank, kv_lora_rank=dm.kv_rank,
                      qk_nope_head_dim=dm.nope, qk_rope_head_dim=dm.rope,
                      v_head_dim=dm.v_dim),
        moe=MoEConfig(n_experts=dm.n_experts, top_k=dm.top_k,
                      d_expert=dm.d_expert, n_shared=dm.n_shared,
                      router_aux_free=True, n_group=dm.n_group,
                      topk_group=dm.topk_group, routed_scale=dm.routed_scale,
                      experts_held=dm.experts_held,
                      expert_offset=dm.expert_offset),
        dense_prefix=dm.dense_layers, rope_theta=dm.rope_theta,
        rope_scaling=YaRNConfig(factor=factor,
                                original_max_position_embeddings=orig,
                                beta_fast=fast, beta_slow=slow, mscale=ms,
                                mscale_all_dim=ms_all),
        max_seq_len=dm.max_positions, tie_embeddings=False, dtype=dtype,
        source="bench")


# --- the reference -----------------------------------------------------

def yarn_mscale(factor: float, mscale: float) -> float:
    return 1.0 if factor <= 1 else 0.1 * mscale * math.log(factor) + 1.0


def yarn_inv_freq(dm: Dims) -> np.ndarray:
    """DeepseekV3YarnRotaryEmbedding's frequencies: the extrapolated ones
    ``theta^(-2i/D)`` above the high correction dim, divided by ``factor``
    below the low one, a linear ramp between."""
    factor, orig, fast, slow, _, _ = dm.yarn
    d, base = dm.rope, dm.rope_theta

    def corr(rot):
        return d * math.log(orig / (rot * 2 * math.pi)) / (2 * math.log(base))

    low, high = max(math.floor(corr(fast)), 0), min(math.ceil(corr(slow)), d - 1)
    if low == high:
        high += 0.001
    extra = 1.0 / base ** (np.arange(0, d, 2, dtype=np.float64) / d)
    ramp = np.clip((np.arange(d // 2) - low) / (high - low), 0, 1)
    return (extra / factor * ramp + extra * (1 - ramp)).astype(np.float32)


def softmax_scale(dm: Dims) -> float:
    """1/sqrt(nope + rope), times mscale(mscale_all_dim)^2."""
    factor, *_, ms_all = dm.yarn
    return (dm.nope + dm.rope) ** -0.5 * yarn_mscale(factor, ms_all) ** 2


def yarn_rope(x, pos, dm: Dims):
    """x [S, H, rope]: rotate-half rotary embedding at YaRN's frequencies,
    cos and sin times mscale(mscale) / mscale(mscale_all_dim).  The pair
    layout is the program's (halves; see the configuration's
    ``assumed``)."""
    factor, _, _, _, ms, ms_all = dm.yarn
    m = yarn_mscale(factor, ms) / yarn_mscale(factor, ms_all)
    ang = pos[:, None].astype(jnp.float32) * jnp.asarray(yarn_inv_freq(dm))
    cos, sin = jnp.cos(ang)[:, None] * m, jnp.sin(ang)[:, None] * m
    h = dm.rope // 2
    x1, x2 = x[..., :h], x[..., h:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def mla(x, a, pos, dm: Dims, mode):
    """Causal multi-head latent attention, non-absorbed.  x [S, d]."""
    s = x.shape[0]
    h = dm.heads
    cq = rms(linear(x, a["wq_a"], mode), a["q_norm"]["scale"], dm.rms_eps)
    q = linear(cq, a["wq_b"], mode).reshape(s, h, dm.nope + dm.rope)
    kv = linear(x, a["wkv_a"], mode)
    c_kv = rms(kv[:, : dm.kv_rank], a["kv_norm"]["scale"], dm.rms_eps)
    k_nope = linear(c_kv, a["wk_b"], mode).reshape(s, h, dm.nope)
    v = linear(c_kv, a["wv_b"], mode).reshape(s, h, dm.v_dim)
    q_pe = yarn_rope(q[..., dm.nope:], pos, dm)                 # [S, H, r]
    k_pe = yarn_rope(kv[:, None, dm.kv_rank:], pos, dm)[:, 0]   # [S, r]
    q_nope = q[..., : dm.nope]
    scale = softmax_scale(dm)
    nb = s // Q_ROWS

    def block(i):
        q0 = i * Q_ROWS
        qn = jax.lax.dynamic_slice_in_dim(q_nope, q0, Q_ROWS)
        qp = jax.lax.dynamic_slice_in_dim(q_pe, q0, Q_ROWS)
        sc = (mm("bhd,thd->hbt", qn, k_nope, mode, -1, -1)
              + mm("bhd,td->hbt", qp, k_pe, mode, -1, -1)) * scale
        mask = jnp.arange(s)[None, :] <= (q0 + jnp.arange(Q_ROWS))[:, None]
        p = jax.nn.softmax(jnp.where(mask[None], sc, -jnp.inf), axis=-1)
        return mm("hbt,thd->bhd", p, v, mode, -1, 0).reshape(Q_ROWS, -1)

    out = jax.lax.map(block, jnp.arange(nb)).reshape(s, h * dm.v_dim)
    return linear(out, a["wo"], mode)


def swiglu(h, f, mode):
    return linear(jax.nn.silu(linear(h, f["gate"], mode))
                  * linear(h, f["up"], mode), f["down"], mode)


def route(h, router, dm: Dims, mode):
    """Routing weights [S, E_held] of the held experts (0 where a token did
    not pick one), from DeepSeek-V3's gate: sigmoid scores, selection by
    scores + correction bias within the ``topk_group`` best of ``n_group``
    groups (a group scores its two best), weights the chosen un-biased
    scores normalised, times ``routed_scaling_factor``.  Also returns the
    selection scores [S, E]."""
    s = h.shape[0]
    scores = jax.nn.sigmoid(mm("sd,de->se", h, router["w"], mode, -1, 0))
    select = scores + router["b"]
    sel = select.reshape(s, dm.n_group, -1)
    group = jax.lax.top_k(sel, 2)[0].sum(-1)                    # [S, G]
    gmask = jnp.zeros_like(group, bool).at[
        jnp.arange(s)[:, None], jax.lax.top_k(group, dm.topk_group)[1]].set(True)
    sel = jnp.where(gmask[..., None], sel, -jnp.inf).reshape(s, -1)
    idx = jax.lax.top_k(sel, dm.top_k)[1]                       # [S, k]
    w = jnp.take_along_axis(scores, idx, -1)
    w = w / w.sum(-1, keepdims=True) * dm.routed_scale
    held = idx[..., None] == dm.expert_offset + jnp.arange(dm.experts_held)
    return jnp.einsum("sk,skh->sh", w, held.astype(jnp.float32)), select


def held_picks(select, dm: Dims, delta: float = 0.0):
    """Which held experts a token picks, from its selection scores
    ``select`` [..., E]: (surely [..., H], maybe [..., H]).  An expert is
    surely picked if it is picked under every change of each selection
    score by at most ``delta``, maybe picked if under some; at ``delta`` 0
    both are the routing's own picks.  A group's score (its two best) moves
    by at most 2 ``delta``; a held expert is surely picked if its group is
    surely among the ``topk_group`` best and fewer than ``top_k`` experts of
    the groups that may be chosen could score at least as high, maybe
    picked if its group may be chosen and fewer than ``top_k`` experts of
    the groups surely chosen surely score higher."""
    per = dm.n_experts // dm.n_group
    g = jax.lax.top_k(select.reshape(*select.shape[:-1], dm.n_group, per),
                      2)[0].sum(-1)                                # [..., G]
    a, b = g[..., :, None] - 2 * delta, g[..., None, :] + 2 * delta
    g_sure = (b >= a).sum(-1) - 1 < dm.topk_group        # itself counted once
    g_may = (g[..., None, :] - 2 * delta > g[..., :, None] + 2 * delta
             ).sum(-1) < dm.topk_group
    held = dm.expert_offset + np.arange(dm.experts_held)
    mine = held[:, None] != np.arange(dm.n_experts)                # [H, E]
    s_h = select[..., held, None]                                   # [..., H, 1]
    e = select[..., None, :]                                        # [..., 1, E]
    pool_may = jnp.repeat(g_may, per, -1)[..., None, :] & mine
    pool_sure = jnp.repeat(g_sure, per, -1)[..., None, :] & mine
    surely = g_sure[..., held // per] & (
        ((e + delta >= s_h - delta) & pool_may).sum(-1) < dm.top_k)
    maybe = g_may[..., held // per] & (
        ((e - delta > s_h + delta) & pool_sure).sum(-1) < dm.top_k)
    return surely, maybe


def moe(h, f, dm: Dims, mode):
    """The held experts' part of the routed result, plus the shared
    expert, and the selection scores.  Every token runs through every held
    expert, weighted by its routing weight (0 for an expert it did not
    pick)."""
    w, select = route(h, f["router"], dm, mode)                  # [S, H]

    def one(y, ex):
        e, wj = ex                      # one held expert's [d, F] weights
        e = {k: {"w": e[k]} for k in ("gate", "up", "down")}
        return y + wj[:, None] * swiglu(h, e, mode), None

    y, _ = jax.lax.scan(one, swiglu(h, f["shared"], mode),
                        (f["experts"], w.T))
    return y, select


@functools.partial(jax.jit, static_argnames=("dims", "mode"))
def final_hidden(params, tokens, *, dims, mode):
    """tokens [S] int32 (S a multiple of ``Q_ROWS``) -> (final normed
    hidden states [S, d] float32, each MoE layer's selection scores
    [L_moe, S, E])."""
    f32 = jnp.float32
    x = params["embed"]["table"][tokens].astype(f32)
    pos = jnp.arange(tokens.shape[0])

    def layer(kind):
        def step(x, p):
            p = jax.tree_util.tree_map(lambda a: a.astype(f32), p)
            x = x + mla(rms(x, p["norm1"]["scale"], dims.rms_eps), p["attn"],
                        pos, dims, mode)
            h = rms(x, p["norm2"]["scale"], dims.rms_eps)
            if kind == "dense":
                return x + swiglu(h, p["ffn"], mode), None
            y, select = moe(h, p["ffn"], dims, mode)
            return x + y, select
        return step

    x, _ = jax.lax.scan(layer("dense"), x, params["stacks"][0])
    x, select = jax.lax.scan(layer("moe"), x, params["stacks"][1])
    return rms(x, params["final_norm"]["scale"].astype(f32), dims.rms_eps), select


@functools.partial(jax.jit, static_argnames=("dims", "delta"))
def undecided(select, *, dims, delta):
    """select [L_moe, N, E] -> bool [N]: positions at which some MoE layer's
    held picks could change under a change of its selection scores by at
    most ``delta`` (:func:`held_picks`)."""
    surely, maybe = held_picks(select, dims, delta)
    return (surely != maybe).any(-1).any(0)


@functools.partial(jax.jit, static_argnames=("dims", "mode"))
def logits_at(params, hidden, *, dims, mode):
    """hidden [N, d] float32 -> logits [N, V] float32 (the untied head)."""
    head = params["head"]["table"].astype(jnp.float32)
    return mm("nd,vd->nv", hidden, head, mode, -1, -1)


def reference(params, tokens, positions, dims, mode="f32"):
    """(logits [P, V], selection scores [L_moe, P, E]) of one sequence
    ``tokens`` at its P ``positions``.  The sequence is padded at its end to
    a multiple of 512, which a causal model never reads back, so that few
    lengths compile."""
    toks = np.zeros(pad_to(len(tokens), 512), np.int32)
    toks[: len(tokens)] = np.asarray(tokens, np.int32)
    h, select = final_hidden(params, jnp.asarray(toks), dims=dims, mode=mode)
    idx = np.zeros(pad_to(len(positions), 128), np.int32)
    idx[: len(positions)] = np.asarray(positions, np.int32)
    idx = jnp.asarray(idx)
    out = logits_at(params, h[idx], dims=dims, mode=mode)
    n = len(positions)
    return out[:n], select[:, idx][:, :n]


def logits(params, tokens, positions, dims, mode="f32"):
    """The logits the benchmark's check reads: :func:`reference`'s, except
    that in ``"f32"`` every logit is 0 at a position whose held-expert
    picks are undecided within ``ROUTING_DELTA``, so that the check's gap
    reads 0 there.  In bfloat16 the router's input strays from float32,
    as bfloat16 rounding of the matmul operands alone makes it stray, and
    where a held expert's pick turns on less than that, the program may
    rightly take the other side: one held expert carries about 2.5/8 of
    the routed output, which moves the served token's logit by far more
    than rounding elsewhere does.  The positions left out are chosen from
    the reference's own scores, the same for the program and the
    control."""
    out, select = reference(params, tokens, positions, dims, mode)
    if mode != "f32":
        return out
    tie = undecided(select, dims=dims, delta=ROUTING_DELTA)
    log(f"{int(tie.sum())} of {len(positions)} positions left out: a held "
        f"expert's pick lies within {ROUTING_DELTA} of a routing tie")
    return jnp.where(tie[:, None], 0.0, out)


# --- counts from shapes (see harness/counts.py for the conventions) ----

def mla_params(dm) -> int:
    """Matmul weights of one latent-attention layer (no norms)."""
    h = dm.heads
    return (dm.d_model * dm.q_rank + dm.q_rank * h * (dm.nope + dm.rope)
            + dm.d_model * (dm.kv_rank + dm.rope)
            + dm.kv_rank * h * (dm.nope + dm.v_dim) + h * dm.v_dim * dm.d_model)


def expert_params(dm) -> int:
    """One routed (or shared) expert's SwiGLU weights."""
    return 3 * dm.d_model * dm.d_expert


def router_params(dm) -> int:
    return dm.d_model * dm.n_experts + dm.n_experts


def param_count(dm) -> int:
    """Every parameter held here: embedding and head over the vocabulary
    slice, the layers (norms included), the final norm."""
    norms = 2 * dm.d_model + dm.q_rank + dm.kv_rank
    dense = mla_params(dm) + norms + 3 * dm.d_model * dm.d_ff
    moe_layer = (mla_params(dm) + norms + router_params(dm)
                 + (dm.n_shared + dm.experts_held) * expert_params(dm))
    return (2 * dm.vocab * dm.d_model + dm.dense_layers * dense
            + dm.moe_layers * moe_layer + dm.d_model)


def kv_bytes_per_token(dm) -> int:
    """The latent cache: ``kv_rank + rope`` values a layer."""
    return dm.layers * (dm.kv_rank + dm.rope) * BF16


def token_matmul_flops(dm) -> int:
    """Weight matmuls of one token through every layer, without the head:
    routed experts at their expected share here, k x held / E a token."""
    routed = dm.top_k * dm.experts_held / dm.n_experts
    per_moe = (dm.d_model * dm.n_experts
               + (dm.n_shared + routed) * expert_params(dm))
    return int(2 * (dm.layers * mla_params(dm)
                    + dm.dense_layers * 3 * dm.d_model * dm.d_ff
                    + dm.moe_layers * per_moe))


def prefill_flops(dm, n: int) -> int:
    """Admission prefill of one ``n``-token prompt: every token through the
    layers, the head at the last position, and causal attention in the
    non-absorbed form (QK over nope + rope, PV over v, per head)."""
    pairs = n * (n + 1) // 2
    attn = dm.layers * dm.heads * 2 * (dm.nope + dm.rope + dm.v_dim) * pairs
    return n * token_matmul_flops(dm) + 2 * dm.d_model * dm.vocab + attn


def decode_token_flops(dm, ctx: int) -> int:
    """One decoded token attending over ``ctx`` latent positions (itself
    included), in the absorbed form a latent cache asks for."""
    attn = dm.layers * dm.heads * 2 * (2 * dm.kv_rank + dm.rope) * ctx
    return token_matmul_flops(dm) + 2 * dm.d_model * dm.vocab + attn


def paged_decode_call(dm, contexts) -> tuple[int, int]:
    """(FLOPs, bytes) of one layer's paged latent attention over the batch:
    scores over ``kv_rank + rope`` and the latent output over ``kv_rank``
    per head and position; bytes are each row's absorbed query and latent
    output, and each position's latent page entry read once."""
    rows = len(contexts)
    pairs = int(sum(contexts))
    lat = dm.kv_rank + dm.rope
    flops = dm.heads * 2 * (lat + dm.kv_rank) * pairs
    nbytes = BF16 * (rows * dm.heads * (lat + dm.kv_rank) + pairs * lat)
    return flops, nbytes


def expert_call(dm, pairs: int, hit: int) -> tuple[int, int]:
    """(FLOPs, bytes) of the held experts' SwiGLU over ``pairs`` (token,
    expert) pairs: the weights of the ``hit`` experts that received one,
    and each pair's input and output row."""
    flops = 2 * pairs * expert_params(dm)
    nbytes = BF16 * (hit * expert_params(dm) + 2 * pairs * dm.d_model)
    return flops, nbytes
