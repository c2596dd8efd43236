"""DeepSeek-V3 support: group-limited routing, YaRN, the dropless expert
share, the dense prefix as a config field, and the program against the
benchmark's plain reference (``bench/architectures/mla_moe_lm.py``) on
seeded random weights, at a small size on the CPU."""
import dataclasses
import json
import math
import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_config
from repro.configs.base import ModelConfig, MoEConfig, YaRNConfig
from repro.models import Model
from repro.models.ffn import init_moe, moe_ffn, route
from repro.models.layers import rope_freqs, yarn_mscale
from repro.models.transformer import cfg_dense_prefix, layer_kinds

BENCH = os.path.join(os.path.dirname(os.path.dirname(os.path.abspath(
    __file__))), "bench")
if BENCH not in sys.path:
    sys.path.insert(0, BENCH)

from harness.model import load_architecture, make_params  # noqa: E402

ARCH = load_architecture(os.path.dirname(BENCH), "mla_moe_lm")

# a small DeepSeek-V3: 1 dense + 2 MoE layers, 16 routed experts in 4
# groups (2 kept), 4 a token, this share holding experts 4-7
SMALL = {
    "architecture": "mla_moe_lm", "attention_bias": False,
    "first_k_dense_replace": 1, "hidden_act": "silu", "hidden_size": 128,
    "intermediate_size": 256, "kv_lora_rank": 64,
    "max_position_embeddings": 8192, "moe_intermediate_size": 128,
    "moe_layer_freq": 1, "n_group": 4, "n_routed_experts": 4,
    "n_shared_experts": 1, "norm_topk_prob": True, "num_attention_heads": 4,
    "num_experts_per_tok": 4, "num_hidden_layers": 3,
    "num_key_value_heads": 4, "num_nextn_predict_layers": 0,
    "q_lora_rank": 64, "qk_nope_head_dim": 32, "qk_rope_head_dim": 16,
    "rms_norm_eps": 1e-06,
    "rope_scaling": {"beta_fast": 32, "beta_slow": 1, "factor": 40,
                     "mscale": 1, "mscale_all_dim": 1,
                     "original_max_position_embeddings": 4096,
                     "type": "yarn"},
    "rope_theta": 10000, "routed_scaling_factor": 2.5,
    "scoring_func": "sigmoid", "tie_word_embeddings": False,
    "topk_group": 2, "topk_method": "noaux_tc", "v_head_dim": 32,
    "vocab_size": 512,
    "expert_parallel": {"router_experts": 16, "chips_per_layer": 4,
                        "held_offset": 4}}


def small(dtype=jnp.float32):
    dm = ARCH.dims("small", SMALL)
    return dm, ARCH.program_config(dm, dtype)


# -- the published routines, transcribed -----------------------------------

def published_gate(x, weight, bias, n_groups, topk_groups, topk, route_scale):
    """DeepSeek-V3's ``Gate.forward`` (inference/model.py), sigmoid scoring,
    in numpy."""
    scores = 1.0 / (1.0 + np.exp(-(x @ weight)))
    original = scores
    scores = scores + bias
    scores = scores.reshape(x.shape[0], n_groups, -1)
    group_scores = np.sort(scores, -1)[..., -2:].sum(-1)
    indices = np.argsort(-group_scores, -1)[:, :topk_groups]
    mask = np.ones((x.shape[0], n_groups), bool)
    np.put_along_axis(mask, indices, False, 1)
    scores = np.where(mask[..., None], -np.inf, scores).reshape(x.shape[0], -1)
    indices = np.argsort(-scores, -1)[:, :topk]
    weights = np.take_along_axis(original, indices, 1)
    weights = weights / weights.sum(-1, keepdims=True)
    return weights * route_scale, indices


def published_yarn(dim, base, factor, orig, beta_fast, beta_slow):
    """``DeepseekV3YarnRotaryEmbedding``'s inv_freq, in numpy."""
    def find_dim(rot):
        return (dim * math.log(orig / (rot * 2 * math.pi))) / (2 * math.log(base))
    low = max(math.floor(find_dim(beta_fast)), 0)
    high = min(math.ceil(find_dim(beta_slow)), dim - 1)
    freq_extra = 1.0 / (base ** (np.arange(0, dim, 2, dtype=np.float32) / dim))
    freq_inter = 1.0 / (factor * base ** (np.arange(0, dim, 2,
                                                     dtype=np.float32) / dim))
    if low == high:
        high += 0.001
    ramp = np.clip((np.arange(dim // 2, dtype=np.float32) - low)
                   / (high - low), 0, 1)
    inv_freq_mask = 1.0 - ramp
    return freq_inter * (1 - inv_freq_mask) + freq_extra * inv_freq_mask


def published_mscale(scale=1.0, mscale=1.0):
    return 1.0 if scale <= 1 else 0.1 * mscale * math.log(scale) + 1.0


# -- routing and rotary scaling ----------------------------------------------

def test_group_limited_routing_matches_the_published_gate():
    cfg = get_config("deepseek-v3-671b")
    e = cfg.moe
    rng = np.random.default_rng(0)
    x = rng.standard_normal((64, 96)).astype(np.float32)
    p = {"w": jnp.asarray(rng.standard_normal((96, 256)) * 96 ** -0.5,
                          jnp.float32),
         "b": jnp.asarray(rng.standard_normal(256) * 0.02, jnp.float32)}
    w, idx, _ = route(p, jnp.asarray(x), e)
    w_pub, idx_pub = published_gate(x, np.asarray(p["w"]), np.asarray(p["b"]),
                                    8, 4, 8, 2.5)
    order = np.argsort(np.asarray(idx), -1)
    order_pub = np.argsort(idx_pub, -1)
    np.testing.assert_array_equal(np.take_along_axis(np.asarray(idx), order, 1),
                                  np.take_along_axis(idx_pub, order_pub, 1))
    np.testing.assert_allclose(np.take_along_axis(np.asarray(w), order, 1),
                               np.take_along_axis(w_pub, order_pub, 1),
                               rtol=1e-5, atol=1e-6)
    # every token's experts lie in at most topk_group groups
    assert (np.asarray([len(set(r // 32)) for r in np.asarray(idx)]) <= 4).all()


def published_dims():
    return ARCH.dims("deepseek-v3", json.load(open(os.path.join(
        BENCH, "configs", "deepseek-v3.json"))))


def _gate_held(select, dm):
    """The published gate's picks among the held experts, from selection
    scores (weights 0, so only the bias, the selection scores, counts)."""
    _, idx = published_gate(np.zeros((select.shape[0], 1), np.float32),
                            np.zeros((1, dm.n_experts), np.float32),
                            select - 0.5, dm.n_group, dm.topk_group, dm.top_k,
                            1.0)
    held = dm.expert_offset + np.arange(dm.experts_held)
    return (idx[:, :, None] == held).any(1)


def _selection_scores(rng, n, dm):
    """Sigmoid scores of unit logits plus a small correction bias, with
    near-ties made common: every score is rounded to a grid of 1e-3."""
    s = 1 / (1 + np.exp(-rng.standard_normal((n, dm.n_experts))))
    s = s + 0.01 * rng.standard_normal(dm.n_experts)
    return np.round(s, 3).astype(np.float32)


def test_held_picks_without_room_are_the_gates_picks():
    dm = published_dims()
    select = _selection_scores(np.random.default_rng(0), 512, dm)
    select += np.random.default_rng(1).uniform(0, 1e-4, select.shape)
    surely, maybe = ARCH.held_picks(jnp.asarray(select), dm, 0.0)
    want = _gate_held(select, dm)
    np.testing.assert_array_equal(np.asarray(surely), want)
    np.testing.assert_array_equal(np.asarray(maybe), want)
    assert want.any()


@pytest.mark.parametrize("delta", [1e-3, 4e-3])
def test_decided_held_picks_hold_under_any_change_within_delta(delta):
    """Where ``held_picks`` calls a token's held picks decided, no change
    of its selection scores by at most ``delta`` moves them; where it does
    not, some change does (or the token sits on a tie the changes tried
    missed).  Changes: random, and pushed to +-delta against each held
    expert."""
    dm = published_dims()
    rng = np.random.default_rng(2)
    select = _selection_scores(rng, 2048, dm)
    surely, maybe = map(np.asarray, ARCH.held_picks(jnp.asarray(select), dm,
                                                    delta))
    assert (surely <= maybe).all()
    decided = (surely == maybe).all(-1)
    nominal = _gate_held(select, dm)
    assert (surely[decided] == nominal[decided]).all()
    held = dm.expert_offset + np.arange(dm.experts_held)
    moved = np.zeros(len(select), bool)
    for trial in range(24):
        d = rng.uniform(-delta, delta, select.shape).astype(np.float32)
        if trial % 2:
            sign = np.where(rng.random(len(select)) < 0.5, 1, -1)[:, None]
            d = np.full_like(d, -delta) * sign
            d[:, held] = delta * sign
        picks = _gate_held(select + d, dm)
        assert (picks[decided] == nominal[decided]).all()
        moved |= (picks != nominal).any(-1)
    assert 0 < (~decided).sum() and moved[~decided].mean() > 0.5
    assert decided.mean() > 0.5


def test_undecided_positions_read_no_gap(capsys):
    """The check's logits are the reference's, except all 0 at the
    positions whose held picks are undecided within ``ROUTING_DELTA``."""
    dm, cfg = small()
    params = make_params(cfg, 5)
    toks = np.random.default_rng(5).integers(1, dm.vocab, 200).tolist()
    pos = np.arange(100, 199)
    ref, select = ARCH.reference(params, toks, pos, dm)
    tie = np.asarray(ARCH.undecided(select, dims=dm, delta=ARCH.ROUTING_DELTA))
    got = np.asarray(ARCH.logits(params, toks, pos, dm, "f32"))
    assert 0 < tie.sum() < len(pos)
    np.testing.assert_array_equal(got[tie], 0.0)
    np.testing.assert_array_equal(got[~tie], np.asarray(ref)[~tie])
    assert f"{tie.sum()} of {len(pos)} positions left out" in capsys.readouterr().err
    np.testing.assert_array_equal(
        np.asarray(ARCH.logits(params, toks, pos, dm, "fp8")),
        np.asarray(ARCH.reference(params, toks, pos, dm, "fp8")[0]))


def test_yarn_frequencies_and_mscale_match_the_published_formulas():
    ys = get_config("deepseek-v3-671b").rope_scaling
    np.testing.assert_allclose(
        np.asarray(rope_freqs(64, 1e4, ys)),
        published_yarn(64, 1e4, 40.0, 4096, 32, 1), rtol=1e-6)
    assert yarn_mscale(40.0, 1.0) == published_mscale(40.0, 1.0)
    assert abs(yarn_mscale(40.0, 1.0) ** 2 - 1.8739) < 1e-4
    # without scaling the frequencies are the plain ones
    np.testing.assert_allclose(np.asarray(rope_freqs(64, 1e4)),
                               1e4 ** (-np.arange(0, 64, 2) / 64), rtol=1e-6)


def test_published_config_values():
    cfg = get_config("deepseek-v3-671b")
    e = cfg.moe
    assert (e.n_experts, e.top_k, e.d_expert, e.n_shared) == (256, 8, 2048, 1)
    assert (e.n_group, e.topk_group, e.routed_scale) == (8, 4, 2.5)
    assert cfg.rope_scaling == YaRNConfig(40.0, 4096, 32.0, 1.0, 1.0, 1.0)
    assert cfg.max_seq_len == 163840 and cfg.dense_prefix == 3


@pytest.mark.parametrize("arch, prefix", [("deepseek-v3-671b", 3),
                                          ("kimi-k2-1t-a32b", 1)])
def test_dense_prefix_is_a_config_field(arch, prefix):
    for smoke in (False, True):
        cfg = get_config(arch, smoke=smoke)
        assert cfg_dense_prefix(cfg) == prefix
        assert layer_kinds(cfg)[0] == ("dense_prefix", prefix)
        # the count no longer keys on the name
        renamed = dataclasses.replace(cfg, name="another-name")
        assert cfg_dense_prefix(renamed) == prefix


# -- the expert share ---------------------------------------------------------

def _moe_cfg(held=None, offset=0, cf=1.25):
    return ModelConfig(
        name="share-test", family="moe", n_layers=1, d_model=128, n_heads=2,
        n_kv_heads=2, d_ff=64, vocab_size=64,
        moe=MoEConfig(n_experts=16, top_k=4, d_expert=128, n_shared=1,
                      capacity_factor=cf, router_aux_free=True, n_group=4,
                      topk_group=2, routed_scale=2.5, experts_held=held,
                      expert_offset=offset),
        dtype=jnp.float32)


@pytest.mark.parametrize("use_kernels", [False, True])
def test_expert_shares_add_up_to_the_uncut_layer(use_kernels):
    """Shares at offsets 0, 4, 8, 12, each with the shared expert taken out
    but once, sum to the uncut layer (capacity large enough to drop
    nothing)."""
    full_cfg = _moe_cfg(cf=16.0)
    p = init_moe(jax.random.key(0), full_cfg)
    p["router"]["b"] = 0.02 * jax.random.normal(jax.random.key(2), (16,))
    x = jax.random.normal(jax.random.key(1), (2, 8, 128), jnp.float32)
    full, _ = moe_ffn(p, x, full_cfg)
    shared = None
    total = 0.0
    pairs = 0
    for off in (0, 4, 8, 12):
        cfg = _moe_cfg(held=4, offset=off)
        ps = dict(p, experts=jax.tree_util.tree_map(
            lambda a: a[off:off + 4], p["experts"]))
        y, aux = moe_ffn(ps, x, cfg, use_kernels=use_kernels)
        if shared is None:
            from repro.models.ffn import mlp
            shared = mlp(p["shared"], x.reshape(16, 128)).reshape(x.shape)
        total = total + (y - shared)
        pairs += int(aux["moe_held"][0])
    np.testing.assert_allclose(np.asarray(total + shared), np.asarray(full),
                               rtol=1e-4, atol=1e-4)
    assert pairs == 16 * 4          # every (token, expert) pair, once


def test_share_drops_no_pair_when_tokens_share_an_expert():
    """A decode step of 16 rows in which every token picks held expert 0:
    the share computes all 16 pairs (a capacity-bounded dispatch keeps
    round(16 * 4 / 16 * 1.25) = 5 of them)."""
    cfg = _moe_cfg(held=4, offset=0)
    p = init_moe(jax.random.key(0), cfg)
    p["router"]["b"] = p["router"]["b"].at[0].set(10.0)   # everyone picks 0
    x = jax.random.normal(jax.random.key(1), (16, 1, 128), jnp.float32)
    y, aux = moe_ffn(p, x, cfg, use_kernels=True)
    w, idx, _ = route(p["router"], x.reshape(16, 128), cfg.moe)
    assert (np.asarray(idx) == 0).any(-1).all()
    xf = x.reshape(16, 128)
    want = np.zeros((16, 128), np.float32)
    for t in range(16):
        for j in range(4):
            ex = int(idx[t, j])
            if ex < 4:
                g = xf[t] @ p["experts"]["gate"][ex]
                u = xf[t] @ p["experts"]["up"][ex]
                want[t] += float(w[t, j]) * np.asarray(
                    (jax.nn.silu(g) * u) @ p["experts"]["down"][ex])
    from repro.models.ffn import mlp
    want += np.asarray(mlp(p["shared"], xf))
    np.testing.assert_allclose(np.asarray(y).reshape(16, 128), want,
                               rtol=1e-4, atol=1e-4)
    assert int(aux["moe_held"][0]) == int((np.asarray(idx) < 4).sum()) >= 16
    # the uncut layer under the same routing drops pairs at this capacity
    full_cfg = _moe_cfg()
    pf = init_moe(jax.random.key(0), full_cfg)
    pf["router"] = p["router"]
    pf["experts"] = jax.tree_util.tree_map(
        lambda a, b: a.at[:4].set(b), pf["experts"], p["experts"])
    yf, _ = moe_ffn(pf, x, full_cfg)
    assert not np.allclose(np.asarray(yf), np.asarray(y), atol=1e-3)


# -- the program against the reference ------------------------------------------

def test_prefill_logits_match_the_reference():
    dm, cfg = small()
    params = make_params(cfg, 7)
    toks = np.random.default_rng(7).integers(1, dm.vocab, 48)
    logits, _, counters = Model(cfg, use_kernels=True).prefill(
        params, {"tokens": jnp.asarray(toks)[None]})
    held = counters["moe_held"]
    ref, _ = ARCH.reference(params, toks.tolist(), [47], dm)
    np.testing.assert_allclose(np.asarray(logits), np.asarray(ref),
                               rtol=1e-4, atol=1e-4)
    # pairs that reached experts 4-7 over the two MoE layers
    assert 0 < int(held[0]) <= 2 * 48 * 4 and 0 < int(held[1]) <= 2 * 4


def test_paged_decode_matches_the_reference_forward():
    """Prefill, then paged decode through the cache, greedy: every served
    token is the reference's argmax of its full forward over the prompt
    and the tokens served before it, with the logit it was chosen by."""
    from repro.serving import InferenceEngine, Request

    dm, cfg = small()
    params = make_params(cfg, 11)
    engine = InferenceEngine(Model(cfg, use_kernels=True), params,
                             max_slots=2, max_len=96, paged_kv=True,
                             page_size=16)
    rng = np.random.default_rng(11)
    prompts = [rng.integers(1, dm.vocab, n).tolist() for n in (37, 21)]
    for i, pr in enumerate(prompts):
        engine.submit(Request(rid=i, prompt=pr, max_tokens=6))
    done = {r.rid: r for r in engine.run(100)}
    for i, pr in enumerate(prompts):
        out = done[i].output
        assert len(out) == 6
        toks = pr + out[:-1]
        ref = np.asarray(ARCH.reference(
            params, toks, np.arange(len(pr) - 1, len(toks)), dm)[0])
        gap = ref.max(-1) - ref[np.arange(len(out)), out]
        assert gap.max() < 1e-4, gap


def test_long_context_matches_the_reference():
    """Past three 512-row chunks of the MLA prefill and past YaRN's
    original context (cut here to 256 positions, so that the blend
    reaches the positions read), in float32: the prefill's logits at every
    position, then paged decode through the cache, agree with the
    reference's full forward."""
    from repro.models.transformer import lm_forward
    from repro.serving import InferenceEngine, Request

    conf = json.loads(json.dumps(SMALL))
    conf["rope_scaling"]["original_max_position_embeddings"] = 256
    dm = ARCH.dims("small-long", conf)
    cfg = ARCH.program_config(dm, jnp.float32)
    params = make_params(cfg, 13)
    prompt = np.random.default_rng(13).integers(1, dm.vocab, 1400).tolist()
    logits, _, _ = lm_forward(params, jnp.asarray(prompt)[None], cfg,
                              use_kernels=True, with_cache=False)
    pos = np.arange(0, 1400, 7)
    ref, _ = ARCH.reference(params, prompt, pos, dm)
    np.testing.assert_allclose(np.asarray(logits[0, pos]), np.asarray(ref),
                               rtol=1e-4, atol=1e-4)
    engine = InferenceEngine(Model(cfg, use_kernels=True), params,
                             max_slots=1, max_len=1536, paged_kv=True,
                             page_size=128)
    engine.submit(Request(rid=0, prompt=prompt, max_tokens=5))
    out = {r.rid: r for r in engine.run(20)}[0].output
    toks = prompt + out[:-1]
    ref = np.asarray(ARCH.reference(
        params, toks, np.arange(len(prompt) - 1, len(toks)), dm)[0])
    gap = ref.max(-1) - ref[np.arange(len(out)), out]
    assert len(out) == 5 and gap.max() < 1e-4, gap


def test_expert_counts_in_the_engine_spans():
    from repro.runtime import tracing
    from repro.serving import InferenceEngine, Request

    dm, cfg = small(jnp.bfloat16)
    params = make_params(cfg, 3)
    engine = InferenceEngine(Model(cfg, use_kernels=True), params,
                             max_slots=2, max_len=64, paged_kv=True,
                             page_size=16)
    engine.submit(Request(rid=0, prompt=list(range(1, 30)), max_tokens=4))
    engine.submit(Request(rid=1, prompt=list(range(5, 20)), max_tokens=4))
    t0 = tracing._clock()
    engine.run(50)
    spans = tracing.spans(since_ns=t0)
    admits = [s.attrs for s in spans if s.name == "engine.admit.prefill"]
    decodes = [s.attrs for s in spans if s.name == "engine.step"
               and s.attrs.get("kind") == "decode"]
    assert len(admits) == 2 and decodes
    for a in admits + decodes:
        assert 0 <= a["moe_experts_hit"] <= 2 * 4
        assert a["moe_experts_hit"] <= a["moe_pairs_held"]
    assert all(a["moe_pairs_held"] <= 2 * 2 * 4 for a in decodes)


def test_dense_models_return_no_expert_counts():
    cfg = get_config("qwen2-0.5b", smoke=True)
    m = Model(cfg)
    params = m.init(jax.random.key(0))
    assert m.prefill(params, {"tokens": jnp.ones((1, 8), jnp.int32)})[2] == {}
    caches = m.init_paged_caches(5, 16)
    out = m.paged_decode(params, jnp.ones((1,), jnp.int32), caches,
                         jnp.ones((1, 4), jnp.int32), jnp.zeros((1,), jnp.int32))
    assert out[2] == {}
