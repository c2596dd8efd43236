"""The ``mla_moe_lm`` architecture: its counts at the published sizes of
``configs/deepseek-v3.json``, and a tiny latent-attention, expert-share
serve cell added as files only, run once as it is and once with one held
expert's output altered (on the CPU, the harness's look for a chip
skipped)."""
import json
import os
import shutil

import jax.numpy as jnp
import pytest

from conftest import BENCH, ROOT, run_cell
from harness import model

ARCH = model.load_architecture(ROOT, "mla_moe_lm")

TINY_CONFIG = {
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
# a backlog, as in the cell: off the chip an eager admission takes seconds
TINY_TRAFFIC = {
    "kind": "serve", "load": "backlog", "requests": 6, "slots": 2,
    "max_len": 256, "page_size": 128,
    "prompt_corpus": {"n": 3, "dist": "uniform", "low": 20, "high": 60,
                      "corpus_seed": 1},
    "output_tokens": {"dist": "uniform", "low": 4, "high": 8},
    "template_seed": 2, "check_requests": 3}
# with the positions whose held picks lie within the reference's routing
# delta of a tie left out, over 8 seeds the program read 0-0.004 and the
# float8 control 0.019-0.22 (7 of 8 above 0.06); the check reads 10 served
# positions
TINY_LIMITS = {"token_gap": {"limit": 0.04}}
CELL = "tiny_mla_moe.serve"


def published():
    return ARCH.dims("deepseek-v3", model.load_config(
        os.path.join(BENCH, "configs", "deepseek-v3.json")))


def test_counts_at_published_sizes():
    dm = published()
    assert (dm.d_model, dm.heads, dm.q_rank, dm.kv_rank) == (7168, 128, 1536, 512)
    assert (dm.n_experts, dm.experts_held, dm.top_k) == (256, 8, 8)
    assert ARCH.kv_bytes_per_token(dm) == 5_760
    # at the published sizes: MLA 187,105,280 a layer, dense FFN 396,361,728,
    # router 7168*256 + 256, shared expert 44,040,192, 8 experts 352,321,536
    assert ARCH.mla_params(dm) == 187_105_280
    assert 3 * dm.d_model * dm.d_ff == 396_361_728
    assert ARCH.router_params(dm) == 1_835_264
    assert ARCH.expert_params(dm) == 44_040_192
    norms = 2 * 7168 + 1536 + 512
    total = (2 * 16_160 * 7168 + 5 * (187_105_280 + norms) + 396_361_728
             + 4 * (1_835_264 + 9 * 44_040_192) + 7168)
    assert ARCH.param_count(dm) == total
    assert 3.15e9 < total < 3.17e9


def test_call_counts():
    dm = published()
    flops, nbytes = ARCH.expert_call(dm, 5, 3)
    assert flops == 2 * 5 * 3 * 7168 * 2048
    assert nbytes == 2 * (3 * 3 * 7168 * 2048 + 2 * 5 * 7168)
    flops, nbytes = ARCH.paged_decode_call(dm, [4000, 5000])
    assert flops == 128 * 2 * (576 + 512) * 9000
    assert nbytes == 2 * (2 * 128 * (576 + 512) + 9000 * 576)
    # a decode token: the head, the latent attention, routed experts at
    # their expected share of 8 x 8 / 256 a token
    d1, d0 = ARCH.decode_token_flops(dm, 1), ARCH.decode_token_flops(dm, 0)
    assert d1 - d0 == 5 * 128 * 2 * (2 * 512 + 64)
    routed = 8 * 8 / 256 * 44_040_192
    assert d0 == int(2 * (5 * 187_105_280 + 396_361_728 + 4 * (
        7168 * 256 + 44_040_192 + routed))) + 2 * 7168 * 16_160


def test_yarn_matches_the_program():
    from repro.models.layers import rope_freqs
    dm = published()
    cfg = ARCH.program_config(dm)
    assert jnp.allclose(rope_freqs(64, 1e4, cfg.rope_scaling),
                        ARCH.yarn_inv_freq(dm), rtol=1e-6)
    from repro.models.attention import mla_softmax_scale
    assert abs(mla_softmax_scale(cfg) - ARCH.softmax_scale(dm)) < 1e-9
    assert abs(ARCH.softmax_scale(dm) * 192 ** 0.5 - 1.8739) < 1e-4


@pytest.fixture
def tiny_root(tmp_path):
    """A checkout whose benchmark adds one MLA + expert-share
    configuration, its traffic and its limits as files only."""
    b = tmp_path / "bench"
    for d in ("metrics", "architectures", "traffic"):
        shutil.copytree(os.path.join(BENCH, d), b / d,
                        ignore=shutil.ignore_patterns("__pycache__"))
    for d in ("configs", "limits"):
        (b / d).mkdir()
    (b / "configs" / "tiny.json").write_text(json.dumps(TINY_CONFIG))
    (b / "traffic" / "tiny_serve.json").write_text(json.dumps(TINY_TRAFFIC))
    (b / "limits" / f"{CELL}.json").write_text(json.dumps(TINY_LIMITS))
    (tmp_path / "src").symlink_to(os.path.join(ROOT, "src"))
    spec = json.load(open(os.path.join(ROOT, "BENCHMARK.json")))
    spec["configs"].append({"name": "tiny_mla_moe", "source": "test",
                            "file": "bench/configs/tiny.json",
                            "reduced": [], "why": "rehearsal"})
    spec["workloads"].append({"name": CELL, "config": "tiny_mla_moe",
                              "traffic": "tiny_serve", "chips": 1,
                              "why": "rehearsal"})
    for m in spec["end_to_end"] + spec["per_layer"]:
        if "deepseek-v3.longdecode16" in m.get("workloads", ()):
            m["workloads"].append(CELL)
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(spec))
    return str(tmp_path)


def test_added_mla_moe_cell_runs_from_files_only(tiny_root, capsys):
    rc, line = run_cell(tiny_root, CELL, seed=2 ** 33 + 7, seconds=4.0,
                        capsys=capsys)
    assert rc == 0 and line is not None
    assert line["correct"] is True, line["check"]
    assert set(line["metrics"]) == {"tbt_p95_ms", "output_tokens_per_s",
                                    "setup_s"}
    assert line["attempted"] > 0 and line["failed"] == 0


def test_traced_mla_moe_cell_counts_its_experts(tiny_root, capsys):
    """Off the chip the trace holds no device operations, so the device
    metrics stay silent; the program's spans still carry the pairs."""
    from repro.runtime import tracing

    t0 = tracing._clock()
    rc, line = run_cell(tiny_root, CELL, seconds=2.0, trace=1, capsys=capsys)
    assert rc == 0 and line["correct"] is True
    steps = [s.attrs for s in tracing.spans(since_ns=t0)
             if s.name == "engine.step" and s.attrs.get("kind") == "decode"]
    assert steps and all("moe_pairs_held" in a for a in steps)
    assert all(0 <= a["moe_experts_hit"] <= 2 * 4 for a in steps)
    assert any(a["moe_pairs_held"] > 0 for a in steps)
    assert "expert_gemm_roofline.serve" not in line["metrics"]


def test_held_expert_altered_is_not_correct(tiny_root, capsys, monkeypatch):
    """One held expert's output comes out altered where it is produced."""
    from repro.models import ffn

    orig = ffn._expert_mlp

    def altered(p_experts, buf, use_kernels, rows=None):
        out = orig(p_experts, buf, use_kernels, rows)
        return out.at[1].multiply(jnp.asarray(-3.0, out.dtype))

    monkeypatch.setattr(ffn, "_expert_mlp", altered)
    rc, line = run_cell(tiny_root, CELL, seed=5, capsys=capsys)
    assert rc == 0 and line["correct"] is False
    assert line["check"]["token_gap"]["value"] > TINY_LIMITS["token_gap"]["limit"]
