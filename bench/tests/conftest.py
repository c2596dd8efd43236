"""Tests of the benchmark itself, on the CPU:

    JAX_PLATFORMS=cpu python -m pytest -q bench/tests

They import the harness from ``bench/`` and the program from ``src/``."""
import json
import os
import shutil
import sys

import pytest

os.environ.setdefault("JAX_PLATFORMS", "cpu")
BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROOT = os.path.dirname(BENCH)
for p in (BENCH, os.path.join(ROOT, "src")):
    if p not in sys.path:
        sys.path.insert(0, p)

DUMMY_CONFIG = {
    "architecture": "dummy_lm", "hidden_act": "silu", "hidden_size": 128,
    "intermediate_size": 256, "num_hidden_layers": 2,
    "num_attention_heads": 4, "num_key_value_heads": 2, "head_dim": 32,
    "vocab_size": 512, "max_position_embeddings": 1024,
    "rope_theta": 10000.0, "rms_norm_eps": 1e-06,
    "tie_word_embeddings": True, "attention_bias": True, "scale_depth": 1.4}
DUMMY_TRAFFIC = {
    "dummy_graph": {"kind": "graph", "batch": 2, "seq": 64, "inputs": 2,
                    "check_rows": 1},
    "dummy_serve": {
        "kind": "serve", "load": "poisson", "rate_per_s": 4.0, "slots": 2,
        "max_len": 256, "page_size": 128,
        "prompt_corpus": {"n": 3, "dist": "uniform", "low": 20, "high": 60,
                          "corpus_seed": 1},
        "output_tokens": {"dist": "uniform", "low": 4, "high": 8},
        "template_seed": 2, "check_requests": 3},
}
# limits at this size: the program reads ~0.015 (graph) and 0 (serve); the
# float8 control ~0.2 and ~0.05
DUMMY_LIMITS = {"dummy.graph": {"logit_err": {"limit": 0.05}},
                "dummy.serve": {"token_gap": {"limit": 0.02}}}
DUMMY_ARCHITECTURE = '''"""dummy_lm: the dense decoder under a name of its own."""
import os

from harness.model import load_architecture

_dense = load_architecture(os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__)))), "dense_lm")
globals().update({k: v for k, v in vars(_dense).items()
                  if not k.startswith("__")})
'''
DUMMY_READER = '''"""dummy_count: calls (graph) or engine ticks (serve) in the window."""


def read(run):
    return float(run.calls if run.kind == "graph" else len(run.ticks))
'''


@pytest.fixture
def dummy_root(tmp_path):
    """A checkout whose benchmark adds one architecture, one
    configuration, two traffic mixes, their limits and one metric reader
    as files only, beside the harness, the architectures and the metric
    readers of this repository."""
    b = tmp_path / "bench"
    for d in ("metrics", "architectures"):
        shutil.copytree(os.path.join(BENCH, d), b / d,
                        ignore=shutil.ignore_patterns("__pycache__"))
    (b / "architectures" / "dummy_lm.py").write_text(DUMMY_ARCHITECTURE)
    for d in ("configs", "traffic", "limits"):
        (b / d).mkdir()
    (b / "configs" / "dummy.json").write_text(json.dumps(DUMMY_CONFIG))
    for name, t in DUMMY_TRAFFIC.items():
        (b / "traffic" / f"{name}.json").write_text(json.dumps(t))
    for name, lim in DUMMY_LIMITS.items():
        (b / "limits" / f"{name}.json").write_text(json.dumps(lim))
    (b / "metrics" / "dummy_count.py").write_text(DUMMY_READER)
    (tmp_path / "src").symlink_to(os.path.join(ROOT, "src"))
    spec = json.load(open(os.path.join(ROOT, "BENCHMARK.json")))
    spec["configs"].append({"name": "dummy", "source": "test",
                            "file": "bench/configs/dummy.json",
                            "reduced": [], "why": "rehearsal"})
    kind_of = {w["name"]: json.load(open(os.path.join(
        BENCH, "traffic", w["traffic"] + ".json")))["kind"]
        for w in spec["workloads"]}
    for kind in ("graph", "serve"):
        spec["workloads"].append({"name": f"dummy.{kind}", "config": "dummy",
                                  "traffic": f"dummy_{kind}", "chips": 1,
                                  "why": "rehearsal"})
    for m in spec["end_to_end"] + spec["per_layer"]:
        if "workloads" in m:
            kinds = sorted({kind_of[c] for c in m["workloads"]})
            m["workloads"] += [f"dummy.{k}" for k in kinds]
    spec["per_layer"].append({"name": "dummy_count", "unit": "n",
                              "better": "higher", "source": "host_clock",
                              "layer": "rehearsal", "moves": "setup_s"})
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(spec))
    return str(tmp_path)


def run_cell(root, workload, seed=11, seconds=2.0, trace=0, capsys=None):
    """Run the harness in this process with its look for a chip skipped;
    returns (exit code, the JSON result line or None)."""
    from harness.main import main

    rc = main(["--workload", workload, "--seed", str(seed), "--seconds",
               str(seconds), "--trace", str(trace)], root=root,
              require_chip=False)
    out = capsys.readouterr().out.strip().splitlines() if capsys else []
    line = json.loads(out[-1]) if out and out[-1].startswith("{") else None
    return rc, line
