"""The harness is driven by data: a configuration, a traffic mix, limits
and a metric reader added as files only run as a cell (on the CPU, with
the harness's look for a chip skipped).  Without a TPU the command itself
prints no result and fails."""
import os
import subprocess
import sys

import pytest

from conftest import ROOT, run_cell


@pytest.mark.parametrize("kind, metrics", [
    ("graph", {"exec_ms", "setup_s"}),
    ("serve", {"tbt_p95_ms", "output_tokens_per_s", "setup_s"}),
])
def test_added_cell_runs_from_files_only(dummy_root, capsys, kind, metrics):
    rc, line = run_cell(dummy_root, f"dummy.{kind}", seed=2 ** 33 + 5,
                        seconds=4.0, capsys=capsys)
    assert rc == 0 and line is not None
    assert line["correct"] is True, line["check"]
    assert set(line["metrics"]) == metrics
    assert line["attempted"] > 0 and line["failed"] == 0
    assert list(line)[-1] == "check"
    assert line["device"]["platform"] == "cpu"


def test_traced_run_reports_the_added_reader(dummy_root, capsys):
    rc, line = run_cell(dummy_root, "dummy.graph", seconds=1.0, trace=1,
                        capsys=capsys)
    assert rc == 0 and line["correct"] is True
    assert line["metrics"]["dummy_count"]["value"] > 0
    assert "speedup_vs_sequential.graph" in line["metrics"]
    assert "exec_ms" not in line["metrics"]


def test_command_refuses_to_run_without_a_tpu():
    p = subprocess.run(
        [sys.executable, os.path.join(ROOT, "bench", "run.py"), "--workload",
         "qwen2-0.5b.graph_b8s256", "--seed", "1", "--seconds", "1",
         "--trace", "0"], cwd=ROOT, capture_output=True, text=True,
        env=dict(os.environ, JAX_PLATFORMS="cpu"), timeout=300)
    assert p.returncode != 0
    assert p.stdout.strip() == ""
