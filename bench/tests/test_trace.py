"""The trace reduction: busy share as a union of intervals, device time
by operation name, idle gaps charged to the harness span open at their
middle.  On hand-made events, and on a small trace recorded on a TPU v5e
(``data/v5e_tiny.xplane.pb``: three calls of a jitted bf16 matmul, each
under ``bench.call``, with ``bench.idle`` sleeps between them)."""
import os

import pytest

from harness import trace

DATA = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data")
MS = 1_000_000


def test_union_merges_overlaps():
    assert trace.union([(5, 7), (0, 2), (1, 3), (3, 4)]) == [[0, 4], [5, 7]]
    assert trace.clip([(0, 4), (5, 7)], 1, 6) == [(1, 4), (5, 6)]


def test_op_names_drop_numeric_suffixes():
    assert trace.op_name("fusion.123", "jit_run(7)") == "jit_run/fusion"
    assert trace.op_name("branch_gemm_pallas", None) == "branch_gemm_pallas"
    hlo = ("%branch_gemm_pallas.81 = bf16[2,2048,4864]{2,1,0} custom-call("
           "bf16[2,2048,896]{2,1,0} %pad.33), custom_call_target=\"x\"")
    assert trace.op_name(hlo, "jit_run(1779)") == "jit_run/branch_gemm_pallas"


def test_reduce_busy_ops_and_gaps():
    tr = trace.Trace(
        device_ops={"/device:TPU:0": [
            ("m/a", 0, 4 * MS), ("m/b", 2 * MS, 5 * MS),    # overlap
            ("m/a", 8 * MS, 9 * MS), ("m/c", 20 * MS, 30 * MS)]},
        spans=[("bench.call", 0, 6 * MS), ("bench.wait", 6 * MS, 10 * MS),
               ("bench.inner", 7 * MS, 8 * MS)])
    s = trace.reduce(tr, 0, 10 * MS)
    assert s.window_s == pytest.approx(0.010)
    assert s.busy_s == pytest.approx(0.006)                # [0,5] + [8,9]
    assert s.ops == pytest.approx({"m/a": 0.005, "m/b": 0.003})
    assert s.op_seconds("m/") == pytest.approx(0.008)
    # gaps: [5,8] (mid 6.5: bench.wait) and [9,10] (bench.wait)
    assert s.gaps == pytest.approx({"bench.wait": 0.004})
    b = s.breakdown()
    assert b["device_ops"][0] == ["m/a", pytest.approx(0.005)]


def test_reduce_refuses_a_trace_without_device_ops():
    with pytest.raises(ValueError):
        trace.reduce(trace.Trace(device_ops={}, spans=[]), 0, MS)


def test_recorded_v5e_trace():
    tr = trace.Trace.load(os.path.join(DATA, "v5e_tiny.xplane.pb"))
    assert list(tr.device_ops) == ["/device:TPU:0"]
    names = {n for n, _, _ in tr.device_ops["/device:TPU:0"]}
    assert names == {"jit__lambda/copy-start", "jit__lambda/copy-done",
                     "jit__lambda/convolution_reduce_fusion"}
    assert [n for n, _, _ in tr.spans] == ["bench.call", "bench.idle"] * 3
    lo, hi = tr.spans[0][1] - 5 * MS, tr.spans[-1][2]
    s = trace.reduce(tr, lo, hi)
    ops = tr.device_ops["/device:TPU:0"]
    assert s.busy_s == pytest.approx(sum(e - b for _, b, e in ops) * 1e-9)
    assert s.op_seconds("convolution_reduce_fusion") == pytest.approx(
        3 * 642e-9, rel=0.01)
    # three 0.6 us matmuls in a 15 ms window: the device is idle nearly
    # all of it, and every gap falls under one of the harness's spans or
    # before the first one
    assert sum(s.gaps.values()) == pytest.approx(s.window_s - s.busy_s)
    assert set(s.gaps) <= {"bench.call", "bench.idle", trace.NO_SPAN}
    assert s.gaps[trace.NO_SPAN] > 0     # the 5 ms before the first span


def test_control_flow_containers_are_left_out():
    ops = [("%while.3 = (s32[]) while(%t), body=%b", 0, 10 * MS),
           ("%fusion.1 = f32[] fusion()", 1 * MS, 2 * MS)]
    named = trace._with_modules(ops, [("jit_step(5)", 0, 10 * MS)])
    assert named == [("jit_step/fusion", 1 * MS, 2 * MS)]
