"""Counts from shapes against hand sums of the two configurations."""
import os

import pytest

from conftest import BENCH, ROOT
from harness import counts, model
from harness.peaks import peak_for, roofline_s

ARCH = model.load_architecture(ROOT, "dense_lm")


def dims(name):
    return ARCH.dims(name, model.load_config(
        os.path.join(BENCH, "configs", name + ".json")))


@pytest.mark.parametrize("name, params, kv", [
    # 151936*896 + 24*(2*896*896 + 2*896*128 + 896+2*128 + 3*896*4864
    # + 2*896) + 896
    ("qwen2-0.5b", 494_032_768, 24 * 2 * 2 * 64 * 2),
    # 122753*2304 + 40*(4*2304*2304 + 3*2304*5760 + 2*2304) + 2304
    ("minicpm-2b", 2_724_880_896, 40 * 2 * 2304 * 2),
])
def test_parameters_and_kv_bytes(name, params, kv):
    dm = dims(name)
    assert ARCH.param_count(dm) == params
    assert ARCH.kv_bytes_per_token(dm) == kv
    assert ARCH.kv_bytes_per_token(dm) == {"qwen2-0.5b": 12_288,
                                             "minicpm-2b": 368_640}[name]


def test_forward_flops_are_dense_plus_causal_attention():
    dm = dims("minicpm-2b")
    f = ARCH.forward_flops(dm, 1, 1024)
    dense = 2 * 1024 * (ARCH.param_count(dm) - 40 * 2 * 2304 - 2304)
    attn = 40 * 36 * 4 * 64 * (1024 * 1025 // 2)
    assert f == dense + attn
    assert 5.7e12 < f < 5.8e12


def test_branch_gemm_and_paged_decode_counts():
    flops, nbytes = counts.branch_gemm(3, 2048, 896, 896, True)
    assert flops == 2 * 3 * 2048 * 896 * 896
    assert nbytes == 2 * 3 * (2048 * 896 + 896 * 896 + 2048 * 896 + 896)
    dm = dims("minicpm-2b")
    flops, nbytes = ARCH.paged_decode_call(dm, [2000, 3000])
    assert flops == 36 * 4 * 64 * 5000
    assert nbytes == 2 * (2 * 2 * 36 * 64 + 2 * 5000 * 36 * 64)


def test_peaks_by_device_kind():
    p = peak_for("TPU v5 lite")
    assert p["bf16_flops_per_s"] == 197e12 and p["hbm_bytes_per_s"] == 819e9
    assert roofline_s(197e12, 0, p) == 1.0
    assert roofline_s(0, 819e9, p) == 1.0
    with pytest.raises(ValueError):
        peak_for("TPU v4")
