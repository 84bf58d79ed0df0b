"""The FLOP and byte functions against hand counts for the three cells
(the figures of ISSUE 23)."""
import json
import os

import pytest

from benchmarks import flops

HERE = os.path.dirname(os.path.abspath(__file__))
BENCH = os.path.dirname(HERE)


def cfg(name):
    with open(os.path.join(BENCH, "configs", f"{name}.json")) as f:
        return json.load(f)


def test_layer_and_total_parameters():
    c = cfg("mistral7b-d4")
    # wq 4096x4096, wk and wv 4096x1024, wo 4096x4096, three 4096x14336
    assert flops.layer_matmul_params(c) == 218_103_808
    assert flops.head_params(c) == 131_072_000
    # 4 x 218.1M + 262.1M (+ nine norm vectors)
    assert flops.total_params(c) == 4 * 218_103_808 + 2 * 131_072_000 + 9 * 4096
    assert flops.total_params(cfg("mistral7b-d8x4")) == pytest.approx(2007.0e6, rel=1e-3)


def test_attended_keys():
    assert flops.attended_keys(4, None) == 10
    assert flops.attended_keys(4, 2) == 1 + 2 + 2 + 2
    # 8,192 queries under a 4,096 window see 3,072.25 keys on average
    assert flops.attended_keys(8192, 4096) / 8192 == pytest.approx(3072.25)
    assert flops.attended_keys(512, 4096) == 512 * 513 // 2


@pytest.mark.parametrize("config,batch,seen,matmul,attention", [
    ("mistral7b-d4", 2, 8192, 98.65e12, 9.90e12),    # 108 TFLOP a step
    ("mistral7b-d4", 32, 512, 98.65e12, 0.826e12),    # attention under 1%
    ("mistral7b-d8x4", 4, 8192, 368.9e12, 39.6e12),   # 408 TFLOP a step
])
def test_step_flops_by_hand(config, batch, seen, matmul, attention):
    f = flops.step_flops(cfg(config), batch, seen)
    assert f["matmul"] == pytest.approx(matmul, rel=2e-3)
    assert f["attention"] == pytest.approx(attention, rel=2e-3)
    assert f["total"] == f["matmul"] + f["attention"]
    assert f["tokens"] == batch * seen


def test_flash_call_cost_and_roofline():
    c = cfg("mistral7b-d4")
    fwd = flops.flash_call_cost(c, 2, 8192, "fwd")
    bwd = flops.flash_call_cost(c, 2, 8192, "bwd")
    keys = 4096 * 4097 // 2 + 4096 * 4096
    assert fwd["flops"] == 2 * 2 * 2 * 32 * 128 * keys
    assert bwd["flops"] == fwd["flops"] * 5 / 2
    tensor = 2 * 32 * 8192 * 128 * 2
    assert fwd["bytes"] == 4 * tensor + 2 * 32 * 8192 * 4
    peak = {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}
    r = flops.roofline_seconds(fwd["flops"], fwd["bytes"], peak)
    assert r["bound"] == "flops"
    assert r["seconds"] == pytest.approx(fwd["flops"] / 197e12)
    assert flops.roofline_seconds(1.0, 819e9, peak) == {"seconds": 1.0, "bound": "bytes"}
