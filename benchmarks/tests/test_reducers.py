"""Each reducer on a hand-built trace with known busy intervals, kernel
events, one exposed and one hidden collective."""
import importlib
import json
import os

import pytest

from benchmarks import flops
from benchmarks import trace as tr

HERE = os.path.dirname(os.path.abspath(__file__))
BENCH = os.path.dirname(HERE)
PEAK = {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}


def metric(name):
    with open(os.path.join(BENCH, "metrics", f"{name}.json")) as f:
        return json.load(f)


@pytest.fixture
def ctx():
    with open(os.path.join(HERE, "data", "tiny-config.json")) as f:
        cfg = json.load(f)
    cell = {"name": "hand", "batch": 4, "seen_len": 256, "chips": 2}
    return {
        "cell": cell, "cfg": cfg, "peak": PEAK, "fmt": tr.trace_format(),
        "trace": tr.load(os.path.join(HERE, "data", "hand_trace.json")),
        "window": {"steps": 10, "tokens": 10 * 1024, "elapsed_s": 2.0},
        "traced": {"step_s": [0.3, 0.1, 0.2, 0.5, 0.4], "window_s": 1000e-9, "steps": 5},
        "memory_peak_bytes": 3 * 2**30,
    }


def reduce(name, ctx):
    m = metric(name)
    mod = importlib.import_module(f"benchmarks.reducers.{m['reducer']}")
    return mod.reduce(ctx, m.get("params", {}))


def test_idle_share(ctx):
    # device 0 is busy 900 of 1,000 ns, device 1 800: the async line and
    # the nested all-reduce add nothing
    assert reduce("device_idle_share.train", ctx) == pytest.approx(15.0)
    assert tr.busy_seconds(ctx["trace"], ctx["fmt"]) == pytest.approx(850e-9)


def test_flash_time_share(ctx):
    assert reduce("flash_time_share.train", ctx) == pytest.approx(30.0)


def test_collective_exposed_share(ctx):
    # all-gather.1 runs alone for 100 ns; all-reduce.2 lies inside fusion.2
    assert reduce("collective_exposed_share.train", ctx) == pytest.approx(10.0)


def test_flash_roofline(ctx):
    cfg, cell = ctx["cfg"], ctx["cell"]
    rows = cell["batch"] // cell["chips"]
    fwd = flops.flash_call_cost(cfg, rows, 256, "fwd")
    bwd = flops.flash_call_cost(cfg, rows, 256, "bwd")
    least = (flops.roofline_seconds(fwd["flops"], fwd["bytes"], PEAK)["seconds"]
             + flops.roofline_seconds(bwd["flops"], bwd["bytes"], PEAK)["seconds"])
    # one forward call (100 ns) and one backward pair (200 ns) per device
    assert reduce("flash_roofline.train", ctx) == pytest.approx(100 * least / 300e-9)


def test_step_median_mfu_and_memory(ctx):
    assert reduce("step_ms_median.train", ctx) == pytest.approx(300.0)
    assert reduce("peak_hbm_gib.train", ctx) == pytest.approx(3.0)
    per_step = flops.step_flops(ctx["cfg"], 4, 256)["total"]
    assert reduce("mfu_step.train", ctx) == pytest.approx(
        100 * per_step * 10 / 2.0 / (2 * 197e12))


def test_nothing_to_read_returns_nothing(ctx):
    """No trace or no matching event: no number, never a 0. (A run
    without a chip makes no per-layer number at all: test_harness.py.)"""
    for plane in ctx["trace"]["planes"]:
        for line in plane["lines"]:
            line["events"] = [e for e in line["events"]
                              if "tpu_custom_call" not in e[0] and "all-" not in e[0]]
    assert reduce("flash_roofline.train", ctx) is None
    assert reduce("flash_time_share.train", ctx) is None
    assert reduce("collective_exposed_share.train", ctx) is None
    ctx["trace"] = None
    assert reduce("device_idle_share.train", ctx) is None
    ctx["memory_peak_bytes"] = None  # the CPU backend reports none
    assert reduce("peak_hbm_gib.train", ctx) is None


def test_breakdown_names_gaps_by_host_span(ctx):
    b = tr.breakdown(ctx["trace"], ctx["fmt"])
    names = [n for n, _ in b["device_ops"]]
    assert "fusion.1 fusion bf16[8,128]" in names
    assert "custom-call.7 tpu_custom_call bf16[8,256,64]" in names
    assert dict(b["device_ops"])["fusion.1 fusion bf16[8,128]"] == pytest.approx(250e-9)
    # device 0's one gap, 700-800 ns, falls under the closing wait
    assert b["idle_gaps"] == [["bench.sync", pytest.approx(100e-9)]]


def test_interval_arithmetic():
    assert tr.union([(5, 7), (0, 2), (1, 3)]) == [(0, 3), (5, 7)]
    assert tr.subtract([(0, 10)], [(2, 3), (5, 12)]) == [(0, 2), (3, 5)]
    assert tr.subtract([(0, 4), (6, 9)], [(3, 7)]) == [(0, 3), (7, 9)]
    assert tr.gaps([(0, 1), (3, 4)]) == [(1, 3)]
