"""The metrics that find their events by the program's own names
(`name=` on the Pallas kernels, the jitted step's name), on a hand-built
trace: named kernels inside and outside a shard_map, a renamed backward
that must make the by-name metric read nothing, and the step's program
on two device planes beside a stray one."""
import copy
import importlib
import json
import os
import re

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
        "trace": tr.load(os.path.join(HERE, "data", "hand_trace_named.json")),
        "traced": {"step_s": [0.3, 0.1, 0.2], "window_s": 4200e-9, "steps": 3},
    }


def reduce(name, ctx):
    m = metric(name)
    mod = importlib.import_module(f"benchmarks.reducers.{m['reducer']}")
    return mod.reduce(ctx, m.get("params", {}))


def least(ctx, kind):
    cell = ctx["cell"]
    cost = flops.flash_call_cost(
        ctx["cfg"], cell["batch"] // cell["chips"], cell["seen_len"], kind)
    return flops.roofline_seconds(cost["flops"], cost["bytes"], PEAK)["seconds"]


def rename(ctx, old, new):
    """Every event's name with the regular expression `old` replaced."""
    for plane in ctx["trace"]["planes"]:
        for line in plane["lines"]:
            for ev in line["events"]:
                ev[0] = re.sub(old, new, ev[0])


def test_flash_fwd_by_name(ctx):
    # three forward calls a device (two flash_fwd, one flash_fwd_streamed)
    # of 100 ns on device 0 and 200 ns on device 1; the renamed kernel,
    # the unnamed shard_map call and the fusion that reads %flash_fwd.3
    # are not forwards
    want = 100 * (3 * least(ctx, "fwd")) * (1 / 300e-9 + 1 / 600e-9) / 2
    assert reduce("flash_fwd_roofline.train", ctx) == pytest.approx(want)


def test_flash_bwd_by_name(ctx):
    # one dq (150 ns) and one dk/dv (250 ns) a device: one backward call
    want = 100 * least(ctx, "bwd") / 400e-9
    assert reduce("flash_bwd_roofline.train", ctx) == pytest.approx(want)


def test_by_shape_metric_lies_between_and_counts_the_renamed_kernel(ctx):
    """The accepted by-shape metric reads every Mosaic call of these
    result shapes, the renamed and the unnamed ones too: what the
    by-name metrics are for."""
    got = reduce("flash_roofline.train", ctx)
    lo, hi = sorted([reduce("flash_fwd_roofline.train", ctx),
                     reduce("flash_bwd_roofline.train", ctx)])
    # 4 forward-shaped and 3 backward-shaped events on each device
    fwd, bwd = least(ctx, "fwd"), least(ctx, "bwd")
    want = 100 * (4 * fwd + 1.5 * bwd) * (1 / 900e-9 + 1 / 1200e-9) / 2
    assert got == pytest.approx(want)
    assert lo != hi


@pytest.mark.parametrize("old,new,gone,stays", [
    # a perf_opt fuses dq with dk/dv into one kernel under a new name
    (r"%flash_bwd_(dq|dkv)\.", "%flash_bwd.", "flash_bwd_roofline.train",
     "flash_fwd_roofline.train"),
    (r"%flash_fwd", "%attention_fwd", "flash_fwd_roofline.train",
     "flash_bwd_roofline.train"),
])
def test_a_renamed_kernel_goes_missing_not_wrong(ctx, old, new, gone, stays):
    before = reduce(stays, ctx)
    rename(ctx, old, new)
    assert reduce(gone, ctx) is None
    assert reduce(stays, ctx) == pytest.approx(before)
    # the by-shape metric reads on as if nothing had happened
    assert reduce("flash_roofline.train", ctx) is not None


def test_parent_program_reports_none_of_them(ctx):
    """On a program without the names (the parent of the PR that brought
    them) each reader finds nothing and raises nothing."""
    rename(ctx, "%flash_fwd", "%checkpoint")
    rename(ctx, "%flash_bwd_dq", "%jvp__")
    rename(ctx, "%flash_bwd_dkv", "%jvp__")
    rename(ctx, r"jit_train_step\(", "jit__step(")
    for name in ("flash_fwd_roofline.train", "flash_bwd_roofline.train",
                 "step_device_ms.train"):
        assert reduce(name, ctx) is None
    ctx["trace"] = None
    assert reduce("step_device_ms.train", ctx) is None


def test_step_device_ms_is_the_median_per_device_then_the_mean(ctx):
    # device 0: 1000, 1200, 1100 ns -> 1100; device 1: 1400, 1300, 1500
    # -> 1400; the 3 ns jit_convert_element_type program is ignored
    assert reduce("step_device_ms.train", ctx) == pytest.approx(1250e-6)
    one = copy.deepcopy(ctx)
    one["trace"]["planes"] = one["trace"]["planes"][:1] + one["trace"]["planes"][2:]
    assert reduce("step_device_ms.train", one) == pytest.approx(1100e-6)


def test_breakdown_names_the_kernels(ctx):
    names = [n for n, _ in tr.breakdown(ctx["trace"], ctx["fmt"])["device_ops"]]
    assert "flash_bwd_dkv.5 tpu_custom_call bf16[8,256,64]" in names
    assert "flash_fwd.3 tpu_custom_call bf16[8,256,64]" in names

