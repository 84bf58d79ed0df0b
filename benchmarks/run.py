#!/usr/bin/env python3
"""One process, one cell, one result line.

    python benchmarks/run.py --workload <name> --seed <n> --seconds <s> --trace <0|1>

Driven by data: the cell is `benchmarks/workloads/<name>.json`, its
configuration `benchmarks/configs/<config>.json`, its runner
`benchmarks/runners/<runner>.py`; with `--trace 1` every metric file of
`benchmarks/metrics/` that lists the cell (or lists none and moves a
metric the cell reports) is read by the reducer it names under
`benchmarks/reducers/`. A new cell, configuration or metric on an
existing reducer is new files and one entry in `BENCHMARK.json`.

It measures on the machine it is started on, fails without a TPU that
`benchmarks/peaks.json` knows, and never falls back to the CPU.
"""
from __future__ import annotations

import time

T_START = time.perf_counter()  # set-up is counted from here

import argparse
import gc
import glob
import importlib
import json
import os
import shutil
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

TRACED_STEPS = 5
TRACE_DIR = os.path.join(ROOT, ".bench_xplane")
DEFAULT_CACHE_DIR = os.path.join(ROOT, ".jax_cache")


class Refused(Exception):
    """The run cannot be made here: no result line, exit code not 0."""


def load_json(*parts):
    path = os.path.join(HERE, *parts)
    if not os.path.isfile(path):
        raise Refused(f"no such file: {os.path.relpath(path, ROOT)}")
    with open(path) as f:
        return json.load(f)


def place_compile_cache(jax) -> None:
    """JAX's persistent cache at a fixed path inside the checkout, unless
    the environment names one. Every program goes in, the small ones
    too, so that only a checkout's first run of a cell compiles."""
    if not os.environ.get("JAX_COMPILATION_CACHE_DIR"):
        jax.config.update("jax_compilation_cache_dir", DEFAULT_CACHE_DIR)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)


def find_devices(jax, chips: int):
    """The chips the cell asks for and their peaks, or a refusal."""
    try:
        devices = jax.devices()
    except RuntimeError as e:
        raise Refused(f"JAX found no device: {e}") from e
    platform = devices[0].platform
    if platform != "tpu":
        raise Refused(f"JAX found platform {platform!r}, not a TPU; the "
                      "benchmark does not fall back")
    kind = devices[0].device_kind
    peaks = load_json("peaks.json")["devices"]
    if kind not in peaks:
        raise Refused(f"device_kind {kind!r} is not in benchmarks/peaks.json")
    if len(devices) < chips:
        raise Refused(f"the cell asks for {chips} chips, JAX found {len(devices)}")
    return devices[:chips], peaks[kind]


def metric_files(cell_name: str, reported):
    """Per-layer metric files that have something to read in this cell."""
    out = []
    for path in sorted(glob.glob(os.path.join(HERE, "metrics", "*.json"))):
        with open(path) as f:
            m = json.load(f)
        listed = m.get("workloads")
        if listed is not None and cell_name not in listed:
            continue
        if listed is None and m["moves"] not in reported:
            continue
        out.append(m)
    return out


def execute(cell, cfg, seed, seconds, trace, devices, peak, t_start=None,
            keep_trace=False):
    """The whole of a run behind the look for a chip: set-up, window,
    traced steps, the comparison, the result. `peak` is None where the
    device has none (a rehearsal on the CPU): no device metric is made."""
    from benchmarks import trace as tr

    t_start = time.perf_counter() if t_start is None else t_start
    runner = importlib.import_module(f"benchmarks.runners.{cell['runner']}")
    run = runner.Run(cell, cfg, seed, devices)
    run.setup()
    setup_s = time.perf_counter() - t_start
    window = run.window(seconds)

    traced = trace_data = None
    if trace:
        tdir = os.path.join(TRACE_DIR, cell["name"])
        shutil.rmtree(tdir, ignore_errors=True)
        traced = run.traced_steps(TRACED_STEPS, tdir)
        xplane = tr.find_xplane(tdir)
        trace_data = tr.load(xplane) if xplane else None
        if not keep_trace:
            shutil.rmtree(tdir, ignore_errors=True)
    memory_peak = run.memory_peak_bytes()

    end_to_end = run.end_to_end(window, setup_s)
    run.free()
    gc.collect()
    ok, compared = run.verify()

    dev0 = devices[0]
    device = {"platform": dev0.platform, "kind": dev0.device_kind,
              "count": len(devices), "memory_peak_bytes": memory_peak}
    result = {"correct": bool(ok), "attempted": window["steps"], "failed": 0}
    if not trace:
        result["metrics"] = end_to_end
    elif peak is None:
        result["metrics"] = {}  # no chip: no per-layer number is made
    else:
        fmt = tr.trace_format()
        ctx = {"cell": cell, "cfg": cfg, "peak": peak, "window": window,
               "traced": traced, "trace": trace_data, "fmt": fmt,
               "memory_peak_bytes": memory_peak}
        metrics = {}
        for m in metric_files(cell["name"], set(end_to_end)):
            reducer = importlib.import_module(f"benchmarks.reducers.{m['reducer']}")
            value = reducer.reduce(ctx, m.get("params", {}))
            if value is not None:
                metrics[m["name"]] = {"value": value, "unit": m["unit"]}
        result["metrics"] = metrics
        busy = tr.busy_seconds(trace_data, fmt) if trace_data else None
        if busy is not None:
            device["busy_s"] = busy
            device["window_s"] = traced["window_s"]
            result["breakdown"] = tr.breakdown(trace_data, fmt)
    result["device"] = device
    result["window"] = window
    result["setup_phases"] = dict(run.phases, before_run_s=setup_s - run.phases["run_setup_s"])
    result["compared"] = compared
    return result


def report(result) -> None:
    """Each number compared beside its limit as the last lines of
    standard error; the result as the last line of standard output."""
    sys.stdout.flush()
    for name, c in result["compared"].items():
        print(f"compared {name}: value {c['value']!r} limit {c['limit']!r}",
              file=sys.stderr)
    print(f"correct: {result['correct']}", file=sys.stderr, flush=True)
    print(json.dumps(result), flush=True)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--keep-trace", action="store_true",
                    help="leave the .xplane.pb under .bench_xplane/ (for "
                         "benchmarks/tools/trace_summary.py)")
    args = ap.parse_args(argv)
    try:
        cell = load_json("workloads", f"{args.workload}.json")
        cfg = load_json("configs", f"{cell['config']}.json")
        try:
            import jax

            import kubedl_tpu  # noqa: F401 — the system under test
        except ImportError as e:
            raise Refused(f"the program is not in this directory: {e}") from e
        place_compile_cache(jax)
        devices, peak = find_devices(jax, int(cell["chips"]))
    except Refused as e:
        print(f"benchmarks/run.py: {e}", file=sys.stderr)
        return 3
    result = execute(cell, cfg, args.seed, args.seconds, bool(args.trace),
                     devices, peak, T_START, keep_trace=args.keep_trace)
    report(result)
    return 0


if __name__ == "__main__":
    sys.exit(main())
