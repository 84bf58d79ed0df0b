#!/usr/bin/env python3
"""What one cell's set-up sends the compile log, and what listening costs.

    python hack/probe_compile_log.py --workload <cell> [--seed N] \
        [--head-block N] [--out chiprun_out/probe_compile_log.<cell>.json]

Drives `benchmarks/runners/<runner>.py:Run.setup()` of a benchmark cell as
`benchmarks/run.py` does (the same cache directory and settings, the same
devices), with the four listeners of `kubedl_tpu/obs/compiles.py` wrapped
so that each call is counted and timed on its own `perf_counter` span, and
prints one JSON line:

* `events` by kind and `listener_s`, the seconds the listeners took in
  all: what the log costs a set-up when no trace is exported;
* `phases`, the runner's own split of set-up (`first_step_s` is what the
  step's three phases are read against), and `records`, every compiled
  function the log kept, `train_step` among them;
* `nested`, the dozen functions with the most own tracing time: which
  inner function the step's trace is spent in (ROADMAP Speed 6(b));
* `mosaic_lowering`, calls and seconds of Pallas's lowering of a kernel to
  Mosaic, by the kernel's `name=`, inside JAX's conversion of the step to
  MLIR: each `pallas_call` site of the step is lowered again, and this is
  where `step_lower_s.train` goes in a model of many sites (PERF.md Open
  question 27d). `--head-block N` traces the state-space scan's kernels
  with N heads a program (`ops/ssm_scan.py:HEAD_BLOCK`, steered here and
  by no switch of the program).

Times here are the host's. `--tiny CELL CONFIG` rehearses on the CPU with
two files of `benchmarks/tests/data/` and measures nothing worth keeping.
"""
from __future__ import annotations

import argparse
import importlib
import json
import os
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)


def timed_listeners(log, monitoring):
    """The log's four listeners, each behind a wrapper that counts its
    calls and sums its own time; registered in the originals' place."""
    cost = {"events": {"scalar": 0, "time_span": 0, "duration": 0, "event": 0},
            "listener_s": 0.0}

    def wrap(kind, listener):
        def timed(*args, **kwargs):
            t0 = time.perf_counter()
            listener(*args, **kwargs)
            cost["listener_s"] += time.perf_counter() - t0
            cost["events"][kind] += 1
        return timed

    monitoring.unregister_scalar_listener(log.on_scalar)
    monitoring.unregister_event_time_span_listener(log.on_span)
    monitoring.unregister_event_duration_listener(log.on_duration)
    monitoring.unregister_event_listener(log.on_event)
    monitoring.register_scalar_listener(wrap("scalar", log.on_scalar))
    monitoring.register_event_time_span_listener(wrap("time_span", log.on_span))
    monitoring.register_event_duration_secs_listener(wrap("duration", log.on_duration))
    monitoring.register_event_listener(wrap("event", log.on_event))
    return cost


def timed_mosaic_lowering():
    """Pallas's lowering of a kernel to Mosaic, counted and timed by the
    kernel's name (the rule is looked up on its module at every call)."""
    from jax._src.pallas.mosaic import pallas_call_registration as reg

    by_name = {}
    rule = reg.pallas_call_tpu_lowering_rule

    def timed(ctx, *in_nodes, **params):
        t0 = time.perf_counter()
        try:
            return rule(ctx, *in_nodes, **params)
        finally:
            row = by_name.setdefault(params.get("name") or "?", [0, 0.0])
            row[0] += 1
            row[1] += time.perf_counter() - t0

    reg.pallas_call_tpu_lowering_rule = timed
    return by_name


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload")
    ap.add_argument("--tiny", nargs=2, metavar=("CELL", "CONFIG"))
    ap.add_argument("--seed", type=int, default=2147840001)
    ap.add_argument("--head-block", type=int, default=0)
    ap.add_argument("--out", default="")
    args = ap.parse_args(argv)

    import jax
    from jax import monitoring

    from benchmarks import run as R
    from kubedl_tpu.obs import compiles

    if args.tiny:
        data = os.path.join(ROOT, "benchmarks", "tests", "data")
        with open(os.path.join(data, args.tiny[0])) as f:
            cell = json.load(f)
        with open(os.path.join(data, args.tiny[1])) as f:
            cfg = json.load(f)
        devices = jax.devices()[:int(cell["chips"])]
    else:
        cell = R.load_json("workloads", f"{args.workload}.json")
        cfg = R.load_json("configs", f"{cell['config']}.json")
        R.place_compile_cache(jax)
        devices, _ = R.find_devices(jax, int(cell["chips"]))
    if args.head_block:
        from kubedl_tpu.ops import ssm_scan

        ssm_scan.HEAD_BLOCK = args.head_block

    log = compiles.install()
    cost = timed_listeners(log, monitoring)
    mosaic = timed_mosaic_lowering()
    runner = importlib.import_module(f"benchmarks.runners.{cell['runner']}")
    run = runner.Run(cell, cfg, args.seed, devices)
    t0 = time.perf_counter()
    run.setup()
    setup_s = time.perf_counter() - t0

    table = sorted(log.nested().items(), key=lambda kv: -kv[1]["own_s"])[:12]
    out = {
        "cell": cell["name"], "seed": args.seed, "head_block": args.head_block or None,
        "device": {"platform": devices[0].platform, "kind": devices[0].device_kind},
        "run_setup_s": setup_s, "phases": run.phases,
        "events": cost["events"], "events_total": sum(cost["events"].values()),
        "listener_s": cost["listener_s"], "listener_errors": log.errors,
        "compiled_functions": log.count(), "records": log.records(),
        "nested": [dict(fun=name, **row) for name, row in table],
        "mosaic_lowering": {name: {"calls": calls, "seconds": seconds}
                            for name, (calls, seconds) in sorted(mosaic.items())},
    }
    line = json.dumps(out)
    if args.out:
        os.makedirs(os.path.dirname(os.path.abspath(args.out)), exist_ok=True)
        with open(args.out, "w") as f:
            f.write(line + "\n")
    print(line, flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
