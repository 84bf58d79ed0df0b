#!/usr/bin/env python3
"""`readings.py` for a cell whose runner brings its own reference: the
readings a cell's limits are set from, many seeds in one process.

    python benchmarks/tools/readings_of_runner.py --workload <name> --seeds 16 \
        --control-seeds 4 [--out chiprun_out/readings.jsonl]

For every seed: the program's first steps through the runner's own
set-up (no measured window), then the runner's float32 reference
(`Run.reference()`), and the numbers of benchmarks/check.py: the lower
readings. For the first `--control-seeds`: each control (the reference
in a lower precision) and each planted fault put in the program's place
against the same reference: the upper readings. One JSON line a seed.
"""
from __future__ import annotations

import argparse
import gc
import importlib
import json
import os
import sys
import time

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=int, default=16)
    ap.add_argument("--first-seed", type=int, default=2_200_000_001)
    ap.add_argument("--control-seeds", type=int, default=4)
    ap.add_argument("--controls", default="fp8")
    ap.add_argument("--faults", default="")
    ap.add_argument("--out")
    args = ap.parse_args(argv)

    import jax

    from benchmarks import check, run as R

    cell = R.load_json("workloads", f"{args.workload}.json")
    cfg = R.load_json("configs", f"{cell['config']}.json")
    R.place_compile_cache(jax)
    devices, _ = R.find_devices(jax, int(cell["chips"]))
    runner = importlib.import_module(f"benchmarks.runners.{cell['runner']}")
    numbers = getattr(runner, "numbers", check.numbers)  # the runner's own, if any
    out = open(args.out, "a") if args.out else None
    for i in range(args.seeds):
        seed = args.first_seed + 7919 * i
        t0 = time.perf_counter()
        run = runner.Run(cell, cfg, seed, devices)
        run.setup()
        t_prog = time.perf_counter() - t0
        program = run.readings
        run.free()
        gc.collect()
        t0 = time.perf_counter()
        ref = run.reference()
        rec = {"workload": cell["name"], "seed": seed,
               "program": numbers(program, ref),
               "worst": check.worst_leaves(program, ref),
               "loss": {"program": program["loss"], "reference": ref["loss"]},
               "program_s": t_prog, "reference_s": time.perf_counter() - t0}
        if i < args.control_seeds:
            for mode in [m for m in args.controls.split(",") if m]:
                t0 = time.perf_counter()
                rec[f"control_{mode}"] = numbers(run.reference(mode=mode), ref)
                rec[f"control_{mode}_s"] = time.perf_counter() - t0
            for fault in [f for f in args.faults.split(",") if f]:
                rec[f"fault_{fault}"] = numbers(run.reference(fault=fault), ref)
        del run
        gc.collect()
        line = json.dumps(rec)
        print(line, flush=True)
        if out:
            out.write(line + "\n")
            out.flush()
    if out:
        out.close()
    return 0


if __name__ == "__main__":
    sys.exit(main())
