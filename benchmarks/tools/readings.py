#!/usr/bin/env python3
"""The readings a cell's limits are set from, many seeds in one process.

    python benchmarks/tools/readings.py --workload <name> --seeds 12 \
        --control-seeds 3 --fault-seeds 3 [--out chiprun_out/readings.jsonl]

For every seed: the program's first steps through the runner's own
set-up (no measured window: training's readings need none), then the
float32 reference, and the three numbers of benchmarks/check.py: the
lower readings. For the first `--control-seeds`: the int8 control put in
the program's place against the same reference; for the first
`--fault-seeds`: each planted fault likewise. One JSON line a seed.
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
    ap.add_argument("--workload")
    ap.add_argument("--cell-file", help="a cell file outside workloads/ (tests)")
    ap.add_argument("--config-file")
    ap.add_argument("--seeds", type=int, default=12)
    ap.add_argument("--first-seed", type=int, default=2_200_000_001)
    ap.add_argument("--control-seeds", type=int, default=3)
    ap.add_argument("--fault-seeds", type=int, default=3)
    ap.add_argument("--faults", default="half_batch")
    ap.add_argument("--controls", default="int8,fp8")
    ap.add_argument("--allow-cpu", action="store_true")
    ap.add_argument("--out")
    args = ap.parse_args(argv)

    import jax

    from benchmarks import check, run as R
    from benchmarks.reference import llama_ref

    if args.cell_file:
        cell = json.load(open(args.cell_file))
        cfg = json.load(open(args.config_file))
    else:
        cell = R.load_json("workloads", f"{args.workload}.json")
        cfg = R.load_json("configs", f"{cell['config']}.json")
    if args.allow_cpu:
        devices = jax.devices()[:int(cell["chips"])]
    else:
        R.place_compile_cache(jax)
        devices, _ = R.find_devices(jax, int(cell["chips"]))
    runner = importlib.import_module(f"benchmarks.runners.{cell['runner']}")
    n_full = int(cell["reference"]["steps"])
    out = open(args.out, "a") if args.out else None

    def emit(rec):
        line = json.dumps(rec)
        print(line, flush=True)
        if out:
            out.write(line + "\n")
            out.flush()

    for i in range(args.seeds):
        seed = args.first_seed + 7919 * i
        t0 = time.perf_counter()
        run = runner.Run(cell, cfg, seed, devices)
        run.setup()
        t_prog = time.perf_counter() - t0
        program, batches = run.readings, run.first_batches
        run.free()
        del run
        gc.collect()

        def reference(mode="f32", fault=None):
            t = time.perf_counter()
            ref = llama_ref.Reference(cfg, cell, seed, devices, mode=mode, fault=fault)
            r = ref.run(batches, n_full)
            del ref
            gc.collect()
            return r, time.perf_counter() - t

        ref, t_ref = reference()
        rec = {"workload": cell["name"], "seed": seed,
               "program": check.numbers(program, ref),
               "worst": check.worst_leaves(program, ref),
               "loss": {"program": program["loss"], "reference": ref["loss"]},
               "program_s": t_prog, "reference_s": t_ref}
        if i < args.control_seeds:
            for mode in [m for m in args.controls.split(",") if m]:
                ctl, t_ctl = reference(mode=mode)
                rec[f"control_{mode}"] = check.numbers(ctl, ref)
                rec[f"control_{mode}_s"] = t_ctl
        if i < args.fault_seeds:
            for fault in [f for f in args.faults.split(",") if f]:
                bad, _ = reference(fault=fault)
                rec[f"fault_{fault}"] = check.numbers(bad, ref)
        emit(rec)
    if out:
        out.close()
    return 0


if __name__ == "__main__":
    sys.exit(main())
