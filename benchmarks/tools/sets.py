#!/usr/bin/env python3
"""The runs a bound is set from: for one cell, sets of runs with the
same seeds in every set, each run a process of its own as the driver
makes them, and the spread of every end-to-end metric per set (the
distance between the quartiles as `statistics.quantiles(values, n=4)`
gives them, as a share of the median). This parent never touches JAX.

    python benchmarks/tools/sets.py --workload <name> --seeds 11,12,13,14,15,16 \
        [--sets 2] [--traced-seeds 21,22,23] [--out chiprun_out/sets.jsonl]
"""
from __future__ import annotations

import argparse
import json
import os
import statistics
import subprocess
import sys
import time

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))


def one_run(command, workload, seed, seconds, trace):
    t0 = time.time()
    p = subprocess.run(
        command + ["--workload", workload, "--seed", str(seed),
                   "--seconds", str(seconds), "--trace", str(trace)],
        cwd=ROOT, capture_output=True, text=True)
    rec = {"workload": workload, "seed": seed, "trace": trace, "rc": p.returncode,
           "wall_s": time.time() - t0}
    lines = p.stdout.strip().splitlines()
    try:
        rec["result"] = json.loads(lines[-1])
    except (IndexError, ValueError):
        rec["stderr_tail"] = p.stderr[-2000:]
    return rec


def spread(values):
    q = statistics.quantiles(values, n=4)
    return (q[2] - q[0]) / statistics.median(values)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--sets", type=int, default=2)
    ap.add_argument("--traced-seeds", default="")
    ap.add_argument("--out", default=os.path.join(ROOT, "chiprun_out", "sets.jsonl"))
    args = ap.parse_args(argv)
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    command, seconds = bench["command"], bench["run_seconds"]
    seeds = [int(s) for s in args.seeds.split(",") if s]
    os.makedirs(os.path.dirname(os.path.abspath(args.out)), exist_ok=True)
    summary = {"workload": args.workload, "sets": []}
    with open(args.out, "a") as out:
        def keep(rec, **extra):
            rec.update(extra)
            out.write(json.dumps(rec) + "\n")
            out.flush()
            r = rec.get("result", {})
            print(json.dumps({k: rec[k] for k in ("seed", "trace", "rc", "wall_s")}
                             | {"correct": r.get("correct"),
                                "metrics": {k: v["value"] for k, v in r.get("metrics", {}).items()}}),
                  flush=True)
            return rec

        for s in range(args.sets):
            recs = [keep(one_run(command, args.workload, seed, seconds, 0), set=s)
                    for seed in seeds]
            by_metric = {}
            for rec in recs:
                for name, m in rec.get("result", {}).get("metrics", {}).items():
                    by_metric.setdefault(name, []).append(m["value"])
            summary["sets"].append({
                name: {"median": statistics.median(v),
                       "spread": spread(v) if len(v) >= 2 else None,
                       "first_run": v[0], "n": len(v)}
                for name, v in by_metric.items()})
        for seed in [int(s) for s in args.traced_seeds.split(",") if s]:
            keep(one_run(command, args.workload, seed, seconds, 1), set="traced")
    print("SUMMARY " + json.dumps(summary), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
