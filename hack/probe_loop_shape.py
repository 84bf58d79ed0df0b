#!/usr/bin/env python3
"""The two shapes a looped stack's passes can take in the program, side
by side at a benchmark cell's size: a `lax.scan` over the passes (what
`kubedl_tpu/models/llama.py:_backbone` does) and the same loop unrolled
in Python (T x N layer bodies in one program).

    JAX_PLATFORMS=cpu python hack/probe_loop_shape.py --describe   # no chip: compile for a described v5e
    python hack/probe_loop_shape.py [--steps 6]                    # on the chip: compile, run, time

Prints a JSON line a shape: trace+lower and compile seconds, the
compiler's memory analysis and, on the chip, the median step and
`peak_bytes_in_use`. PERF.md section 6 (PR 30) has the readings.
"""
from __future__ import annotations

import argparse
import json
import os
import statistics
import sys
import time

os.environ.setdefault("TPU_LOG_DIR", "disabled")
ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

CELL = "ouro-2.6b-d8-train-8k"


def python_loop(f, carry, xs, length):
    import jax
    import jax.numpy as jnp

    ys = []
    for _ in range(length):
        carry, y = f(carry, None)
        ys.append(y)
    return carry, jax.tree_util.tree_map(lambda *a: jnp.stack(a), *ys)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--describe", action="store_true")
    ap.add_argument("--steps", type=int, default=6)
    ap.add_argument("--batch", type=int)
    ap.add_argument("--shapes", default="scan,unrolled")
    args = ap.parse_args(argv)

    import jax

    from benchmarks import run as R
    from benchmarks import weights_looped
    from benchmarks.runners import train_looped
    from kubedl_tpu.models import llama

    jax.config.update("jax_enable_compilation_cache", False)
    cell = R.load_json("workloads", f"{CELL}.json")
    cfg = R.load_json("configs", f"{cell['config']}.json")
    if args.batch:
        cell = dict(cell, batch=args.batch)
    if args.describe:
        from jax.experimental import topologies

        topo = topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
        devices = topo.devices[:1]
        jax.default_backend = lambda: "tpu"  # the kernels ask it whether to interpret
    else:
        devices, _ = R.find_devices(jax, 1)
    scan = llama._scan_passes
    for shape in args.shapes.split(","):
        llama._scan_passes = scan if shape == "scan" else python_loop
        run = train_looped.Run(cell, cfg, 1, devices)
        run.build()
        shapes = jax.eval_shape(weights_looped.make_fn(cfg), jax.random.PRNGKey(0))
        sds = lambda s, sh: jax.ShapeDtypeStruct(s.shape, s.dtype, sharding=sh)
        params = jax.tree_util.tree_map(sds, shapes, run.param_shardings)
        init = run.init_state.jit
        state = jax.tree_util.tree_map(
            sds, jax.eval_shape(init, params),
            init.lower(params).compile().output_shardings)
        tokens = jax.ShapeDtypeStruct(
            (run.batch, run.seen_len + 1), "int32", sharding=run.batch_sharding)
        t0 = time.perf_counter()
        lowered = run.jit_step.lower(state, tokens)
        t1 = time.perf_counter()
        compiled = lowered.compile()
        t2 = time.perf_counter()
        ma, text = compiled.memory_analysis(), compiled.as_text()
        rec = {"shape": shape, "batch": run.batch, "trace_lower_s": t1 - t0,
               "compile_s": t2 - t1, "argument_bytes": ma.argument_size_in_bytes,
               "temp_bytes": ma.temp_size_in_bytes,
               "peak_estimate_bytes": ma.argument_size_in_bytes
               + ma.output_size_in_bytes - ma.alias_size_in_bytes + ma.temp_size_in_bytes,
               "code_bytes": ma.generated_code_size_in_bytes,
               "mosaic_kernels": text.count('custom_call_target="tpu_custom_call"'),
               "while_loops": text.count(" while(")}
        if not args.describe:
            st = run.init_state(run.make_weights(1))
            times = []
            for _ in range(args.steps):
                batch = run._put(run.next_batch())
                t = time.perf_counter()
                st, metrics = compiled(st, batch)
                jax.block_until_ready(metrics["loss"])
                times.append(time.perf_counter() - t)
            rec["step_s"] = times
            rec["step_median_s"] = statistics.median(times[1:])
            rec["loss"] = float(metrics["loss"])
            rec["memory_peak_bytes"] = run.memory_peak_bytes()
            del st, metrics
        print(json.dumps(rec), flush=True)
        del run, compiled, lowered
    return 0


if __name__ == "__main__":
    sys.exit(main())
