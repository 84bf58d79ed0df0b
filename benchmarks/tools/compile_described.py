#!/usr/bin/env python3
"""Compile a cell's train step for a described (not attached) v5e:2x2
and print the compiler's memory analysis. No chip, no run, no time.

    JAX_PLATFORMS=cpu python benchmarks/tools/compile_described.py <cell> [...]
"""
from __future__ import annotations

import json
import os
import sys

os.environ.setdefault("TPU_LOG_DIR", "disabled")
HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)


def main(argv) -> int:
    import jax
    from jax.experimental import topologies

    from benchmarks import run as R
    from benchmarks import weights
    from benchmarks.runners import train

    jax.config.update("jax_enable_compilation_cache", False)
    topo = topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
    # the kernels ask jax.default_backend() whether to interpret; here it
    # still says cpu, so steer it in this script, not in the program
    jax.default_backend = lambda: "tpu"
    for name in argv:
        cell = R.load_json("workloads", f"{name}.json")
        cfg = R.load_json("configs", f"{cell['config']}.json")
        run = train.Run(cell, cfg, 0, topo.devices[:int(cell["chips"])])
        run.build()
        shapes = jax.eval_shape(weights.make_fn(cfg), jax.random.PRNGKey(0))
        params = jax.tree_util.tree_map(
            lambda s, sh: jax.ShapeDtypeStruct(s.shape, s.dtype, sharding=sh),
            shapes, run.param_shardings)
        init = run.init_state.jit
        state = jax.tree_util.tree_map(
            lambda s, sh: jax.ShapeDtypeStruct(s.shape, s.dtype, sharding=sh),
            jax.eval_shape(init, params),
            init.lower(params).compile().output_shardings)
        tokens = jax.ShapeDtypeStruct(
            (run.batch, run.seen_len + 1), "int32", sharding=run.batch_sharding)
        compiled = run.train_step.lower(state, tokens).compile()
        ma = compiled.memory_analysis()
        text = compiled.as_text()
        print(json.dumps({
            "cell": name,
            "argument_bytes": ma.argument_size_in_bytes,
            "output_bytes": ma.output_size_in_bytes,
            "alias_bytes": ma.alias_size_in_bytes,
            "temp_bytes": ma.temp_size_in_bytes,
            "peak_estimate_bytes": ma.argument_size_in_bytes
            + ma.output_size_in_bytes - ma.alias_size_in_bytes
            + ma.temp_size_in_bytes,
            "mosaic_kernels": text.count('custom_call_target="tpu_custom_call"'),
            "all_gather": text.count("all-gather"),
            "reduce_scatter": text.count("reduce-scatter"),
            "all_reduce": text.count("all-reduce"),
        }), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
