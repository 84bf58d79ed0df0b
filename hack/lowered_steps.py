#!/usr/bin/env python3
"""Whether a change left the benchmark's cells' train steps as they were:
each cell's step lowered for a described (not attached) v5e:2x2 in two
checkouts, and compared. No chip, no run, no compile: tracing and
lowering only, at the cell's real size.

    JAX_PLATFORMS=cpu python hack/lowered_steps.py --parent .parent [cell ...]

For every cell (all of `BENCHMARK.json`'s that both checkouts can build,
or those named) it prints the sha256 of the lowered StableHLO text in
both trees, whether the texts are equal byte for byte, and whether they
are equal outside the Mosaic kernels' bodies. A kernel's body is
serialized MLIR bytecode that holds its source's path and line numbers,
so it differs wherever a line of a kernel's file moved or the checkout
lies elsewhere; what the kernels compute is compared by the other half:
the jaxpr (which holds every `pallas_call`'s kernel, grid and compiler
parameters and no source location) of `flash_attention`'s forward and
backward at the 8k cells' shapes, and of the state-space convolution's
kernels (`ops/causal_conv.py:split_conv`) at the Granite cell's, hashed
in both trees.
(`git archive <commit> | tar -x -C .parent` makes the second checkout.)
"""
from __future__ import annotations

import argparse
import hashlib
import json
import os
import re
import subprocess
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
BODY = re.compile(r'\\22body\\22: \\22[A-Za-z0-9+/=]*\\22')
# [batch, heads, sequence, head size], window: the Mistral, LFM2 and Ouro cells'
FLASH_SHAPES = [((2, 32, 8192, 128), 4096), ((1, 32, 8192, 128), 4096),
                ((2, 32, 8192, 64), None), ((2, 16, 8192, 128), None)]
# u, taps, bias, offset, widths: the Granite cell's convolution (ops/causal_conv.py)
GRANITE_CONV = ((2, 8192, 8512), (4352, 4), (4352,), 4096, (4096, 128, 128))


def in_tree(cells) -> int:
    """Run inside one checkout (cwd, first on sys.path): a JSON line a cell
    and one for the flash kernels."""
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    sys.path.insert(0, os.getcwd())
    import importlib

    import jax
    import jax.numpy as jnp
    from jax.experimental import topologies

    jax.config.update("jax_enable_compilation_cache", False)
    topo = topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
    jax.default_backend = lambda: "tpu"  # the kernels' own question, steered here
    from benchmarks import run as R

    as_struct = lambda tree, shardings: jax.tree_util.tree_map(
        lambda s, sh: jax.ShapeDtypeStruct(s.shape, s.dtype, sharding=sh), tree, shardings)
    for name in cells:
        try:
            cell = R.load_json("workloads", f"{name}.json")
            cfg = R.load_json("configs", f"{cell['config']}.json")
            runner = importlib.import_module(f"benchmarks.runners.{cell['runner']}")
            run = runner.Run(cell, cfg, 0, topo.devices[:int(cell["chips"])])
            run.build()
        except Exception as e:  # a cell this checkout cannot build
            print(json.dumps({"cell": name, "error": f"{type(e).__name__}: {e}"[:200]}), flush=True)
            continue
        step = getattr(run, "jit_step", None) or run.train_step
        params = as_struct(jax.eval_shape(lambda: run.make_weights(0)), run.param_shardings)
        init = run.init_state.jit
        state = as_struct(jax.eval_shape(init, params),
                          init.lower(params).compile().output_shardings)
        tokens = jax.ShapeDtypeStruct(
            (run.batch, run.seen_len + 1), "int32", sharding=run.batch_sharding)
        text = step.lower(state, tokens).as_text()
        bare = BODY.sub("BODY", text)
        print(json.dumps({
            "cell": name, "bytes": len(text), "kernels": len(BODY.findall(text)),
            "sha256": hashlib.sha256(text.encode()).hexdigest(),
            "sha256_outside_kernel_bodies": hashlib.sha256(bare.encode()).hexdigest()}),
            flush=True)
    from kubedl_tpu.ops import flash_attention as fa

    fa.interpret = lambda: False
    hashes = []
    for shape, window in FLASH_SHAPES:
        x = jax.ShapeDtypeStruct(shape, jnp.bfloat16)
        loss = lambda q, k, v: jnp.sum(fa.flash_attention(
            q, k, v, causal=True, window=window).astype(jnp.float32))
        jaxpr = str(jax.make_jaxpr(jax.value_and_grad(loss, argnums=(0, 1, 2)))(x, x, x))
        hashes.append(hashlib.sha256(jaxpr.encode()).hexdigest()[:16])
    from kubedl_tpu.ops import causal_conv as cc

    cc.interpret = lambda: False
    u = jax.ShapeDtypeStruct(GRANITE_CONV[0], jnp.bfloat16)
    w, b = (jax.ShapeDtypeStruct(s, jnp.float32) for s in GRANITE_CONV[1:3])
    loss = lambda u, w, b: sum(jnp.sum(v.astype(jnp.float32)) for v in cc.split_conv(
        u, w, b, *GRANITE_CONV[3:]))
    jaxpr = str(jax.make_jaxpr(jax.value_and_grad(loss, argnums=(0, 1, 2)))(u, w, b))
    print(json.dumps({"flash_jaxprs": hashes,
                      "conv_jaxpr": hashlib.sha256(jaxpr.encode()).hexdigest()[:16]}),
          flush=True)
    return 0


def lines_of(tree: str, cells) -> dict:
    out = subprocess.run(
        [sys.executable, os.path.abspath(__file__), "--in-tree", *cells], cwd=tree,
        env={**os.environ, "JAX_PLATFORMS": "cpu"}, capture_output=True, text=True)
    rows = [json.loads(l) for l in out.stdout.splitlines() if l.startswith("{")]
    if not rows:
        raise SystemExit(f"{tree}: no result\n{out.stderr[-2000:]}")
    return {r.get("cell", "flash_jaxprs"): r for r in rows}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--parent", help="a second checkout to compare with")
    ap.add_argument("--in-tree", action="store_true", help=argparse.SUPPRESS)
    ap.add_argument("cells", nargs="*")
    args = ap.parse_args(argv)
    if args.in_tree:
        return in_tree(args.cells)
    cells = args.cells
    if not cells:
        with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
            cells = [w["name"] for w in json.load(f)["workloads"]]
    change = lines_of(ROOT, cells)
    parent = lines_of(os.path.abspath(args.parent), cells) if args.parent else {}
    for name in cells:
        c, p = change[name], parent.get(name)
        if p is None or "error" in p or "error" in c:
            print(name, json.dumps({"change": c, "parent": p}))
            continue
        print(f"{name}: kernels {p['kernels']} -> {c['kernels']}, whole text equal "
              f"{p['sha256'] == c['sha256']}, equal outside kernel bodies "
              f"{p['sha256_outside_kernel_bodies'] == c['sha256_outside_kernel_bodies']} "
              f"({c['sha256_outside_kernel_bodies'][:16]})")
    c, p = change["flash_jaxprs"], parent.get("flash_jaxprs")
    print(f"flash kernels' jaxprs at {FLASH_SHAPES}: {c['flash_jaxprs']}"
          + (f", equal to the parent's {p['flash_jaxprs'] == c['flash_jaxprs']}" if p else ""))
    print(f"the state-space convolution's kernels' jaxpr at {GRANITE_CONV}: "
          f"{c['conv_jaxpr']}"
          + (f", equal to the parent's {p['conv_jaxpr'] == c['conv_jaxpr']}" if p else ""))
    return 0


if __name__ == "__main__":
    sys.exit(main())
