#!/usr/bin/env python3
"""The hyper-connections' passes alone, and what they cost a start.

    python hack/probe_hc_kernels.py [--parent .parent] [--calls 20] \
        [--variants 256x32x512,128x16x512] [--out chiprun_out/probe_hc_kernels.json]
    JAX_PLATFORMS=cpu python hack/probe_hc_kernels.py --compile [--parent .parent]

On the chip, one mapping at the Xing4.0 cell's shape (streams `[2, 8192,
4 x 3584]` bf16, the sublayer's output `[2, 8192, 3584]`, the mapping's
leaves as `benchmarks/weights_xing.py` seeds them): u and the streams
after the sublayer from the streams, forward alone and forward with its
backward against fixed cotangents of u and of the new streams, in ms a
call on the host's clock over `--calls` calls with one closing wait; in
this checkout's `kernel` form (`models/hyper.py hc_branch` / `hc_merge`
as a TPU takes them: ops/hyper_mix.py's four Pallas calls), its `xla`
form (traced while `mix_takes_kernel` is made to say no) and, with
`--parent DIR` (`git archive <commit> | tar -x -C DIR`), that checkout's
XLA passes (`hc_map`, `hc_pre`, `hc_mix` over `[b, t, n, d]` streams);
the device ms of each kernel by its `name=` under the profiler; how far
each output and gradient lies from the kernel form's. `--variants` times
the kernel form again at other sizes of a program's token block, a
pass's rows and its lanes (`TOKENSxROWSxLANES`). `--tiny` rehearses on
the CPU at a small shape and measures nothing.

`--compile` needs no chip: it builds the Xing4.0 cell's train step as
`benchmarks/runners/train_latent.py` does, for a described v5e, and
prints its trace, lowering and backend-compile seconds, the serialized
executable's bytes, the compiler's peak estimate and the Mosaic calls by
kernel name, in this checkout and (`--parent DIR`) in that one, each in
a process of its own.
"""
from __future__ import annotations

import argparse
import importlib.util
import json
import os
import re
import statistics
import subprocess
import sys
import tempfile
import time

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
CELL = "xing4.0-29b-a4b-d5e8-train-8k"
# batch, tokens, streams, stream width
SHAPE = (2, 8192, 4, 3584)
TINY = (2, 64, 4, 256)
KERNELS = ("hc_pre_fwd", "hc_post_fwd", "hc_post_bwd", "hc_pre_bwd")
NAMES = ("u", "streams", "dx", "dy", "dp_pre", "dp_post", "dp_res")


def compile_in_tree() -> int:
    """This process's checkout (cwd): the cell's step for a described v5e."""
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    sys.path.insert(0, os.getcwd())
    import jax
    from jax.experimental import topologies

    jax.config.update("jax_enable_compilation_cache", False)
    topo = topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
    jax.default_backend = lambda: "tpu"  # the kernels' own question, steered here
    from benchmarks import run as R
    from benchmarks.runners import train_latent

    cell = R.load_json("workloads", f"{CELL}.json")
    cfg = R.load_json("configs", f"{cell['config']}.json")
    run = train_latent.Run(cell, cfg, 0, topo.devices[:1])
    run.build()
    as_struct = lambda tree, shardings: jax.tree_util.tree_map(
        lambda s, sh: jax.ShapeDtypeStruct(s.shape, s.dtype, sharding=sh), tree, shardings)
    params = as_struct(jax.eval_shape(lambda: run.make_weights(0)), run.param_shardings)
    init = run.init_state.jit
    state = as_struct(jax.eval_shape(init, params),
                      init.lower(params).compile().output_shardings)
    tokens = jax.ShapeDtypeStruct((run.batch, run.seen_len + 1), "int32",
                                  sharding=run.batch_sharding)
    t0 = time.perf_counter()
    traced = run.jit_step.trace(state, tokens)
    t1 = time.perf_counter()
    lowered = traced.lower()
    t2 = time.perf_counter()
    compiled = lowered.compile()
    t3 = time.perf_counter()
    text = compiled.as_text()
    try:
        from jax.experimental.serialize_executable import serialize

        executable_bytes = len(serialize(compiled)[0])
    except Exception as e:  # noqa: BLE001 — a compile-only client may refuse
        executable_bytes = f"{type(e).__name__}: {e}"[:120]
    ma = compiled.memory_analysis()
    kernels = {}
    for name in re.findall(r"^\s*%([a-z_]+)[.\d]* = .*tpu_custom_call", text, re.M):
        kernels[name] = kernels.get(name, 0) + 1
    print(json.dumps({
        "trace_s": t1 - t0, "lower_s": t2 - t1, "compile_s": t3 - t2,
        "executable_bytes": executable_bytes,
        "peak_estimate_bytes": ma.argument_size_in_bytes + ma.output_size_in_bytes
        - ma.alias_size_in_bytes + ma.temp_size_in_bytes,
        "temp_bytes": ma.temp_size_in_bytes,
        "tpu_custom_calls": text.count('custom_call_target="tpu_custom_call"'),
        "kernels": dict(sorted(kernels.items()))}), flush=True)
    return 0


def compile_both(args) -> int:
    trees = {"change": ROOT}
    if args.parent:
        trees["parent"] = os.path.abspath(args.parent)
    record = {}
    for name, tree in trees.items():
        out = subprocess.run(
            [sys.executable, os.path.abspath(__file__), "--compile-in-tree"], cwd=tree,
            env={**os.environ, "JAX_PLATFORMS": "cpu"}, capture_output=True, text=True)
        rows = [json.loads(l) for l in out.stdout.splitlines() if l.startswith("{")]
        if not rows:
            raise SystemExit(f"{tree}: no result\n{out.stderr[-3000:]}")
        record[name] = rows[-1]
        print(name, json.dumps(rows[-1]), flush=True)
    _write(args.out, {"compile": record})
    return 0


def _write(path: str, record) -> None:
    os.makedirs(os.path.dirname(path) or ".", exist_ok=True)
    with open(path, "w") as f:
        json.dump(record, f, indent=1)


# --- on the chip ------------------------------------------------------------

def load_parent_hyper(tree: str):
    path = os.path.join(tree, "kubedl_tpu", "models", "hyper.py")
    spec = importlib.util.spec_from_file_location("hyper_of_parent", path)
    loaded = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(loaded)
    return loaded


def inputs_of(shape, seed=0):
    """The streams and the sublayer's output standard normal in bf16, the
    mapping's leaves as the cell's weights seed them (P_* normal(0, 0.02),
    a_* 0.1, b_res 2 on its diagonal), the two cotangents."""
    import jax
    import jax.numpy as jnp

    b, t, n, d = shape
    ks = jax.random.split(jax.random.PRNGKey(seed), 7)
    bf = lambda k, s: jax.random.normal(k, s, jnp.float32).astype(jnp.bfloat16)
    proj = lambda k, c: jax.random.normal(k, (n * d, c), jnp.float32) * 0.02
    hc = {"p_pre": proj(ks[0], n), "p_post": proj(ks[1], n), "p_res": proj(ks[2], n * n),
          "b_pre": jnp.zeros((n,), jnp.float32), "b_post": jnp.zeros((n,), jnp.float32),
          "b_res": 2.0 * jnp.eye(n, dtype=jnp.float32),
          "a_pre": jnp.full((), 0.1, jnp.float32), "a_post": jnp.full((), 0.1, jnp.float32),
          "a_res": jnp.full((), 0.1, jnp.float32)}
    return ((bf(ks[3], (b, t, n * d)), bf(ks[4], (b, t, d)), hc),
            (bf(ks[5], (b, t, d)), bf(ks[6], (b, t, n * d))))


ARGS = (20, 1e-6, (-30.0, 30.0))  # the cell's iterations, eps, clamp


def this_checkout(kernel: bool, tiny: bool):
    from kubedl_tpu.models import hyper

    name, steer = (("mix_takes_kernel", lambda *a, **kw: kernel) if tiny or not kernel
                   else (None, None))

    def step(x, y, hc):
        was = getattr(hyper, name) if name else None
        if name:
            setattr(hyper, name, steer)
        try:
            u, onto = hyper.hc_branch(x, hc, x.shape[2] // y.shape[2], *ARGS)
            return u, hyper.hc_merge(onto, y)
        finally:
            if name:
                setattr(hyper, name, was)
    return step


def parent_form(module):
    def step(x, y, hc):
        b, t, d = y.shape
        xs = x.reshape(b, t, x.shape[2] // d, d)
        mapping = module.hc_map(xs, hc, *ARGS)
        return module.hc_pre(xs, mapping), module.hc_mix(xs, y, mapping).reshape(x.shape)
    return step


def calls_of(step):
    import jax

    def both(ins, cts):
        outs, vjp = jax.vjp(step, *ins)
        dx, dy, dhc = vjp(cts)
        return tuple(outs) + (dx, dy, dhc["p_pre"], dhc["p_post"], dhc["p_res"])

    return jax.jit(lambda ins: step(*ins)), jax.jit(both)


def timed(fn, args, calls: int):
    import jax

    out = jax.block_until_ready(fn(*args))
    jax.block_until_ready(fn(*args))
    t0 = time.perf_counter()
    for _ in range(calls):
        last = fn(*args)
    jax.block_until_ready(last)
    return (time.perf_counter() - t0) / calls * 1e3, out


def kernel_ms(fn, args, calls: int):
    """Median device ms of each kernel over `calls` runs under the profiler."""
    import jax

    sys.path.insert(0, ROOT)
    from benchmarks import trace as tr

    with tempfile.TemporaryDirectory() as trace_dir:
        with jax.profiler.trace(trace_dir):
            for _ in range(calls):
                jax.block_until_ready(fn(*args))
        path = tr.find_xplane(trace_dir)
        trace = tr.load(path) if path else {"planes": []}
    fmt = tr.trace_format()
    found = {}
    for plane in tr.device_planes(trace, fmt)[:1]:
        for kernel in KERNELS:
            events = tr.matching(tr.op_events(plane, fmt), rf"^%{kernel}[.\d]* = ")
            if events:
                found[kernel] = statistics.median(ev[2] for ev in events) / 1e6
    return found


def gap(got, want) -> float:
    import jax.numpy as jnp
    import numpy as np

    got, want = (np.asarray(v.astype(jnp.float32)) for v in (got, want))
    return float(np.linalg.norm(got - want) / np.linalg.norm(want))


def probe_chip(args) -> int:
    sys.path.insert(0, ROOT)
    import jax

    from kubedl_tpu.ops import hyper_mix

    device = jax.devices()[0]
    if device.platform != "tpu" and not args.tiny:
        print(f"probe_hc_kernels: needs a TPU, found {device.platform} "
              "(--tiny rehearses on the CPU and measures nothing)", file=sys.stderr)
        return 2
    shape = TINY if args.tiny else SHAPE
    record = {"device": device.device_kind, "platform": device.platform,
              "calls": args.calls, "shape": shape, "forms": {}}
    forms = {"kernel": this_checkout(True, args.tiny),
             "xla": this_checkout(False, args.tiny)}
    if args.parent:
        forms["parent"] = parent_form(load_parent_hyper(args.parent))
    variants = [tuple(int(v) for v in s.split("x")) for s in args.variants.split(",") if s]
    sizes = ("TOKEN_BLOCK", "ROWS", "LANES")
    default = tuple(getattr(hyper_mix, k) for k in sizes)
    runs = [(name, step, default) for name, step in forms.items()]
    runs += [(f"kernel_{'x'.join(map(str, v))}", forms["kernel"], v) for v in variants]
    ins, cts = inputs_of(shape)
    first = None
    for name, step, variant in runs:
        for k, v in zip(sizes, variant):
            setattr(hyper_mix, k, v)
        jax.clear_caches()  # the wrappers' traces hold the sizes
        fwd, both = calls_of(step)
        fwd_ms, _ = timed(fwd, (ins,), args.calls)
        both_ms, out = timed(both, (ins, cts), args.calls)
        row = {"sizes": list(variant)}
        if not args.tiny:
            row.update(fwd_ms=fwd_ms, fwd_bwd_ms=both_ms,
                       kernels_ms=kernel_ms(both, (ins, cts), args.calls))
        first = first or out
        row["gaps"] = {k: gap(g, w) for k, g, w in zip(NAMES, out, first)}
        record["forms"][name] = row
        times = ("not measured" if args.tiny else
                 f"forward {fwd_ms:.3f} ms, with backward {both_ms:.3f} ms, "
                 f"kernels {row['kernels_ms']}")
        print(f"{name:22s} {times}; gaps to the kernel form "
              + " ".join(f"{k}={v:.2e}" for k, v in row["gaps"].items()), flush=True)
    for k, v in zip(sizes, default):
        setattr(hyper_mix, k, v)
    _write(args.out, record)
    return 0


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--parent", help="a second checkout to read beside this one")
    ap.add_argument("--calls", type=int, default=20)
    ap.add_argument("--variants", default="", help="TOKENSxROWSxLANES,...")
    ap.add_argument("--tiny", action="store_true")
    ap.add_argument("--compile", action="store_true")
    ap.add_argument("--compile-in-tree", action="store_true", help=argparse.SUPPRESS)
    ap.add_argument("--out", default=None)
    args = ap.parse_args(argv)
    if args.compile_in_tree:
        return compile_in_tree()
    args.out = args.out or os.path.join(
        ROOT, "chiprun_out",
        "probe_hc_compile.json" if args.compile else "probe_hc_kernels.json")
    return compile_both(args) if args.compile else probe_chip(args)


if __name__ == "__main__":
    sys.exit(main())
