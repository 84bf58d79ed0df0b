#!/usr/bin/env python3
"""The chunked scan alone, on the chip, at the Granite cell's shape: x
`[2, 8192, 64, 64]` bf16, state 128, chunk 256 (`models/ssm.py
chunked_scan`: nine of the cell's ten layers call it three times a step).

    python hack/probe_ssm_scan.py [--parent .parent] [--calls 20] \
        [--out chiprun_out/probe_ssm_scan.json]

Two forms of this checkout's scan: `kernel` (what `chunked_scan` takes at
this shape on a TPU: `ops/ssm_scan.py`'s two Pallas calls, XLA keeping the
decays' cumulative sums) and `xla` (the form every other shape takes;
traced here while `scan_takes_kernel` is made to say no), and with
`--parent DIR` (`git archive <commit> | tar -x -C DIR`) that checkout's
`chunked_scan` beside them in the same process. For each: ms a
call of the forward alone and of the forward with its backward (the
gradient of all five inputs against a fixed cotangent), on the host's
clock over `--calls` calls with one closing wait; the device ms of each
kernel by its `name=` over the same calls under the profiler; and how far
`y` and each gradient lie from the first form's (norm of the difference
over the norm). The forward's `y` leaves the call as `[b, t, h, p]`, which
costs the kernel form a relayout of 268 MB that the model's step does not
pay (there `y` goes on as `[b, t, h * p]`): read the kernels' own ms.
`--head-block N` traces the kernels with N heads a program, `--forms
kernel` leaves this checkout's XLA form out. `--tiny` is the rehearsal on
the CPU: a small shape, the gaps compared, every time "not measured" (a
CPU time is no device number).
"""
from __future__ import annotations

import argparse
import importlib.util
import json
import os
import statistics
import sys
import tempfile
import time

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

import jax
import jax.numpy as jnp
import numpy as np

from benchmarks import trace as tr
from kubedl_tpu.models import ssm
from kubedl_tpu.ops import ssm_scan

# batch, tokens, heads, head size, state, chunk
SHAPE = (2, 8192, 64, 64, 128, 256)
TINY = (1, 384, 8, 64, 128, 128)
KERNELS = ("ssm_scan_fwd", "ssm_scan_bwd")
INPUTS = ("x", "dt", "a", "B", "C")


def load_ssm(tree: str, name: str):
    """`models/ssm.py` of a checkout, under a module name of its own."""
    path = os.path.join(tree, "kubedl_tpu", "models", "ssm.py")
    spec = importlib.util.spec_from_file_location(name, path)
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


def inputs_of(shape, seed=0):
    """The cell's ranges: A uniform in [1, 16], dt log-uniform in
    [0.001, 0.1], the rest standard normal in bf16; and a cotangent."""
    b, t, h, p, n, _ = shape
    ks = jax.random.split(jax.random.PRNGKey(seed), 6)
    bf = jnp.bfloat16
    x = jax.random.normal(ks[0], (b, t, h, p), jnp.float32).astype(bf)
    dt = jnp.exp(jax.random.uniform(
        ks[1], (b, t, h), jnp.float32, np.log(1e-3), np.log(1e-1)))
    a = -jax.random.uniform(ks[2], (h,), jnp.float32, 1.0, 16.0)
    b_ = jax.random.normal(ks[3], (b, t, n), jnp.float32).astype(bf)
    c_ = jax.random.normal(ks[4], (b, t, n), jnp.float32).astype(bf)
    dy = jax.random.normal(ks[5], (b, t, h, p), jnp.float32)
    return (x, dt, a, b_, c_), dy


def calls_of(module, chunk: int, kernel: bool, tiny: bool):
    """Jitted forward and forward-with-backward of a checkout's scan."""
    # what steers the choice while the scan is traced: nothing for the
    # kernels on a TPU, the choice itself on the CPU and for the XLA form
    name, steer = (("scan_takes_kernel", lambda *a, **kw: kernel) if tiny or not kernel
                   else (None, None))

    def scan(*args):
        if name is None or not hasattr(module, name):  # or a tree before PR 33
            return module.chunked_scan(*args, chunk)[0]
        was = getattr(module, name)
        setattr(module, name, steer)
        try:
            return module.chunked_scan(*args, chunk)[0]
        finally:
            setattr(module, name, was)

    def both(args, dy):
        y, vjp = jax.vjp(scan, *args)
        return (y,) + vjp(dy)

    return jax.jit(lambda args: scan(*args)), jax.jit(both)


def timed(fn, args, calls: int):
    out = jax.block_until_ready(fn(*args))
    jax.block_until_ready(fn(*args))
    t0 = time.perf_counter()
    for _ in range(calls):
        last = fn(*args)
    jax.block_until_ready(last)
    return (time.perf_counter() - t0) / calls * 1e3, out


def kernel_ms(fn, args, calls: int):
    """Median device ms of each scan kernel over `calls` runs of `fn`
    under the profiler; `{}` where the trace holds no such event."""
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
            events = tr.matching(tr.op_events(plane, fmt),
                                 rf"^%{kernel}[.\d]* = ")
            if events:
                found[kernel] = statistics.median(ev[2] for ev in events) / 1e6
    return found


def gap(got, want) -> float:
    got, want = (np.asarray(v.astype(jnp.float32)) for v in (got, want))
    return float(np.linalg.norm(got - want) / np.linalg.norm(want))


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--parent", help="a second checkout to read beside this one")
    ap.add_argument("--calls", type=int, default=20)
    ap.add_argument("--head-block", type=int, default=ssm_scan.HEAD_BLOCK)
    ap.add_argument("--forms", default="kernel,xla",
                    help="of this checkout, in the order to run them")
    ap.add_argument("--tiny", action="store_true")
    ap.add_argument("--out", default=os.path.join(
        ROOT, "chiprun_out", "probe_ssm_scan.json"))
    args = ap.parse_args(argv)

    device = jax.devices()[0]
    if device.platform != "tpu" and not args.tiny:
        print(f"probe_ssm_scan: needs a TPU, found {device.platform} "
              "(--tiny rehearses on the CPU and measures nothing)",
              file=sys.stderr)
        return 2
    ssm_scan.HEAD_BLOCK = args.head_block  # read when a call is traced
    shape = TINY if args.tiny else SHAPE
    forms = {name: (ssm, name == "kernel") for name in args.forms.split(",")}
    if args.parent:
        forms["parent"] = (load_ssm(args.parent, "ssm_of_parent"), False)
    ins, dy = inputs_of(shape)
    record = {"device": device.device_kind, "platform": device.platform,
              "calls": args.calls, "shape": shape,
              "head_block": args.head_block, "forms": {}}
    first = None
    for name, (module, kernel) in forms.items():
        fwd, both = calls_of(module, shape[-1], kernel, args.tiny)
        fwd_ms, _ = timed(fwd, (ins,), args.calls)
        both_ms, out = timed(both, (ins, dy), args.calls)
        row = {}
        if not args.tiny:
            row = {"fwd_ms": fwd_ms, "fwd_bwd_ms": both_ms,
                   "kernels_ms": kernel_ms(both, (ins, dy), args.calls)}
        first = first or out
        row["gaps"] = {k: gap(g, w) for k, g, w in zip(
            ("y",) + tuple("d" + i for i in INPUTS), out, first)}
        record["forms"][name] = row
        times = ("not measured" if args.tiny else
                 f"forward {fwd_ms:.3f} ms, with backward {both_ms:.3f} ms, "
                 f"kernels {row['kernels_ms']}")
        print(f"{name:7s} {times}; gaps to the first form "
              + " ".join(f"{k}={v:.2e}" for k, v in row["gaps"].items()),
              flush=True)
    os.makedirs(os.path.dirname(args.out) or ".", exist_ok=True)
    with open(args.out, "w") as f:
        json.dump(record, f, indent=1)
    return 0


if __name__ == "__main__":
    sys.exit(main())
