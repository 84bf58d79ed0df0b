#!/usr/bin/env python3
"""A state-space layer's convolution alone, on the chip, at the Granite
cell's shape: the in projection's output `[2, 8192, 8512]` bf16 of which
xBC is columns 4,096-8,447 (`[2, 8192, 4352]`), K = 4 taps and a bias in
float32 (`models/ssm.py split_conv`: nine of the cell's ten layers call it
three times a step).

    python hack/probe_causal_conv.py [--parent .parent] [--calls 20] \
        [--short-conv] [--out chiprun_out/probe_causal_conv.json]

Two forms of this checkout's step: `kernel` (what `split_conv` takes at
this shape on a TPU: `ops/causal_conv.py`'s two Pallas calls) and `xla`
(the form every other shape takes; traced here while `conv_takes_kernel`
is made to say no), and with `--parent DIR` (`git archive <commit> | tar
-x -C DIR`) that checkout's XLA operations beside them in the same
process. For each: ms a call of the forward alone (x, B and C from the
projection's output) and of the forward with its backward (the gradient
of the projection's output, the taps and the bias against fixed
cotangents of x, B and C), on the host's clock over `--calls` calls with
one closing wait; the device ms of each kernel by its `name=` over the
same calls under the profiler; how far x, B, C and each gradient lie from
the first form's (norm of the difference over the norm), and how many
elements of x, B and C differ at all. `--short-conv` times
`models/short_conv.py:short_conv` at the LFM2 cell's shape (`[2, 8192,
2048]`, K = 3) whole and its two projections alone, forward and forward
with backward, and its gates and taps alone in each form: `gates_kernel`
(ops/causal_conv.py's `short_conv_fwd` / `short_conv_bwd`, what
`gated_taps` takes at this shape on a TPU), `gates_xla` (the XLA
operations every other shape takes) and, with `--parent`, that
checkout's XLA operations; for each also the device ms a call (all its
operations, and each kernel by name) against the memory bound, and the
gaps of y, du and dw to the kernel's. `--tiny` is the rehearsal on the
CPU: a small shape, the gaps compared, every time "not measured" (a CPU
time is no device number).
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
from kubedl_tpu.models import short_conv, ssm
from kubedl_tpu.models.quant import matmul as _mm
from kubedl_tpu.ops import causal_conv

# batch, tokens, inner width, state, heads, taps
SHAPE = (2, 8192, 4096, 128, 64, 4)
TINY = (2, 384, 256, 128, 8, 4)
# batch, tokens, hidden, taps: the LFM2 cell's convolution layers
SHORT = (2, 8192, 2048, 3)
SHORT_TINY = (2, 256, 128, 3)
KERNELS = ("ssm_conv_fwd", "ssm_conv_bwd")
SHORT_KERNELS = ("short_conv_fwd", "short_conv_bwd")
OUTPUTS = ("x", "B", "C")
GRADS = ("dh", "dw", "db")


def load(tree: str, module: str, name: str):
    """`models/<module>.py` of a checkout, under a module name of its own."""
    path = os.path.join(tree, "kubedl_tpu", "models", module + ".py")
    spec = importlib.util.spec_from_file_location(name, path)
    loaded = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(loaded)
    return loaded


def inputs_of(shape, seed=0):
    """The cell's ranges: the projection's output standard normal in bf16,
    taps and bias uniform(-1/2, 1/2) in float32 (`benchmarks/weights_ssm.py`);
    and the cotangents of x, B and C."""
    b, t, d_inner, n, h, k = shape
    ks = jax.random.split(jax.random.PRNGKey(seed), 6)
    bf, d = jnp.bfloat16, d_inner + 2 * n
    hidden = jax.random.normal(ks[0], (b, t, 2 * d_inner + 2 * n + h), jnp.float32)
    w = jax.random.uniform(ks[1], (d, k), jnp.float32, -0.5, 0.5)
    bias = jax.random.uniform(ks[2], (d,), jnp.float32, -0.5, 0.5)
    douts = tuple(jax.random.normal(key, (b, t, width), jnp.float32).astype(bf)
                  for key, width in zip(ks[3:], (d_inner, n, n)))
    return (hidden.astype(bf), w, bias), douts


def xla_of(taps_module):
    """The step as a checkout's XLA operations (`causal_taps` of its
    `models/short_conv.py`): what `ssm_mixer` held before the kernels."""
    def step(h, w, bias, d_inner, state):
        _, xbc, _ = jnp.split(h, [d_inner, 2 * d_inner + 2 * state], axis=-1)
        xbc = jax.nn.silu(taps_module.causal_taps(xbc, w).astype(jnp.float32)
                          + bias).astype(h.dtype)
        return tuple(jnp.split(xbc, [d_inner, d_inner + state], axis=-1))
    return step


def calls_of(step, shape):
    """Jitted forward and forward-with-backward of a form of the step."""
    d_inner, state = shape[2], shape[3]
    fn = lambda h, w, bias: step(h, w, bias, d_inner, state)

    def both(args, douts):
        outs, vjp = jax.vjp(fn, *args)
        return tuple(outs) + vjp(tuple(douts))

    return jax.jit(lambda args: fn(*args)), jax.jit(both)


def steered(module, call, kernel: bool, tiny: bool):
    """`call` of this checkout, its form chosen in `module` as
    `hack/probe_ssm_scan.py` steers the scan: nothing for the kernels on
    a TPU, the choice itself on the CPU and for the XLA form."""
    name, steer = (("conv_takes_kernel", lambda *a, **kw: kernel) if tiny or not kernel
                   else (None, None))

    def step(*args):
        was = getattr(module, name) if name else None
        if name:
            setattr(module, name, steer)
        try:
            return call(*args)
        finally:
            if name:
                setattr(module, name, was)
    return step


def this_checkout(kernel: bool, tiny: bool):
    """`ssm.split_conv`'s x, B and C."""
    return steered(ssm, lambda h, w, bias, d_inner, state: ssm.split_conv(
        h, w, bias, d_inner, state)[0][1:4], kernel, tiny)


def timed(fn, args, calls: int):
    out = jax.block_until_ready(fn(*args))
    jax.block_until_ready(fn(*args))
    t0 = time.perf_counter()
    for _ in range(calls):
        last = fn(*args)
    jax.block_until_ready(last)
    return (time.perf_counter() - t0) / calls * 1e3, out


def device_ms(fn, args, calls: int, kernels):
    """Device ms a call of `fn` under the profiler: every operation's time
    summed over `calls` runs, over `calls`; and the median of each named
    kernel's events (`{}` where the trace holds none)."""
    with tempfile.TemporaryDirectory() as trace_dir:
        with jax.profiler.trace(trace_dir):
            for _ in range(calls):
                jax.block_until_ready(fn(*args))
        path = tr.find_xplane(trace_dir)
        trace = tr.load(path) if path else {"planes": []}
    fmt = tr.trace_format()
    total, found = 0.0, {}
    for plane in tr.device_planes(trace, fmt)[:1]:
        events = tr.op_events(plane, fmt)
        total = sum(ev[2] for ev in events) / 1e6 / calls
        for kernel in kernels:
            named = tr.matching(events, rf"^%{kernel}[.\d]* = ")
            if named:
                found[kernel] = statistics.median(ev[2] for ev in named) / 1e6
    return total, found


def gap(got, want) -> float:
    got, want = (np.asarray(v.astype(jnp.float32)) for v in (got, want))
    return float(np.linalg.norm(got - want) / np.linalg.norm(want))


def differing(got, want) -> int:
    return int(np.sum(np.asarray(got.astype(jnp.float32))
                      != np.asarray(want.astype(jnp.float32))))


def probe_forms(args, record) -> None:
    shape = TINY if args.tiny else SHAPE
    forms = {name: this_checkout(name == "kernel", args.tiny)
             for name in ("kernel", "xla")}
    if args.parent:
        forms["parent"] = xla_of(load(args.parent, "short_conv", "conv_of_parent"))
    ins, douts = inputs_of(shape)
    first = None
    for name, step in forms.items():
        fwd, both = calls_of(step, shape)
        fwd_ms, _ = timed(fwd, (ins,), args.calls)
        both_ms, out = timed(both, (ins, douts), args.calls)
        row = {}
        if not args.tiny:
            row = {"fwd_ms": fwd_ms, "fwd_bwd_ms": both_ms,
                   "kernels_ms": device_ms(both, (ins, douts), args.calls, KERNELS)[1]}
        first = first or out
        row["gaps"] = {k: gap(g, w) for k, g, w in zip(OUTPUTS + GRADS, out, first)}
        row["differing"] = {k: differing(g, w)
                            for k, g, w in zip(OUTPUTS, out, first)}
        record["forms"][name] = row
        times = ("not measured" if args.tiny else
                 f"forward {fwd_ms:.3f} ms, with backward {both_ms:.3f} ms, "
                 f"kernels {row['kernels_ms']}")
        print(f"{name:8s} {times}; gaps to the first form "
              + " ".join(f"{k}={v:.2e}" for k, v in row["gaps"].items())
              + "; elements that differ "
              + " ".join(f"{k}={v}" for k, v in row["differing"].items()),
              flush=True)


def short_parent(tree: str):
    """The gates and taps as a checkout's `models/short_conv.py` writes
    them in XLA operations (its `causal_taps`)."""
    parent = load(tree, "short_conv", "short_conv_of_parent")

    def step(u, w):
        b_, c_, z = jnp.split(u, 3, axis=-1)
        return c_ * parent.causal_taps(b_ * z, w)
    return step


def probe_short_conv(args, record) -> None:
    """`short_conv` whole, its two projections alone, and its gates and
    taps alone in each form: ms a call on the host's clock and on the
    device, forward and forward with backward, beside the memory bound
    (bf16 arrays of [b, t, d] at 819 GB/s: B, C, z read and y written
    forward; B, C, z and dy read and dB, dC, dz written backward)."""
    b, t, d, k = SHORT_TINY if args.tiny else SHORT
    ks = jax.random.split(jax.random.PRNGKey(1), 6)
    bf = jnp.bfloat16
    layer = short_conv.short_conv_init(ks[0], d, k)
    u = jax.random.normal(ks[1], (b, t, d), jnp.float32).astype(bf)
    gated = jax.random.normal(ks[2], (b, t, d), jnp.float32).astype(bf)
    d_out = jax.random.normal(ks[3], (b, t, d), jnp.float32).astype(bf)
    d_in = jax.random.normal(ks[4], (b, t, 3 * d), jnp.float32).astype(bf)
    array_ms = b * t * d * 2 / 819e9 * 1e3
    bound = {"fwd_ms": 4 * array_ms, "fwd_bwd_ms": 11 * array_ms}
    pieces = {
        "whole": (lambda u, layer: short_conv.short_conv(u, layer)[0], (u, layer), d_out),
        "in_projection": (_mm, (u, layer["conv_in"]), d_in),
        "out_projection": (_mm, (gated, layer["conv_out"]), d_out),
    }
    gated = lambda u, w: short_conv.gated_taps(u, w)[0]
    gates = {"gates_kernel": steered(short_conv, gated, True, args.tiny),
             "gates_xla": steered(short_conv, gated, False, args.tiny)}
    if args.parent:
        gates["gates_parent"] = short_parent(args.parent)
    for name, step in gates.items():
        pieces[name] = (step, (d_in, layer["conv_w"]), d_out)
    first = None
    for name, (fn, ins, cotangent) in pieces.items():
        def both(ins, cotangent, fn=fn):
            out, vjp = jax.vjp(fn, *ins)
            return (out,) + vjp(cotangent)

        fwd = jax.jit(lambda ins, fn=fn: fn(*ins))
        fwd_ms, _ = timed(fwd, (ins,), args.calls)
        both_ms, out = timed(jax.jit(both), (ins, cotangent), args.calls)
        row = {} if args.tiny else {"fwd_ms": fwd_ms, "fwd_bwd_ms": both_ms}
        line = "not measured" if args.tiny else (
            f"forward {fwd_ms:.3f} ms, with backward {both_ms:.3f} ms")
        if name.startswith("gates"):
            first = first or out
            row["gaps"] = {g: gap(o, f) for g, o, f in zip(("y", "du", "dw"), out, first)}
            row["differing"] = {g: differing(o, f) for g, o, f in
                                zip(("y", "du", "dw"), out, first)}
            line += "; gaps to the kernel's " + " ".join(
                f"{g}={v:.2e}" for g, v in row["gaps"].items()) + "; elements that differ " + (
                " ".join(f"{g}={v}" for g, v in row["differing"].items()))
            if not args.tiny:
                for key, call in (("fwd_ms", (fwd, (ins,))),
                                  ("fwd_bwd_ms", (jax.jit(both), (ins, cotangent)))):
                    total, kernels = device_ms(*call, args.calls, SHORT_KERNELS)
                    row["device_" + key] = total
                    row["kernels_" + key] = kernels
                    row["bound_share_" + key] = bound[key] / total
                line += (f"; on the device forward {row['device_fwd_ms']:.3f} ms "
                         f"({100 * row['bound_share_fwd_ms']:.1f}% of the memory bound "
                         f"{bound['fwd_ms']:.3f}), with backward {row['device_fwd_bwd_ms']:.3f} "
                         f"ms ({100 * row['bound_share_fwd_bwd_ms']:.1f}% of "
                         f"{bound['fwd_bwd_ms']:.3f}), kernels {row['kernels_fwd_bwd_ms']}")
        record["short_conv"][name] = row
        print(f"short_conv {name:15s} {line}", flush=True)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--parent", help="a second checkout to read beside this one")
    ap.add_argument("--calls", type=int, default=20)
    ap.add_argument("--short-conv", action="store_true")
    ap.add_argument("--tiny", action="store_true")
    ap.add_argument("--out", default=os.path.join(
        ROOT, "chiprun_out", "probe_causal_conv.json"))
    args = ap.parse_args(argv)

    device = jax.devices()[0]
    if device.platform != "tpu" and not args.tiny:
        print(f"probe_causal_conv: needs a TPU, found {device.platform} "
              "(--tiny rehearses on the CPU and measures nothing)",
              file=sys.stderr)
        return 2
    record = {"device": device.device_kind, "platform": device.platform,
              "calls": args.calls, "shape": TINY if args.tiny else SHAPE,
              "token_block": causal_conv.TOKEN_BLOCK, "rows": causal_conv.ROWS,
              "forms": {}, "short_conv": {}}
    probe_forms(args, record)
    if args.short_conv:
        probe_short_conv(args, record)
    os.makedirs(os.path.dirname(args.out) or ".", exist_ok=True)
    with open(args.out, "w") as f:
        json.dump(record, f, indent=1)
    return 0


if __name__ == "__main__":
    sys.exit(main())
