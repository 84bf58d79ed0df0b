#!/usr/bin/env python3
"""The flash kernels, each alone, on the chip, at the shapes the
benchmark's 8k cells run: `[64, 8192, 128]` under a window of 4,096 (the
Mistral cells; the LFM2 cell has the shape, full causal), `[32, 8192, 128]`
full causal (the Ouro cell), `[64, 8192, 256 | 128]` full causal (the
latent-attention cell: q and k of 192 padded to 256, v of 128; a tree
whose kernels give q, k and v one width is not run there) and
`[48, 8192, 128]` under a window of 4,096 and full causal (the
gated-attention cell's windowed layers and its full one), blocks of 512.

    python hack/probe_flash_blocks.py [--parent .parent] [--reps 5] \
        [--out chiprun_out/probe_flash_blocks.json]

For each kernel a tree holds (`flash_fwd` and `flash_bwd_dqkv`, or an
older tree's `flash_bwd_dq` and `flash_bwd_dkv`) and each shape:
`block_plan`'s counts for a head, ms a call, us a program and us a
visited block, and the backward's ms a call, all its kernels together. A
call is `_fwd` or `_bwd` as the custom VJP makes it, run `--reps` times
under the profiler; a kernel's time is the median of its events on the
device's "XLA Ops" line, by the `name=` the benchmark's metrics read. A
second run with every inner loop emptied (`_segments` answers no block,
as a traced value, so that what a program builds before its loop is
still built) gives a program's fixed cost: its time over the grid's
programs. What is left of the whole call, over the visited blocks of all
heads, is a block's cost.

`--parent DIR` loads `DIR/kubedl_tpu/ops/flash_attention.py` beside this
checkout's in the same process (`git archive <commit> | tar -x -C DIR`),
prints both and, for each of `out`, `lse`, `dq`, `dk`, `dv`, whether the
two trees' are equal element for element, and the largest difference
where they are not. `--tiny` is the rehearsal on the CPU: small shapes,
the bits compared, every time "not measured" (a CPU time is no device
number).
"""
from __future__ import annotations

import argparse
import importlib.util
import json
import os
import re
import statistics
import sys
import tempfile

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

import jax
import jax.numpy as jnp
import numpy as np

from benchmarks import trace as tr

BLOCK = 512
# name -> (batch * heads, sequence, head size, window[, value head size])
SHAPES = {
    "window_4096": (64, 8192, 128, 4096),
    "full_causal": (32, 8192, 128, None),
    "latent_256_128": (64, 8192, 256, None, 128),
    "gated_window_4096": (48, 8192, 128, 4096),
    "gated_full_causal": (48, 8192, 128, None),
}
TINY = {
    "window_512": (2, 1024, 128, 512),
    "full_causal": (2, 1024, 128, None),
    "latent_256_128": (2, 1024, 256, None, 128),
}
# kernel -> the side of `block_plan` that counts its blocks
KERNELS = {"flash_fwd": "fwd_dq", "flash_bwd_dq": "fwd_dq",
           "flash_bwd_dkv": "dkv", "flash_bwd_dqkv": "dkv"}
ARRAYS = ("out", "lse", "dq", "dk", "dv")


def load_kernels(tree: str, name: str):
    """`ops/flash_attention.py` of a checkout, under a module name of its
    own so that two trees' kernels live side by side."""
    path = os.path.join(tree, "kubedl_tpu", "ops", "flash_attention.py")
    spec = importlib.util.spec_from_file_location(name, path)
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


def kernels_of(fa):
    """The kernels of `KERNELS` that a tree's module names."""
    with open(fa.__file__) as f:
        named = set(re.findall(r'name="(flash_\w+)"', f.read()))
    return [k for k in KERNELS if k in named]


def calls_of(fa, shape, emptied: bool):
    """Jitted `_fwd` and `_bwd` at `shape`; with `emptied`, traced while
    `_segments` hands every loop an empty range."""
    _, seq, d, window = shape[:4]
    scale = 1.0 / d ** 0.5
    segments = fa._segments

    def no_block(outer, *a, **kw):
        none = jnp.minimum(outer, 0)  # traced: the loop stays in the kernel
        return none, none, none, none

    def traced(fn):
        def run(*args):
            fa._segments = no_block if emptied else segments
            try:
                return fn(*args)
            finally:
                fa._segments = segments
        return jax.jit(run)

    fwd = traced(lambda q, k, v: fa._fwd(
        q, k, v, scale, True, window, BLOCK, BLOCK, seq))
    bwd = traced(lambda q, k, v, out, lse, do: fa._bwd(
        scale, True, window, BLOCK, BLOCK, seq, (q, k, v, out, lse), do))
    return fwd, bwd


def kernel_ms(run, kernels, reps: int):
    """Median device ms of each of `kernels` over `reps` runs of `run`
    under the profiler; `{}` where the trace holds no device plane."""
    jax.block_until_ready(run())  # compiled and warm outside the trace
    with tempfile.TemporaryDirectory() as trace_dir:
        with jax.profiler.trace(trace_dir):
            for _ in range(reps):
                jax.block_until_ready(run())
        path = tr.find_xplane(trace_dir)
        trace = tr.load(path) if path else {"planes": []}
    fmt = tr.trace_format()
    found = {}
    for plane in tr.device_planes(trace, fmt)[:1]:
        for kernel in kernels:
            events = tr.matching(tr.op_events(plane, fmt),
                                 rf"^%{kernel}[.\d]* = ")
            if events:
                found[kernel] = statistics.median(
                    ev[2] for ev in events) / 1e6
    return found


def measure(fa, shape, reps: int):
    """Times of the tree's kernels at `shape`, and the arrays they gave."""
    bh, seq, d, window = shape[:4]
    d_v = shape[4] if len(shape) > 4 else d
    keys = jax.random.split(jax.random.PRNGKey(0), 4)
    q, k, v, do = (jax.random.normal(key, (bh, seq, w), jnp.bfloat16)
                   for key, w in zip(keys, (d, d, d_v, d_v)))
    kernels, rows = kernels_of(fa), {}
    for name, emptied in (("whole", False), ("emptied", True)):
        fwd, bwd = calls_of(fa, shape, emptied)
        out, lse = fwd(q, k, v)
        if not emptied:
            arrays = (out, lse) + tuple(bwd(q, k, v, out, lse, do))
        rows[name] = kernel_ms(
            lambda: (fwd(q, k, v), bwd(q, k, v, out, lse, do)), kernels, reps)
    result = {}
    for kernel in kernels:
        visited, interior = fa.block_plan(
            seq, window, BLOCK, BLOCK, True, KERNELS[kernel])
        programs = bh * (seq // BLOCK)
        row = {"visited_a_head": visited, "interior_a_head": interior,
               "programs": programs}
        whole, empty = rows["whole"].get(kernel), rows["emptied"].get(kernel)
        if whole is not None and empty is not None:
            row.update(
                ms_a_call=whole, ms_a_call_emptied=empty,
                us_a_program=1e3 * empty / programs,
                us_a_visited_block=1e3 * (whole - empty) / (bh * visited))
        result[kernel] = row
    backward = [rows["whole"].get(k) for k in kernels if k != "flash_fwd"]
    if backward and None not in backward:
        result["backward_ms_a_call"] = sum(backward)
    return result, dict(zip(ARRAYS, (np.asarray(a) for a in arrays)))


def compared(parent, change):
    """For each array: equal element for element, else the largest
    difference of the two trees' values in float32."""
    found = {}
    for name in ARRAYS:
        a, b = parent[name], change[name]
        found[name] = (True if np.array_equal(a, b) else float(np.max(np.abs(
            a.astype(np.float32) - b.astype(np.float32)))))
    return found


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--parent", help="a second checkout to read beside this one")
    ap.add_argument("--reps", type=int, default=5)
    ap.add_argument("--tiny", action="store_true")
    ap.add_argument("--out", default=os.path.join(
        ROOT, "chiprun_out", "probe_flash_blocks.json"))
    args = ap.parse_args(argv)

    device = jax.devices()[0]
    if device.platform != "tpu" and not args.tiny:
        print(f"probe_flash_blocks: needs a TPU, found {device.platform} "
              "(--tiny rehearses on the CPU and measures nothing)",
              file=sys.stderr)
        return 2
    trees = {"change": load_kernels(ROOT, "flash_of_change")}
    if args.parent:
        trees = {"parent": load_kernels(args.parent, "flash_of_parent"),
                 **trees}
    record = {"device": device.device_kind, "platform": device.platform,
              "reps": args.reps, "block": BLOCK, "shapes": {}}
    for name, shape in (TINY if args.tiny else SHAPES).items():
        sides, arrays = {}, {}
        for tree, fa in trees.items():
            if len(shape) > 4 and not hasattr(fa, "_whole_seq_params"):
                continue  # kernels of one width for q, k and v
            sides[tree], arrays[tree] = measure(fa, shape, args.reps)
        entry = {"shape": shape, **sides}
        if len(sides) == 2:
            entry["equal"] = compared(arrays["parent"], arrays["change"])
            entry["equal_bits"] = all(v is True for v in entry["equal"].values())
        record["shapes"][name] = entry
        print(f"{name} {list(shape)}"
              + (f" equal_bits={entry['equal_bits']} {entry['equal']}"
                 if len(sides) == 2 else ""))
        for tree, fa in trees.items():
            if tree not in sides:
                continue
            for kernel in kernels_of(fa):
                row = sides[tree][kernel]
                times = ("not measured" if "ms_a_call" not in row else
                         f"{row['ms_a_call']:.3f} ms a call, "
                         f"{row['ms_a_call_emptied']:.3f} emptied, "
                         f"{row['us_a_program']:.3f} us a program, "
                         f"{row['us_a_visited_block']:.3f} us a visited block")
                print(f"  {kernel:14s} {tree:7s} visited "
                      f"{row['visited_a_head']} interior "
                      f"{row['interior_a_head']} programs "
                      f"{row['programs']}: {times}")
            total = sides[tree].get("backward_ms_a_call")
            print(f"  {'backward':14s} {tree:7s} "
                  + ("not measured" if total is None else f"{total:.3f} ms a call"))
    os.makedirs(os.path.dirname(args.out), exist_ok=True)
    with open(args.out, "w") as f:
        json.dump(record, f, indent=1)
    return 0


if __name__ == "__main__":
    sys.exit(main())
