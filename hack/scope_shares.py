#!/usr/bin/env python3
"""Device time by named scope, read by hand from one profiler trace.

    python hack/scope_shares.py <trace dir or .xplane.pb> [out.json]

The model's and the step's `jax.named_scope`s (embed, attn > attn_core
(a latent layer's attn > mla_q, mla_kv, attn_core; a gated layer's
attn > attn_core, attn_gate), hc_map and hc_mix
around a several-stream layer's sublayers, mtp around a multi-token
prediction module (`mtp>attn_core`, ...), short_conv, ssm > ssm_conv / ssm_scan > ssm_carry / ssm_gate_norm,
mlp > moe_route / moe_permute / moe_experts / moe_combine / shared_expert,
head_loss, exit_gate, optimizer, grad_norm, and loop_pass around a looped
stack's pass: PERF.md section 3) reach each
device operation's `op_name`, not its name. On a TPU the profiler keeps
the `op_name` as the stat `tf_op` of the operation's *event metadata*,
which `jax.profiler.ProfileData` (jax 0.9) does not hand out: its
`event.stats` holds only `device_offset_ps`, `device_duration_ps` and
`Time Scale Multiplier`. So this reads the `.xplane.pb` itself, with a
protobuf wire reader of a few lines and the field numbers of
`xplane.proto` below, and nothing but the standard library. Until a
`benchmark` PR hands reducers the `op_name` (PERF.md Open question 13),
this is how the per-scope shares of PERF.md section 5 are read.

Per device plane it prints the device time of each scope as a share of
the traced window (first operation's start to the last one's end; an
instant under a `while` or `conditional` counts once, for the innermost
operation: `own_times`), the share that is remat recompute
(`rematted_computation` in the `op_name`), what no scope covers, each
named kernel's calls and time (`flash*`, `gmm*`, `moe_gather`,
`ssm_scan_fwd`, `ssm_scan_bwd`, `ssm_conv_fwd`, `ssm_conv_bwd`,
`short_conv_fwd`, `short_conv_bwd`, `hc_pre_fwd`, `hc_post_fwd`,
`hc_post_bwd`, `hc_pre_bwd`), the
operations that took the most device time with their scope and what the
compiler's cost analysis says they move (`largest_ops`, and each scope's
own in `largest_ops_by_scope`, where the same fusion of every layer is one
entry), the runs of each
program on the modules line, and the host's `train.*` / `bench.*` spans
with the device gaps that fall under each.
"""
from __future__ import annotations

import glob
import json
import os
import re
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from benchmarks import trace as tr  # interval arithmetic only; no JAX

# innermost first: an operation under attn/attn_core counts as attn_core
SCOPES = ("hc_map", "hc_mix", "mla_q", "mla_kv", "shared_expert",
          "attn_core", "attn_gate", "attn", "short_conv",
          "ssm_carry", "ssm_scan", "ssm_conv", "ssm_gate_norm", "ssm",
          "moe_route", "moe_permute",
          "moe_experts", "moe_combine", "mlp", "head_loss", "exit_gate",
          "embed", "optimizer", "grad_norm",
          # a looped stack's pass: what no scope inside it covers (its
          # final norm, the loop's own copies)
          "loop_pass")
# a scope around whole layers: an operation under it reads `mtp>attn_core`,
# so that the module's share is the sum of its entries
OUTER_SCOPES = ("mtp",)
DEVICE_PLANE = re.compile(r"^/device:TPU:\d+$")
# jax.trace / jax.lower / jax.compile: a recompile inside the window
# (kubedl_tpu/obs/compiles.py), so the gap under it has a name
HOST_SPAN = re.compile(r"^(train|bench|ckpt|reshard|jax)\.|^train$")
OP_NAME_STAT = "tf_op"
LARGEST_OPS = 24
LARGEST_IN_A_SCOPE = 16
# what the compiler's cost analysis left on an operation's metadata
COST_STATS = ("flops", "bytes_accessed", "model_flops")


def scope_of(op_name: str) -> str:
    """A scope is one component of the name stack, bare or wrapped by a
    transformation: mlp, jvp(mlp), transpose(jvp(mlp)), checkpoint/mlp."""
    def under(scope):
        return re.search(rf"(?:^|[/(]){scope}(?:[/)]|$)", op_name)

    inner = next((scope for scope in SCOPES if under(scope)), "unscoped")
    for outer in OUTER_SCOPES:
        if under(outer):
            return outer if inner == "unscoped" else f"{outer}>{inner}"
    return inner


def own_times(events):
    """(scope, is remat recompute, ns) of each (op_name, start, duration),
    every instant counted once. A `while` or a `conditional` is an event
    that spans the operations of its body (the dispatch's bounded loop,
    the branch a row move takes): time goes to the innermost event, and
    an operation whose own `op_name` names no scope counts under the
    enclosing event's."""
    open_events = []  # [end, scope, is_remat, ns not given to a child]

    def closed(until):
        while open_events and open_events[-1][0] <= until:
            yield tuple(open_events.pop()[1:])

    for op_name, start, dur in sorted(events, key=lambda e: (e[1], -e[2])):
        yield from closed(start)
        scope, is_remat = scope_of(op_name), "rematted_computation" in op_name
        if open_events:
            open_events[-1][3] -= dur
            if scope == "unscoped":
                scope, is_remat = open_events[-1][1:3]
        open_events.append([start + dur, scope, is_remat, dur])
    yield from closed(float("inf"))


# -- the protobuf wire format, as far as xplane.proto needs it ---------------


def fields(buf: bytes):
    """(field number, wire type, value) of one message: varints as ints,
    length-delimited fields as bytes, fixed-width ones as their bytes."""
    i, n = 0, len(buf)

    def varint():
        nonlocal i
        val = shift = 0
        while True:
            b = buf[i]
            i += 1
            val |= (b & 0x7F) << shift
            shift += 7
            if b < 0x80:
                return val

    while i < n:
        key = varint()
        num, wire = key >> 3, key & 7
        if wire == 0:
            yield num, wire, varint()
            continue
        if wire not in (1, 2, 5):
            raise ValueError(f"wire type {wire} is not in xplane.proto")
        size = varint() if wire == 2 else 8 if wire == 1 else 4
        yield num, wire, buf[i:i + size]
        i += size


def first(msg: bytes, num: int, default=None):
    for n, _, v in fields(msg):
        if n == num:
            return v
    return default


def read_plane(buf: bytes) -> dict:
    """XPlane: name=2, lines=3, event_metadata=4, stat_metadata=5 (both
    maps: key=1, value=2). XLine: name=2, timestamp_ns=3, events=4.
    XEvent: metadata_id=1, offset_ps=2, duration_ps=3. XEventMetadata:
    name=2, stats=5. XStat: metadata_id=1, str_value=5, ref_value=7.
    XStatMetadata: name=2."""
    plane = {"name": "", "lines": [], "events_meta": {}, "stat_names": {}}
    raw_meta = {}
    for num, _, val in fields(buf):
        if num == 2:
            plane["name"] = val.decode()
        elif num == 3:
            plane["lines"].append(val)
        elif num == 4:
            raw_meta[first(val, 1, 0)] = first(val, 2, b"")
        elif num == 5:
            plane["stat_names"][first(val, 1, 0)] = (
                first(first(val, 2, b""), 2, b"").decode())
    for mid, meta in raw_meta.items():
        stats = {}
        for num, _, val in fields(meta):
            if num != 5:
                continue
            name = plane["stat_names"].get(first(val, 1, 0), "")
            text = first(val, 5)
            if text is None and first(val, 7) is not None:
                text = plane["stat_names"].get(first(val, 7), "").encode()
            if text is not None:
                stats[name] = text.decode(errors="replace")
            elif name in COST_STATS:  # uint64_value=3, int64_value=4
                stats[name] = first(val, 3, first(val, 4))
        plane["events_meta"][mid] = (first(meta, 2, b"").decode(errors="replace"), stats)
    return plane


def line_events(plane: dict, line: bytes):
    """(name, stats of the metadata, start_ns, duration_ns) of a line."""
    t0_ns = first(line, 3, 0)
    for num, _, val in fields(line):
        if num != 4:
            continue
        name, stats = plane["events_meta"].get(first(val, 1, 0), ("", {}))
        yield (name, stats, t0_ns + first(val, 2, 0) // 1000,
               first(val, 3, 0) // 1000)


def find_xplane(path: str) -> str:
    if os.path.isdir(path):
        found = sorted(glob.glob(os.path.join(
            path, "**", "*.xplane.pb"), recursive=True))
        if not found:
            raise SystemExit(f"no .xplane.pb under {path}")
        return found[-1]
    return path


def largest(ops, n: int):
    return sorted(ops, key=lambda o: -o["seconds"])[:n]


def same_kind(ops):
    """Operations that differ in their instruction numbers alone (one
    layer's fusion and the next layer's) as one entry: calls, seconds,
    flops and bytes summed, `instructions` how many there were."""
    kinds = {}
    for o in ops:
        key = (re.sub(r"%([\w\-]+?)\.\d+", r"%\1", o["op"]), o["remat"])
        k = kinds.setdefault(key, dict(o, op=key[0], instructions=0, calls=0,
                                       seconds=0.0, **{c: 0 for c in COST_STATS if c in o}))
        k["instructions"] += 1
        for field in ("calls", "seconds") + tuple(c for c in COST_STATS if c in o):
            k[field] += o[field]
    return kinds.values()


def summarize(path: str) -> dict:
    path = find_xplane(path)
    with open(path, "rb") as f:
        planes = [read_plane(v) for n, _, v in fields(f.read()) if n == 1]
    out = {"file": path, "op_name_stat": OP_NAME_STAT, "devices": [],
           "host_spans": {}}
    gaps_of_first = None
    for plane in planes:
        if not DEVICE_PLANE.match(plane["name"]):
            continue
        dev = {"plane": plane["name"]}
        for line in plane["lines"]:
            line_name = first(line, 2, b"").decode()
            if line_name == "XLA Modules":
                runs = {}
                for name, _, _, dur in line_events(plane, line):
                    runs.setdefault(re.sub(r"\(\d+\)$", "", name), []).append(dur / 1e6)
                dev["modules_ms"] = runs
            if line_name != "XLA Ops":
                continue
            by_scope, kernels, spans, by_op = {}, {}, [], {}
            remat = named = 0
            events = [e for e in line_events(plane, line) if e[3] > 0]
            for name, stats, start, dur in events:
                spans.append((start, start + dur))
                named += bool(stats.get(OP_NAME_STAT))
                if name not in by_op:  # an instruction: one scope, many events
                    op_name = stats.get(OP_NAME_STAT, "")
                    by_op[name] = {
                        "op": name[:200], "scope": scope_of(op_name),
                        "remat": "rematted_computation" in op_name,
                        "calls": 0, "seconds": 0.0,
                        **{k: stats[k] for k in COST_STATS if k in stats}}
                by_op[name]["calls"] += 1
                by_op[name]["seconds"] += dur / 1e9
                m = re.match(r"^%((?:flash|gmm|moe_gather|ssm_scan|ssm_conv|short_conv|hc_pre|hc_post)\w*?)\.\d+ = ", name)
                if m:
                    k = kernels.setdefault(m[1], [0, 0])
                    k[0] += 1
                    k[1] += dur
            for scope, is_remat, own in own_times(
                    (stats.get(OP_NAME_STAT, ""), start, dur)
                    for _, stats, start, dur in events):
                by_scope[scope] = by_scope.get(scope, 0) + own
                remat += own if is_remat else 0
            if not spans:
                continue
            busy = tr.union(spans)
            window = busy[-1][1] - busy[0][0]
            dev.update({
                "events": len(spans), "events_with_op_name": named,
                "window_s": window / 1e9,
                "busy_share": tr.length(busy) / window,
                "share_of_window": {s: d / window for s, d in sorted(
                    by_scope.items(), key=lambda kv: -kv[1])},
                "remat_recompute_share": remat / window,
                "kernels": {k: {"calls": c, "seconds": d / 1e9,
                                "ms_a_call": d / c / 1e6}
                            for k, (c, d) in sorted(kernels.items())},
                "largest_ops": largest(by_op.values(), LARGEST_OPS),
                "largest_ops_by_scope": {
                    scope: largest(same_kind(o for o in by_op.values()
                                             if o["scope"] == scope),
                                   LARGEST_IN_A_SCOPE)
                    for scope in by_scope},
            })
            if gaps_of_first is None:
                gaps_of_first = tr.gaps(busy)
        if "share_of_window" in dev:
            out["devices"].append(dev)
    gaps_of_first = gaps_of_first or []
    for plane in planes:
        if plane["name"] != "/host:CPU":
            continue
        for line in plane["lines"]:
            for name, _, start, dur in line_events(plane, line):
                if not HOST_SPAN.search(name):
                    continue
                h = out["host_spans"].setdefault(
                    name, {"count": 0, "seconds": 0.0, "device_gap_s": 0.0})
                h["count"] += 1
                h["seconds"] += dur / 1e9
                h["device_gap_s"] += sum(
                    tr.overlap(gap, (start, start + dur))
                    for gap in gaps_of_first) / 1e9
    out["device_gap_s_first_device"] = tr.length(gaps_of_first) / 1e9
    if out["devices"]:
        n = len(out["devices"])
        mean = {}
        for dev in out["devices"]:
            for s, v in dev["share_of_window"].items():
                mean[s] = mean.get(s, 0.0) + v / n
        out["mean_share_of_window"] = dict(sorted(mean.items(), key=lambda kv: -kv[1]))
        out["mean_remat_recompute_share"] = sum(
            d["remat_recompute_share"] for d in out["devices"]) / n
    return out


def main(argv) -> int:
    if not argv:
        print(__doc__, file=sys.stderr)
        return 2
    text = json.dumps(summarize(argv[0]), indent=1)
    if len(argv) > 1:
        os.makedirs(os.path.dirname(os.path.abspath(argv[1])) or ".", exist_ok=True)
        with open(argv[1], "w") as f:
            f.write(text)
    else:
        print(text)
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
