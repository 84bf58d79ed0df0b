"""From a profiler trace to plain intervals, and arithmetic on them.

A trace is read once into a plain structure that the reducers share:

    {"planes": [{"name": str, "lines": [{"name": str,
        "events": [[name, start_ns, duration_ns], ...]}]}]}

`load` reads that structure from the profiler's `.xplane.pb` (with
nothing but JAX) or from a `.json` file in the same form, which is how
the hand-built trace of the tests is kept. Which planes are devices and
which lines hold operations is data: `benchmarks/trace_format.json`.
"""
from __future__ import annotations

import glob
import json
import os
import re
from typing import Dict, Iterable, List, Optional, Tuple

Interval = Tuple[int, int]

HERE = os.path.dirname(os.path.abspath(__file__))


def trace_format() -> Dict:
    with open(os.path.join(HERE, "trace_format.json")) as f:
        return json.load(f)


def find_xplane(trace_dir: str) -> Optional[str]:
    found = sorted(glob.glob(os.path.join(
        trace_dir, "plugins", "profile", "*", "*.xplane.pb")))
    return found[-1] if found else None


def load(path: str) -> Dict:
    if path.endswith(".json"):
        with open(path) as f:
            return json.load(f)
    from jax.profiler import ProfileData

    data = ProfileData.from_file(path)
    planes = []
    for plane in data.planes:
        lines = []
        for line in plane.lines:
            events = [[ev.name, int(ev.start_ns), int(ev.duration_ns)]
                      for ev in line.events]
            lines.append({"name": line.name, "events": events})
        planes.append({"name": plane.name, "lines": lines})
    return {"planes": planes}


# -- selection ---------------------------------------------------------------


def device_planes(trace: Dict, fmt: Dict) -> List[Dict]:
    pat = re.compile(fmt["device_plane"])
    return sorted((p for p in trace["planes"] if pat.search(p["name"])),
                  key=lambda p: p["name"])


def op_events(plane: Dict, fmt: Dict) -> List[List]:
    return [ev for line in plane["lines"] if line["name"] in fmt["op_lines"]
            for ev in line["events"]]


def host_spans(trace: Dict, fmt: Dict) -> List[List]:
    pat = re.compile(fmt["host_plane"])
    prefix = fmt["host_span_prefix"]
    return [ev for p in trace["planes"] if pat.search(p["name"])
            for line in p["lines"] for ev in line["events"]
            if ev[0].startswith(prefix)]


def matching(events: Iterable[List], pattern: str) -> List[List]:
    pat = re.compile(pattern)
    return [ev for ev in events if pat.search(ev[0])]


# -- interval arithmetic -------------------------------------------------------


def intervals(events: Iterable[List]) -> List[Interval]:
    return [(ev[1], ev[1] + ev[2]) for ev in events if ev[2] > 0]


def union(spans: Iterable[Interval]) -> List[Interval]:
    out: List[Interval] = []
    for lo, hi in sorted(spans):
        if out and lo <= out[-1][1]:
            out[-1] = (out[-1][0], max(out[-1][1], hi))
        else:
            out.append((lo, hi))
    return out


def length(spans: Iterable[Interval]) -> int:
    return sum(hi - lo for lo, hi in spans)


def subtract(a: List[Interval], b: List[Interval]) -> List[Interval]:
    """The part of `a` (a union) that `b` (a union) does not cover."""
    out = []
    j = 0
    for lo, hi in a:
        cur = lo
        while j < len(b) and b[j][1] <= cur:
            j += 1
        k = j
        while k < len(b) and b[k][0] < hi:
            if b[k][0] > cur:
                out.append((cur, b[k][0]))
            cur = max(cur, b[k][1])
            k += 1
        if cur < hi:
            out.append((cur, hi))
    return out


def gaps(busy: List[Interval]) -> List[Interval]:
    return [(busy[i][1], busy[i + 1][0]) for i in range(len(busy) - 1)
            if busy[i + 1][0] > busy[i][1]]


def overlap(a: Interval, b: Interval) -> int:
    return max(0, min(a[1], b[1]) - max(a[0], b[0]))


# -- what the result line carries ----------------------------------------------


def busy_seconds(trace: Dict, fmt: Dict) -> Optional[float]:
    """Seconds in which an operation ran on the device, averaged over the
    device planes. None where no device plane holds an operation."""
    per_dev = [length(union(intervals(op_events(p, fmt)))) / 1e9
               for p in device_planes(trace, fmt)]
    per_dev = [b for b in per_dev if b > 0]
    return sum(per_dev) / len(per_dev) if per_dev else None


_HLO = re.compile(r"^%(?P<name>[\w.\-]+) = \(?(?P<shape>\w+\[[\d,]*\])")
_OPCODE = re.compile(r"[}\]\)] (?P<op>[\w\-]+)\(")
_TARGET = re.compile(r'custom_call_target="(?P<t>[^"]+)"')


def short_name(name: str) -> str:
    """On a TPU an operation's event carries its whole HLO text. Keep the
    instruction's name, its opcode (a custom call's target) and its
    first result shape."""
    m = _HLO.match(name)
    if not m:
        return name[:80]
    op = _TARGET.search(name) or _OPCODE.search(name)
    return " ".join(x for x in (m["name"], op and op[1], m["shape"]) if x)


def breakdown(trace: Dict, fmt: Dict, top: int = 10) -> Dict:
    """The device operations that took most time (per device, averaged),
    and the idle time of the first device by what the host was doing."""
    devs = device_planes(trace, fmt)
    if not devs:
        return {"device_ops": [], "idle_gaps": []}
    totals: Dict[str, float] = {}
    for p in devs:
        for name, _, dur in op_events(p, fmt):
            name = short_name(name)
            totals[name] = totals.get(name, 0.0) + dur / 1e9 / len(devs)
    ops = sorted(totals.items(), key=lambda kv: -kv[1])[:top]
    spans = host_spans(trace, fmt)
    idle: Dict[str, float] = {}
    for gap in gaps(union(intervals(op_events(devs[0], fmt)))):
        covered = {}
        for name, start, dur in spans:
            covered[name] = covered.get(name, 0) + overlap(gap, (start, start + dur))
        best = max(covered.items(), key=lambda kv: kv[1], default=("", 0))
        name = best[0] if best[1] > 0 else "no_bench_span"
        idle[name] = idle.get(name, 0.0) + (gap[1] - gap[0]) / 1e9
    gaps_out = sorted(idle.items(), key=lambda kv: -kv[1])[:top]
    return {"device_ops": [[n, s] for n, s in ops],
            "idle_gaps": [[n, s] for n, s in gaps_out]}
