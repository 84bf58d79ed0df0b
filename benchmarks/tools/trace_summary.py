#!/usr/bin/env python3
"""What a trace holds, for a look by hand: every plane and line with its
event count, time span and the names that took most time.

    python benchmarks/tools/trace_summary.py <trace dir or .xplane.pb> [out.json]
"""
from __future__ import annotations

import json
import os
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)


def main(argv) -> int:
    from benchmarks import trace as tr

    path = argv[0]
    if os.path.isdir(path):
        path = tr.find_xplane(path)
    data = tr.load(path)
    out = []
    for plane in data["planes"]:
        for line in plane["lines"]:
            evs = line["events"]
            if not evs:
                continue
            totals, counts = {}, {}
            for name, _, dur in evs:
                totals[name] = totals.get(name, 0) + dur
                counts[name] = counts.get(name, 0) + 1
            top = sorted(totals.items(), key=lambda kv: -kv[1])[:120]
            out.append({
                "plane": plane["name"], "line": line["name"], "events": len(evs),
                "first_ns": min(e[1] for e in evs),
                "last_ns": max(e[1] + e[2] for e in evs),
                "top": [[n, d / 1e9, counts[n]] for n, d in top],
            })
    text = json.dumps(out, indent=1)
    if len(argv) > 1:
        os.makedirs(os.path.dirname(os.path.abspath(argv[1])), exist_ok=True)
        with open(argv[1], "w") as f:
            f.write(text)
    else:
        print(text)
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
