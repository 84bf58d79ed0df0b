"""Median device time of the executions of the program whose name
matches `pattern`, from the lines `trace_format.json` calls
`module_lines`, in milliseconds, averaged over the devices. Nothing
where no such program ran (a program that names its step otherwise)."""
import re
import statistics

from benchmarks import trace as tr


def reduce(ctx, params):
    if ctx.get("trace") is None:
        return None
    pat = re.compile(params["pattern"])
    medians = []
    for plane in tr.device_planes(ctx["trace"], ctx["fmt"]):
        runs = [ev[2] for line in plane["lines"]
                if line["name"] in ctx["fmt"]["module_lines"]
                for ev in line["events"] if pat.search(ev[0]) and ev[2] > 0]
        if runs:
            medians.append(statistics.median(runs))
    if not medians:
        return None
    return sum(medians) / len(medians) / 1e6
