"""Device time of the operations whose name matches `pattern`, over the
traced window, averaged over the devices. Nothing where none matches."""
from benchmarks import trace as tr


def reduce(ctx, params):
    if ctx.get("trace") is None:
        return None
    shares = []
    for plane in tr.device_planes(ctx["trace"], ctx["fmt"]):
        hits = tr.matching(tr.op_events(plane, ctx["fmt"]), params["pattern"])
        shares.append(tr.length(tr.union(tr.intervals(hits))) / 1e9)
    if not shares or not any(shares):
        return None
    return 100.0 * sum(shares) / len(shares) / ctx["traced"]["window_s"]
