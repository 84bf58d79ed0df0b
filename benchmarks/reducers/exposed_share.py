"""Device time in operations matching `pattern` (the collectives) during
which no other operation runs on that device, over the traced window,
averaged over the devices."""
from benchmarks import trace as tr


def reduce(ctx, params):
    if ctx.get("trace") is None:
        return None
    exposed, found = [], False
    for plane in tr.device_planes(ctx["trace"], ctx["fmt"]):
        ops = tr.op_events(plane, ctx["fmt"])
        hits = tr.matching(ops, params["pattern"])
        found = found or bool(hits)
        ids = {id(ev) for ev in hits}
        others = tr.union(tr.intervals(ev for ev in ops if id(ev) not in ids))
        alone = tr.subtract(tr.union(tr.intervals(hits)), others)
        exposed.append(tr.length(alone) / 1e9)
    if not found:
        return None
    return 100.0 * sum(exposed) / len(exposed) / ctx["traced"]["window_s"]
