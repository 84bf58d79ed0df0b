"""1 - the union of the device's operation intervals over the traced
window, averaged over the devices."""
from benchmarks import trace as tr


def reduce(ctx, params):
    if ctx.get("trace") is None:
        return None
    busy = tr.busy_seconds(ctx["trace"], ctx["fmt"])
    if busy is None:
        return None
    return 100.0 * (1.0 - busy / ctx["traced"]["window_s"])
