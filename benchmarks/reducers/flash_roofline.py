"""The flash kernels' share of their roofline: the least time the chip
could take for the calls the trace holds (the larger of FLOPs over peak
and bytes over bandwidth, both from shapes by benchmarks/flops.py), over
the summed device time of those calls, averaged over the devices.

`patterns` names the forward and the backward kernels' events. The calls
are counted from the trace, so a step that stops recomputing the forward
is not credited with work it no longer does."""
from benchmarks import flops
from benchmarks import trace as tr


def reduce(ctx, params):
    if ctx.get("trace") is None:
        return None
    cell, cfg, peak = ctx["cell"], ctx["cfg"], ctx["peak"]
    rows = cell["batch"] // cell["chips"]  # the batch is spread over the chips
    shares = []
    for plane in tr.device_planes(ctx["trace"], ctx["fmt"]):
        ops = tr.op_events(plane, ctx["fmt"])
        least = spent = 0.0
        for kind, per_call in (("fwd", 1), ("bwd", params["bwd_kernels"])):
            hits = tr.matching(ops, params["patterns"][kind])
            calls = len(hits) / per_call
            cost = flops.flash_call_cost(cfg, rows, cell["seen_len"], kind)
            least += calls * flops.roofline_seconds(
                cost["flops"], cost["bytes"], peak)["seconds"]
            spent += sum(ev[2] for ev in hits) / 1e9
        if spent > 0:
            shares.append(least / spent)
    if not shares:
        return None
    return 100.0 * sum(shares) / len(shares)
