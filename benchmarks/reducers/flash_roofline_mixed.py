"""`flash_roofline.py` for a model whose layers attend over windows of
their own (windowed and full layers in one stack): the same walk of the
trace, each call costed by `benchmarks/flops_afmoe.py flash_call_cost`,
the mean over the configuration's layers of the cost at each layer's own
window. The windowed and the full layers' calls share the kernels' names,
so a call cannot be told for one or the other; every layer runs each
kernel the same number of times, so the calls' least time in all is
right. Nothing where the configuration names no such layers or the trace
holds no such call."""
from benchmarks import flops, flops_afmoe
from benchmarks import trace as tr


def reduce(ctx, params):
    cfg = ctx["cfg"]
    kinds = set(cfg.get("layer_types") or ())
    if ctx.get("trace") is None or not kinds or not kinds <= {
            flops_afmoe.WINDOWED, flops_afmoe.FULL}:
        return None
    cell, peak = ctx["cell"], ctx["peak"]
    rows = cell["batch"] // cell["chips"]  # the batch is spread over the chips
    shares = []
    for plane in tr.device_planes(ctx["trace"], ctx["fmt"]):
        ops = tr.op_events(plane, ctx["fmt"])
        least = spent = 0.0
        for kind, per_call in (("fwd", 1), ("bwd", params["bwd_kernels"])):
            hits = tr.matching(ops, params["patterns"][kind])
            calls = len(hits) / per_call
            cost = flops_afmoe.flash_call_cost(cfg, rows, cell["seen_len"], kind)
            least += calls * flops.roofline_seconds(
                cost["flops"], cost["bytes"], peak)["seconds"]
            spent += sum(ev[2] for ev in hits) / 1e9
        if spent > 0:
            shares.append(least / spent)
    if not shares:
        return None
    return 100.0 * sum(shares) / len(shares)
