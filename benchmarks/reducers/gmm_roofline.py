"""The grouped-matmul kernels' share of their roofline: the least time
the chip could take for the `gmm*` calls the trace holds (for each call
the larger of FLOPs over peak and bytes over bandwidth, both by
benchmarks/flops_hybrid.py from the rows the router sent to the experts
held here), over the summed device time of those calls, averaged over
the devices.

The calls are counted from the trace by the kernels' names (`pattern`
captures the kernel), remat copies included, so a step that stops
recomputing a product is not credited with work it no longer does. The
rows come from the program's own counter `moe_rows_held`, which the
runner puts into the traced record: every expert layer makes the same
calls, so each call is costed at the mean rows of a layer and step
(exact where the calls are FLOP-bound, as they are from a few thousand
rows up). Nothing where the trace holds no such call or the program
counts no rows."""
import re

from benchmarks import flops, flops_hybrid
from benchmarks import trace as tr


def reduce(ctx, params):
    traced = ctx.get("traced") or {}
    counters = traced.get("counters") or {}
    if ctx.get("trace") is None or "moe_rows_held" not in counters:
        return None
    cfg, peak = ctx["cfg"], ctx["peak"]
    expert_layers = cfg["num_hidden_layers"] - cfg["num_dense_layers"]
    rows = counters["moe_rows_held"] / (traced["steps"] * expert_layers)
    pat = re.compile(params["pattern"])
    shares = []
    for plane in tr.device_planes(ctx["trace"], ctx["fmt"]):
        least = spent = 0.0
        for name, _, dur in tr.op_events(plane, ctx["fmt"]):
            m = pat.match(name)
            if not m or m["kernel"] not in flops_hybrid.GMM_PRODUCTS:
                continue
            cost = flops_hybrid.gmm_call_cost(cfg, m["kernel"], rows)
            least += flops.roofline_seconds(cost["flops"], cost["bytes"], peak)["seconds"]
            spent += dur / 1e9
        if spent > 0:
            shares.append(least / spent)
    if not shares:
        return None
    return 100.0 * sum(shares) / len(shares)
