"""`flash_roofline.py` for a model whose keys and values have unlike head
sizes: the same walk of the trace, a call costed by
`benchmarks/flops_latent.py flash_call_cost` (q, k, dq, dk at the q/k
width, v, o, do, dv at the value width). The least time is the model's:
lanes the kernels pad and products they compute twice read as time it did
not need. Nothing where the configuration has no such widths or the
trace holds no such call."""
from benchmarks import flops, flops_latent
from benchmarks import trace as tr


def reduce(ctx, params):
    cfg = ctx["cfg"]
    if ctx.get("trace") is None or "qk_nope_head_dim" not in cfg:
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
            cost = flops_latent.flash_call_cost(cfg, rows, cell["seen_len"], kind)
            least += calls * flops.roofline_seconds(
                cost["flops"], cost["bytes"], peak)["seconds"]
            spent += sum(ev[2] for ev in hits) / 1e9
        if spent > 0:
            shares.append(least / spent)
    if not shares:
        return None
    return 100.0 * sum(shares) / len(shares)
