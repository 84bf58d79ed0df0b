"""The whole step's share of the chips' peak where the required FLOPs
depend on what the step counted (rows routed to the experts held here):
the runner sums them over the window's steps into
`window["required_flops"]` (benchmarks/flops_hybrid.py, no recompute),
here over the window's time, over chips times the peak. Nothing where
the runner counts none."""


def reduce(ctx, params):
    win = ctx.get("window") or {}
    if "required_flops" not in win:
        return None
    rate = win["required_flops"] / win["elapsed_s"]
    return 100.0 * rate / (ctx["cell"]["chips"] * ctx["peak"]["bf16_flops_per_s"])
