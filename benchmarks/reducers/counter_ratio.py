"""One of the program's counters over another, in percent, both summed
over the traced steps (the runner's `traced["counters"]`). Nothing where
the program returns no such counters."""


def reduce(ctx, params):
    counters = (ctx.get("traced") or {}).get("counters") or {}
    num, den = counters.get(params["num"]), counters.get(params["den"])
    if num is None or not den:
        return None
    return 100.0 * num / den
