"""Peak device memory of the fullest chip after the window, in GiB."""


def reduce(ctx, params):
    peak = ctx.get("memory_peak_bytes")
    return None if peak is None else peak / 2**30
