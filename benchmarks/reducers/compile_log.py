"""A field of the program's compile log, summed over the records of the
functions that `params["fun"]` matches (a regular expression on the
record's `fun`): `trace_s`, `lower_s`, `executable_s`, or with
`params["field"]` `count` their number.

The log is `kubedl_tpu/obs/compiles.py`'s, one a process, fed by
`jax.monitoring` from the moment `make_train_step` was called: by the
time a reducer runs it holds the set-up's compile of the step, whatever
traced anew in the window or the traced steps, and the reference's
programs (other names). A test hands a log's records in as
`ctx["compile_log"]`. Nothing where the program has no such log (a
parent of the PR that added it) or no record matches.
"""
import re


def reduce(ctx, params):
    records = ctx.get("compile_log")
    if records is None:
        try:
            from kubedl_tpu.obs import compiles
        except ImportError:
            return None
        records = compiles.install().records()
    fun = re.compile(params["fun"])
    matched = [r for r in records if fun.search(r["fun"])]
    if not matched:
        return None
    if params["field"] == "count":
        return len(matched)
    return sum(r[params["field"]] for r in matched)
