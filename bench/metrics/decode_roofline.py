"""Least time of the decode work the traced window's decode steps needed
(``work.decode_step`` at the v5e peaks) over the decode-step program's
measured device time.  Also gives which bound (HBM or FLOP/s) sets the
least time of most of those steps."""
from bench.trace import module_time

MODULE = "jit__decode_impl"


def read(ctx):
    if ctx.trace is None or ctx.peak is None:
        return None
    n, secs = module_time(ctx.trace, MODULE)
    steps = [t for t in ctx.traced_ticks() if t.kind == "decode"]
    if not n or not steps:
        return None
    least, bounds = 0.0, {}
    for t in steps:
        s, bound = ctx.work.least_time(*ctx.work.decode_step(ctx.spec, t.kv_lens, t.substeps), ctx.peak)
        least += s
        bounds[bound] = bounds.get(bound, 0) + 1
    binds = max(bounds, key=bounds.get)
    return {"value": 100.0 * least / secs,
            "note": f"{binds}-bound in {bounds[binds]} of {len(steps)} decode steps; "
                    f"{len(steps)} steps counted, {n} decode executions traced"}
