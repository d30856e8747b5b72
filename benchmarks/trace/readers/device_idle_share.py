"""1 - busy union / traced window, in %, on the idlest device of the cell."""

from benchmarks.trace import reduce


def read(ctx):
    if ctx.trace is None or not ctx.trace.devices:
        return None
    return float(100.0 * max(reduce.idle_share(ctx.trace, ctx.window).values()))
