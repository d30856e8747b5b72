"""Device time of the LEAF operations whose ``field`` (``text``: the whole HLO
instruction and stats; ``name``: the instruction's name) matches ``pattern``,
mean over the cell's devices, divided by ``per`` (a key of ``ctx.facts``), in
ms. ``how``: ``sum`` of durations or length of their ``union``; ``lanes``: the
device's own timeline (``sync``) and/or asynchronous spans (``async``).
Nothing matches -> 0.0 (the layer did no work), no device trace -> None."""

import numpy as np

from benchmarks.trace import reduce


def read(ctx, pattern: str, per: str = "rounds_traced", field: str = "text",
         how: str = "sum", lanes=("sync",)):
    if ctx.trace is None or not ctx.trace.devices or not ctx.facts.get(per):
        return None
    sec = reduce.matching_seconds(ctx.trace, ctx.window, pattern, field, how,
                                  tuple(lanes))
    return float(np.mean(list(sec.values())) / ctx.facts[per] * 1e3)
