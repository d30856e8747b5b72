"""Device-busy time: the union of the device-op intervals inside the traced
window, mean over the cell's devices, divided by ``per`` (a key of
``ctx.facts``, e.g. ``rounds_traced``), in ms."""

import numpy as np

from benchmarks.trace import reduce


def read(ctx, per: str = "rounds_traced"):
    if ctx.trace is None or not ctx.trace.devices or not ctx.facts.get(per):
        return None
    busy = reduce.busy_seconds(ctx.trace, ctx.window)
    return float(np.mean(list(busy.values())) / ctx.facts[per] * 1e3)
