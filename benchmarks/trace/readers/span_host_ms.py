"""Host time per harness span: the span's length minus the device-busy time
inside it, median over the spans of that name, in ms."""

import numpy as np

from benchmarks.trace import reduce


def read(ctx, span: str = "bench/epoch"):
    if ctx.trace is None:
        return None
    host = reduce.span_host_seconds(ctx.trace, span)
    return float(np.median(host) * 1e3) if host else None
