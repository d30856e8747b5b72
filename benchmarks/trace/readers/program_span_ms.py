"""Time of the program's own host spans (``trace/program_spans.py``: the spans
``telemetry/scopes.py`` names, read from the traced run's profiler files), and
the device's idle time put down to them, in ms.

``span``: a span's name without the program's prefix (``loss-fetch``).
``measure``, per span: ``length`` (the span's own length), ``idle`` (device
idle inside it), ``idle_head`` (idle inside it BEFORE the first device
operation that starts in it), ``idle_tail`` (idle inside it AFTER the last
device operation that ends in it); ``how``: ``median``, ``max`` or ``mean``
over the spans of that name inside the traced window (one an epoch).
``idle_outside``: ``span`` is a LIST; the idle time of the traced window
inside none of the spans so named, divided by the traced epochs (``how`` plays
no part). Idle is the idlest device's, as ``device_idle_share`` reads it, so
as means over the epochs ``idle`` of ``epoch-dispatch`` + ``idle_head`` and
``idle_tail`` of ``loss-fetch`` + ``idle_outside`` of the two are the window's
idle time an epoch, but for gaps between two device operations inside one
``loss-fetch``. No trace, no device, the directory not found, or a program
that opens no such span -> None."""

import numpy as np

from benchmarks.trace import program_spans

HOW = {"median": np.median, "max": np.max, "mean": np.mean}


def read(ctx, span, measure: str = "length", how: str = "median"):
    if ctx.trace is None or not ctx.trace.devices or ctx.window is None:
        return None
    names = [span] if isinstance(span, str) else list(span)
    lo, hi = ctx.window
    hits = [s for s in program_spans.of(ctx)
            if s.name in names and s.start >= lo and s.end <= hi]
    if not hits:
        return None
    idle = program_spans.idle_of(ctx)
    if measure == "idle_outside":
        epochs = sum(s.name == program_spans.EPOCH_SPAN for s in ctx.trace.spans)
        return float(idle.outside(hits) / epochs * 1e3)
    return float(HOW[how]([idle.measure(s, measure) for s in hits]) * 1e3)
