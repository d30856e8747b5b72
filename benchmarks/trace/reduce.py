"""From intervals to the numbers the readers and the last line use.

Every function takes a :class:`~extract.Trace` (or its parts) and a window
``(lo, hi)``; all times are seconds. Kept with the benchmark so that every PR
computes the same number the same way (tests/test_reduce.py checks it on a
recorded trace and on hand-made intervals).
"""

from __future__ import annotations

import re
from collections import Counter

from . import intervals as iv


def ops_in(ops, window, lanes=("sync",)):
    lo, hi = window
    return [o for o in ops if o.end > lo and o.start < hi and o.lane in lanes]


def busy_intervals(ops, window):
    """Where some operation of the device's own timeline runs (parents
    included: inside the rounds scan the device is busy between two body
    operations too). Asynchronous spans do not count."""
    return iv.clip(iv.union((o.start, o.end) for o in ops
                            if o.lane == "sync"), *window)


def _matcher(pattern: str, field: str):
    rx = re.compile(pattern)
    return lambda o: rx.search(getattr(o, field)) is not None


def busy_seconds(trace, window) -> dict:
    """Per device: length of the union of its operations inside the window."""
    return {d: iv.total(busy_intervals(ops, window))
            for d, ops in trace.devices.items()}


def idle_share(trace, window) -> dict:
    """Per device: 1 - busy / window."""
    span = window[1] - window[0]
    return {d: 1.0 - b / span for d, b in busy_seconds(trace, window).items()}


def matching_seconds(trace, window, pattern: str, field: str = "text",
                     how: str = "sum", lanes=("sync",)) -> dict:
    """Per device: time (clipped to the window) of the LEAF operations whose
    ``field`` (``text``: the whole instruction; ``name``: its name) matches
    ``pattern``. ``how="sum"`` adds durations (two kernels that overlap both
    count); ``how="union"`` takes the length of the union, which is what an
    operation seen both as ``-start``/``-done`` and as an asynchronous span
    needs."""
    match = _matcher(pattern, field)
    lo, hi = window
    out = {}
    for d, ops in trace.devices.items():
        hits = [(max(o.start, lo), min(o.end, hi))
                for o in ops_in(ops, window, lanes) if o.leaf and match(o)]
        out[d] = (iv.total(iv.union(hits)) if how == "union"
                  else float(sum(b - a for a, b in hits)))
    return out


def top_ops(trace, window, n: int = 10) -> list:
    """``[[name, seconds], ...]``: the operations that took most device time
    inside the window: self time (a parent such as the rounds scan counts
    only what its body does not cover), summed by name, mean over the
    devices."""
    agg: Counter = Counter()
    for ops in trace.devices.values():
        for o in ops_in(ops, window):
            agg[o.name if o.leaf else o.name + " (self)"] += o.self_s
    k = max(len(trace.devices), 1)
    return [[name, sec / k] for name, sec in agg.most_common(n)]


def _leaf_spans(spans):
    """Each instant belongs to the innermost harness span covering it: cut
    every span by the spans nested inside it."""
    out = []
    for s in spans:
        inner = [(t.start, t.end) for t in spans
                 if t is not s and t.start >= s.start and t.end <= s.end
                 and (t.end - t.start) < (s.end - s.start)]
        for lo, hi in iv.subtract([(s.start, s.end)], inner):
            out.append((s.name, lo, hi))
    return out


def idle_gaps(trace, window, device: str | None = None, n: int = 10) -> list:
    """``[[what the host was doing, idle seconds], ...]`` for one device (the
    idlest by default): its idle time inside the window, split by the
    innermost harness span that covers each instant (``outside`` where none
    does), largest first."""
    if not trace.devices:
        return []
    if device is None:
        shares = idle_share(trace, window)
        device = max(shares, key=shares.get)
    gaps = iv.subtract([window], busy_intervals(trace.devices[device], window))
    agg: Counter = Counter()
    covered = []
    for name, lo, hi in _leaf_spans(trace.spans):
        part = iv.total(iv.intersect(gaps, [(lo, hi)]))
        if part > 0:
            agg[name] += part
        covered.append((lo, hi))
    rest = iv.total(iv.subtract(gaps, covered))
    if rest > 0:
        agg["outside"] += rest
    return [[name, sec] for name, sec in agg.most_common(n)]


def span_host_seconds(trace, span_name: str, device: str | None = None) -> list:
    """For every harness span of that name: its length minus the time the
    device (the busiest by default) was busy inside it."""
    hits = [s for s in trace.spans if s.name == span_name]
    if not hits or not trace.devices:
        return []
    if device is None:
        win = (min(s.start for s in hits), max(s.end for s in hits))
        busy = busy_seconds(trace, win)
        device = max(busy, key=busy.get)
    merged = iv.union((o.start, o.end) for o in trace.devices[device]
                      if o.lane == "sync")
    return [(s.end - s.start) - iv.total(iv.clip(merged, s.start, s.end))
            for s in hits]
