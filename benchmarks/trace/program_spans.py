"""The program's own host spans, read from the profiler's files.

``extract.py`` admits only the harness's spans (``bench/...``) and ``run.py``
hands a reader no trace directory; until a ``benchmark`` PR widens the one and
passes the other, this module finds the traced run's directory itself and
reads the spans the fit loop opens (``dinunet_implementations_tpu/telemetry/
scopes.py``, host half: ``HOST_PREFIX + name``, one ``TraceAnnotation`` each,
with ``epoch=`` in the stats), on ``extract.py``'s clock and in its units
(seconds, ``start_ns * 1e-9``).

- ``load(trace_dir)``: every host-plane event whose name starts with the
  program's prefix, as :class:`Span` with the prefix cut off. ``line`` is the
  profiler's thread line (file, plane, index): the loop's spans share the line
  that also holds ``bench/epoch``; ``plan-build`` lies on the prefetch
  thread's. A program without the prefix (the parent of the PR that brought
  the spans) gives ``[]``.
- ``find(ctx)``: the ``bench_out/*/trace`` directory that holds the newest
  ``*.xplane.pb`` (``drivers/train.py _traced`` deletes and rewrites its
  cell's directory at the start of the traced stretch, in this process),
  accepted only if the ``bench/epoch`` spans read from it equal
  ``ctx.trace.spans``' to the nanosecond; else ``None``: a metric is left out,
  not guessed.
- ``of(ctx)``: ``load(find(ctx))``, read once a run (kept on ``ctx``).
- :class:`DeviceIdle` (``idle_of(ctx)``): the measures of ``readers/program_span_ms.py``. The
  idle gaps come from ``ctx.trace`` through ``reduce.busy_intervals`` and
  ``intervals.py``, on the idlest device, as ``device_idle_share`` chooses it.
"""

from __future__ import annotations

import bisect
import glob
import os
from dataclasses import dataclass, field, replace

from benchmarks.lib import cells

from . import extract, reduce
from . import intervals as iv

EPOCH_SPAN = "bench/epoch"  # the harness span that defines the traced window


@dataclass(frozen=True)
class Span:
    name: str  # from load(): without the program's prefix, "loss-fetch"
    start: float  # seconds on the trace clock, as extract.Span
    end: float
    line: str  # "<file tag><plane>#<index of the thread line>"
    stats: dict = field(default_factory=dict, compare=False)


def program_prefix():
    """``scopes.HOST_PREFIX`` of the program under test, or None where it has
    none (its fit loop opens no profiler span)."""
    from dinunet_implementations_tpu.telemetry import scopes

    return getattr(scopes, "HOST_PREFIX", None)


def host_events(trace_dir: str, prefixes: tuple) -> list[Span]:
    """Host-plane events of every ``*.xplane.pb`` under ``trace_dir`` whose
    name starts with one of ``prefixes``, under their whole names, sorted by
    start."""
    from jax.profiler import ProfileData

    out = []
    for path in extract.xplane_files(trace_dir):
        tag = os.path.basename(path)[: -len(".xplane.pb")]
        for plane in ProfileData.from_file(path).planes:
            if extract.DEVICE_PLANE.match(plane.name):
                continue
            for i, line in enumerate(plane.lines):
                for e in line.events:
                    if e.name.startswith(prefixes):
                        s = e.start_ns * 1e-9
                        out.append(Span(e.name, s, s + e.duration_ns * 1e-9,
                                        f"{tag}{plane.name}#{i}",
                                        dict(e.stats)))
    return sorted(out, key=lambda s: s.start)


def load(trace_dir: str) -> list[Span]:
    prefix = program_prefix()
    return _cut(host_events(trace_dir, (prefix,)), prefix) if prefix else []


def _cut(events, prefix: str) -> list[Span]:
    return [replace(s, name=s.name[len(prefix):]) for s in events
            if s.name.startswith(prefix)]


def newest_trace_dir(root: str | None = None):
    """The ``bench_out/*/trace`` directory of the checkout (``cells.ROOT``
    unless a test says otherwise) that holds the newest ``*.xplane.pb``."""
    stamps = {}
    for trace_dir in glob.glob(os.path.join(root or cells.ROOT, "bench_out",
                                            "*", "trace")):
        files = extract.xplane_files(trace_dir)
        if files:
            stamps[trace_dir] = max(map(os.path.getmtime, files))
    return max(stamps, key=stamps.get) if stamps else None


def _read(ctx, root, prefixes: tuple):
    """``(trace_dir, its host events under EPOCH_SPAN and prefixes)`` where the
    newest directory's ``bench/epoch`` spans are ``ctx.trace``'s to the
    nanosecond, else None."""
    trace_dir = newest_trace_dir(root) if ctx.trace is not None else None
    if not trace_dir:
        return None
    events = host_events(trace_dir, (EPOCH_SPAN, *prefixes))
    mine = sorted((s.start, s.end) for s in ctx.trace.spans
                  if s.name == EPOCH_SPAN)
    theirs = sorted((s.start, s.end) for s in events if s.name == EPOCH_SPAN)
    return (trace_dir, events) if mine and mine == theirs else None


def find(ctx, root: str | None = None):
    """The directory ``ctx.trace`` was read from, or None (module docstring)."""
    found = _read(ctx, root, ())
    return found[0] if found else None


def of(ctx, root: str | None = None) -> list[Span]:
    """``load(find(ctx))`` in one pass over the files, read once a run (kept
    on ``ctx``); ``[]`` where the directory is not found or the program opens
    no span."""
    if "program_spans" not in vars(ctx):
        prefix = program_prefix()
        found = _read(ctx, root, (prefix,)) if prefix else None
        ctx.program_spans = _cut(found[1], prefix) if found else []
    return ctx.program_spans


def idle_of(ctx) -> DeviceIdle:
    """:class:`DeviceIdle` of the run ``ctx`` describes, reduced once a run."""
    if "device_idle" not in vars(ctx):
        ctx.device_idle = DeviceIdle(ctx.trace, ctx.window)
    return ctx.device_idle


class DeviceIdle:
    """The idle gaps of the idlest device inside the traced window, and where
    its operations (the device's own timeline) start and end."""

    def __init__(self, trace, window):
        shares = reduce.idle_share(trace, window)
        ops = [o for o in trace.devices[max(shares, key=shares.get)]
               if o.lane == "sync"]
        gaps = iv.subtract([window], reduce.busy_intervals(ops, window))
        self.gap_starts = [a for a, _ in gaps]
        self.gap_ends = [b for _, b in gaps]
        self.before = [0.0]  # before[k]: length of the first k gaps
        for a, b in gaps:
            self.before.append(self.before[-1] + (b - a))
        self.starts = sorted(o.start for o in ops)
        self.ends = sorted(o.end for o in ops)

    def _idle_before(self, t: float) -> float:
        k = bisect.bisect_right(self.gap_starts, t)
        over = max(self.gap_ends[k - 1] - t, 0.0) if k else 0.0
        return self.before[k] - over

    def inside(self, lo: float, hi: float) -> float:
        """Idle time between ``lo`` and ``hi``."""
        return self._idle_before(hi) - self._idle_before(lo) if hi > lo else 0.0

    def measure(self, span, measure: str) -> float:
        """One span's number, in seconds: ``length``; ``idle`` (device idle
        inside it); ``idle_head`` (idle inside it BEFORE the first device
        operation that starts in it: all of it where none starts);
        ``idle_tail`` (idle inside it AFTER the last device operation that
        ends in it, and after that first start: nothing where none ends)."""
        lo, hi = span.start, span.end
        if measure == "length":
            return hi - lo
        if measure == "idle":
            return self.inside(lo, hi)
        i = bisect.bisect_left(self.starts, lo)
        first = min(self.starts[i], hi) if i < len(self.starts) else hi
        if measure == "idle_head":
            return self.inside(lo, first)
        if measure == "idle_tail":
            j = bisect.bisect_right(self.ends, hi) - 1
            last = self.ends[j] if j >= 0 and self.ends[j] > lo else hi
            return self.inside(max(first, last), hi)
        raise ValueError(f"unknown measure {measure!r}")

    def outside(self, spans) -> float:
        """Idle time of the window inside none of ``spans``."""
        return self.before[-1] - sum(
            self.inside(lo, hi)
            for lo, hi in iv.union((s.start, s.end) for s in spans))
