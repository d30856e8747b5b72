"""Thread-safe host-side span tracer.

One tracer instance serves a whole fit: the training loop opens phase spans
(``fit`` / ``epoch`` / ``eval`` / ``checkpoint``) and the spans of its own
host work (telemetry/scopes.py, host half), the prefetch planner thread opens
``plan-build`` spans concurrently, and bench.py times its per-epoch feed path.

A span has two sinks, each on a clock of its own:

- the tracer's **event buffer** (``enabled=True``): spans nest per thread (each
  thread keeps its own stack), timestamps come from ONE monotonic clock
  (``time.perf_counter`` relative to the tracer's birth), so cross-thread
  ordering in the emitted trace is real. That clock is the tracer's alone:
  nothing here can be laid over a device trace;
- the **profiler's host plane** (``annotate=True``): the span's body runs under
  a ``jax.profiler.TraceAnnotation`` named ``scopes.HOST_PREFIX + name``, its
  keyword attributes the event's stats. Whenever a ``jax.profiler`` session
  is running (the benchmark's ``--trace 1`` stretch, an operator's
  ``--xprof-dir`` window) the span lies in the same ``.xplane.pb`` as the
  device's ``XLA Ops`` events, on THEIR clock; with no session an annotation
  costs under a microsecond and leaves nothing behind.

The sinks are independent: :data:`PROFILER_TRACER` records nothing and
annotates, :data:`NULL_TRACER` does neither.

Output formats of the event buffer:

- ``write_jsonl(path)`` — one JSON object per event (machine-diffable; the
  report CLI's input);
- ``write_chrome_trace(path)`` — Chrome trace-event JSON (``traceEvents``
  with complete ``"X"`` spans + thread-name metadata), loadable in Perfetto
  (ui.perfetto.dev) or ``chrome://tracing``.

Span/event names must be string literals or module-level constants at the
call site — jaxlint R007 enforces it — so traces stay greppable and stable
across runs.

Deliberately stdlib-only: the report CLI and bench's host-side timing must
not pull jax in. The annotation class is taken from ``sys.modules`` when a
span opens: a process that never imported jax annotates nothing.
"""

from __future__ import annotations

import json
import os
import sys
import threading
import time
from contextlib import contextmanager, nullcontext

from .scopes import HOST_PREFIX


def duration(cache: dict, start: float, key: str):
    """Append elapsed seconds since ``start`` to ``cache[key]`` (reference
    ``coinstac_dinunet.utils.duration``, used at ``local.py:51-52``). The ONE
    reference-keyed duration-list helper — formerly trainer/logs.py, moved
    here so every timing helper lives with the tracer.

    ``start`` MUST come from ``time.perf_counter()`` — the tracer's one
    monotonic clock. (This helper read ``time.time()`` until r16 while every
    span used ``perf_counter``: an NTP step or DST jump mid-fit corrupted
    the checkpointed duration bookkeeping with negative or wildly wrong
    entries that a resume then carried forward.)"""
    cache.setdefault(key, []).append(time.perf_counter() - start)
    return cache[key][-1]


def new_trace_id() -> str:
    """A fresh 16-hex-char trace/request id for cross-process propagation:
    spool membership events, serving requests and checkpoint metadata carry
    these so one sample is followable from spool ingest through round
    aggregation and checkpoint publish to serve (dispatch rows + spans
    record them as ``trace_ids``)."""
    return os.urandom(8).hex()


class SpanTracer:
    """Collect nested spans + instant events + counters across threads.

    ``enabled=False`` records nothing (every call but ``span`` returns
    immediately) so call sites can thread one tracer object unconditionally —
    :data:`NULL_TRACER` is the shared disabled instance. ``annotate=True``
    also puts every span into the profiler's host plane (module docstring),
    whether or not it is recorded: :data:`PROFILER_TRACER` is the shared
    instance that only annotates.
    """

    def __init__(self, enabled: bool = True, annotate: bool = False):
        self.enabled = enabled
        self.annotate = annotate
        self._lock = threading.Lock()
        self._events: list[dict] = []
        self._listeners: list = []
        self._local = threading.local()
        # perf and unix birth times sampled back-to-back: every event ts is
        # relative to _t0 (monotonic), and the clock_sync row write_jsonl
        # emits lets the pod trace assembler (telemetry/assemble.py) map it
        # onto the wall clock shared across processes
        self._t0 = time.perf_counter()
        self._t0_unix = time.time()

    def clock_sync(self) -> dict:
        """The per-process clock anchor: this tracer's birth on both the
        monotonic (``t0_perf``) and wall (``t0_unix``) clocks, plus the
        pid. An event's wall time is ``t0_unix + ts/1e6`` — or, preferring
        the heartbeat-exchanged offset, ``offset + t0_perf + ts/1e6``."""
        return {
            "ph": "M", "name": "clock_sync", "pid": os.getpid(),
            "t0_perf": self._t0, "t0_unix": self._t0_unix,
        }

    # -- recording --------------------------------------------------------

    def _stack(self) -> list:
        st = getattr(self._local, "stack", None)
        if st is None:
            st = self._local.stack = []
        return st

    def _record(self, ev: dict) -> None:
        with self._lock:
            self._events.append(ev)
            listeners = tuple(self._listeners)
        for fn in listeners:
            fn(ev)

    def add_listener(self, fn) -> None:
        """Mirror every recorded event into ``fn(event_dict)`` — the flight
        recorder's bounded ring feeds from here. Listeners run outside the
        tracer lock and must not raise; on a disabled tracer nothing is ever
        recorded, so nothing is ever delivered."""
        with self._lock:
            self._listeners.append(fn)

    def _annotation(self, name: str, attrs: dict):
        """The profiler annotation around one span's body, or a null context
        where this tracer does not annotate or jax was never imported."""
        jax = sys.modules.get("jax") if self.annotate else None
        if jax is None:
            return nullcontext()
        return jax.profiler.TraceAnnotation(HOST_PREFIX + name, **attrs)

    def span(self, name: str, **attrs):
        """Context manager for one named span. Nests per thread; closes (and
        records) on ANY exit — normal return, early ``break``, or an
        exception unwinding through (``Preempted`` included), with
        ``ok: false`` marking the exceptional exits. A tracer that records
        nothing hands out the bare annotation (or a null context): no
        generator frame on the fit loop's path."""
        if not self.enabled:
            return self._annotation(name, attrs)
        return self._recorded(name, attrs)

    @contextmanager
    def _recorded(self, name: str, attrs: dict):
        with self._annotation(name, attrs):
            stack = self._stack()
            depth = len(stack)
            stack.append(name)
            start = time.perf_counter()
            try:
                yield self
            finally:
                stack.pop()
                end = time.perf_counter()
                self._record({
                    "ph": "X",
                    "name": name,
                    "ts": (start - self._t0) * 1e6,  # trace-event µs
                    "dur": (end - start) * 1e6,
                    "tid": threading.get_ident(),
                    "thread": threading.current_thread().name,
                    "depth": depth,
                    # sys.exc_info survives into finally only while an exception
                    # is actually unwinding through the with-body
                    "ok": sys.exc_info()[0] is None,
                    **attrs,
                })

    def event(self, name: str, **attrs) -> None:
        """Instant event (checkpoint written, site quarantined, retry...)."""
        if not self.enabled:
            return
        self._record({
            "ph": "i",
            "name": name,
            "ts": (time.perf_counter() - self._t0) * 1e6,
            "tid": threading.get_ident(),
            "thread": threading.current_thread().name,
            **attrs,
        })

    def counter(self, name: str, value) -> None:
        """Named counter sample (compile count, queue depth, bytes...)."""
        if not self.enabled:
            return
        self._record({
            "ph": "C",
            "name": name,
            "ts": (time.perf_counter() - self._t0) * 1e6,
            "tid": threading.get_ident(),
            "value": value,
        })

    # -- aggregation (bench / report helpers) -----------------------------

    def events(self) -> list[dict]:
        with self._lock:
            return list(self._events)

    def total_seconds(self, name: str) -> float:
        """Summed duration of every closed span named ``name``."""
        return sum(
            e["dur"] for e in self.events()
            if e["ph"] == "X" and e["name"] == name
        ) / 1e6

    def count(self, name: str) -> int:
        return sum(
            1 for e in self.events()
            if e["ph"] in ("X", "i") and e["name"] == name
        )

    def reset(self) -> None:
        """Drop recorded events (the clock keeps running) — bench uses this
        to exclude warmup from its feed-timing stats."""
        with self._lock:
            self._events.clear()

    # -- emission ---------------------------------------------------------

    def write_jsonl(self, path: str) -> str:
        os.makedirs(os.path.dirname(path) or ".", exist_ok=True)
        with open(path, "w") as fh:
            # first row: the clock anchor, so a bare trace.jsonl is
            # assemblable into a cross-process timeline even without the
            # heartbeat offsets (consumers filter on ph, so the metadata
            # row is invisible to the phase tables)
            fh.write(json.dumps(self.clock_sync()) + "\n")
            for ev in self.events():
                fh.write(json.dumps(ev) + "\n")
        return path

    def write_chrome_trace(self, path: str) -> str:
        """Perfetto/chrome://tracing-loadable trace-event JSON."""
        pid = os.getpid()
        events = self.events()
        out: list[dict] = []
        seen_threads: dict[int, str] = {}
        for ev in events:
            tid = ev.get("tid", 0)
            if tid not in seen_threads:
                seen_threads[tid] = str(ev.get("thread", tid))
        for tid, tname in seen_threads.items():
            out.append({
                "ph": "M", "name": "thread_name", "pid": pid, "tid": tid,
                "args": {"name": tname},
            })
        for ev in events:
            rec = {
                "ph": ev["ph"],
                "name": ev["name"],
                "ts": round(ev["ts"], 3),
                "pid": pid,
                "tid": ev.get("tid", 0),
            }
            if ev["ph"] == "X":
                rec["dur"] = round(ev["dur"], 3)
            if ev["ph"] == "i":
                rec["s"] = "t"  # thread-scoped instant
            args = {
                k: v for k, v in ev.items()
                if k not in ("ph", "name", "ts", "dur", "tid", "thread")
            }
            if args:
                rec["args"] = args
            out.append(rec)
        os.makedirs(os.path.dirname(path) or ".", exist_ok=True)
        with open(path, "w") as fh:
            json.dump({"traceEvents": out, "displayTimeUnit": "ms"}, fh)
        return path


#: shared no-op tracer — thread it where telemetry is off instead of None
NULL_TRACER = SpanTracer(enabled=False)
#: shared tracer that records nothing and annotates: the fit loop's when
#: ``cfg.telemetry`` is off, so that its host spans are in every profile
PROFILER_TRACER = SpanTracer(enabled=False, annotate=True)
