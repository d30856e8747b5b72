"""From the profiler's files to plain intervals.

``load(trace_dir)`` reads EVERY ``*.xplane.pb`` under the directory (one per
host) through ``jax.profiler.ProfileData`` and EVERY device plane in each, not
the first (the defect of ``telemetry/xprof.summarize_device_ops``, which also
read a ``.trace.json.gz`` that jax 0.9 need not write).

What counts as what (looked at by hand on a v5e trace, PR 22):

- a device is a plane named ``/device:TPU:<n>``; its operations are the events
  of the line ``XLA Ops`` (the ``XLA Modules`` and ``Steps`` lines hold whole
  programs and would double-count). An event's name is the whole HLO
  instruction (``%fwd.22 = (bf16[98,512,174]...) custom-call(...),
  custom_call_target="tpu_custom_call"``): ``Op.name`` keeps the part before
  `` = `` without the ``%``, ``Op.text`` all of it. The line is NESTED: the
  rounds scan is one ``%while`` event that covers every operation of its body.
  So every op carries ``leaf`` (nothing nested inside it) and ``self_s`` (its
  length minus its direct children): sums and rankings use those, the busy
  union uses everything;
- the line ``Async XLA Ops`` of the same plane holds the spans of asynchronous
  operations from their ``-start`` to their ``-done`` (copies, and on a mesh
  the collectives): kept as ops of lane ``async``, never counted as busy;
- where no device plane exists (a CPU rehearsal, and the recorded fixture of
  ``tests/``) the XLA thunks the CPU client runs, which are host-plane events
  with an ``hlo_op`` stat, form one pseudo-device ``host-xla``: the reduction
  code is then the same, the numbers are not device numbers;
- the harness's own spans are host-plane events whose name starts with
  ``bench/`` (``jax.profiler.TraceAnnotation``), on the same clock.
"""

from __future__ import annotations

import glob
import os
import re
from dataclasses import dataclass, field

DEVICE_PLANE = re.compile(r"^/device:(TPU|GPU):(\d+)")
OP_LINES = {"XLA Ops": "sync", "Async XLA Ops": "async"}
SPAN_PREFIX = "bench/"
TEXT_STATS = ("hlo_category", "long_name", "tf_op", "hlo_op", "kernel_details")


@dataclass
class Op:
    name: str  # the instruction's name: "fusion.400", "all-reduce.3", "fwd.22"
    start: float  # seconds on the trace clock
    end: float
    text: str = ""  # the whole event name plus the stats a pattern may want
    lane: str = "sync"  # "sync": the device's op timeline; "async": a span
    leaf: bool = True  # no other op of the lane is nested inside it
    self_s: float = -1.0  # length minus direct children (set by nest())

    def __post_init__(self):
        self.text = self.text or self.name
        if self.self_s < 0:
            self.self_s = self.end - self.start


@dataclass(frozen=True)
class Span:
    name: str
    start: float
    end: float


@dataclass
class Trace:
    devices: dict = field(default_factory=dict)  # device name -> [Op]
    spans: list = field(default_factory=list)  # [Span], harness spans
    rehearsal: bool = False  # True: no device plane, host-xla stands in

    def window(self, span_name: str = "bench/epoch"):
        """``(lo, hi)`` from the first start to the last end of the spans of
        that name, or None when there is none."""
        hits = [s for s in self.spans if s.name == span_name]
        if not hits:
            return None
        return min(s.start for s in hits), max(s.end for s in hits)


def xplane_files(trace_dir: str) -> list[str]:
    return sorted(glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                            recursive=True))


def _op(event, stats, lane: str = "sync") -> Op:
    start = event.start_ns * 1e-9
    text = " ".join([event.name] + [f"{k}={stats[k]}" for k in TEXT_STATS
                                     if k in stats])
    name = event.name.split(" = ", 1)[0].lstrip("%")
    return Op(name, start, start + event.duration_ns * 1e-9, text, lane)


def nest(ops: list) -> list:
    """Mark the nesting of one sequential lane: ``leaf`` and ``self_s`` of
    every op, from a stack over the ops sorted by start (ties: longest
    first). Returns the list it was given."""
    stack: list[Op] = []
    for o in sorted(ops, key=lambda o: (o.start, -o.end)):
        o.leaf, o.self_s = True, o.end - o.start
        while stack and stack[-1].end <= o.start:
            stack.pop()
        if stack and o.end <= stack[-1].end:
            stack[-1].leaf = False
            stack[-1].self_s -= o.end - o.start
        stack.append(o)
    return ops


def load(trace_dir: str) -> Trace:
    from jax.profiler import ProfileData

    trace = Trace()
    host_ops: list[Op] = []
    for path in xplane_files(trace_dir):
        data = ProfileData.from_file(path)
        tag = os.path.basename(path)[: -len(".xplane.pb")]
        for plane in data.planes:
            dev = DEVICE_PLANE.match(plane.name)
            for line in plane.lines:
                if dev:
                    lane = OP_LINES.get(line.name)
                    if lane is None:
                        continue
                    ops = [_op(e, dict(e.stats), lane) for e in line.events]
                    trace.devices.setdefault(f"{tag}{plane.name}", []).extend(
                        nest(ops) if lane == "sync" else ops)
                    continue
                for e in line.events:
                    if e.name.startswith(SPAN_PREFIX):
                        s = e.start_ns * 1e-9
                        trace.spans.append(
                            Span(e.name, s, s + e.duration_ns * 1e-9))
                    elif plane.name.startswith("/host:"):
                        stats = dict(e.stats)
                        if "hlo_op" in stats:
                            host_ops.append(_op(e, stats))
    if not trace.devices and host_ops:
        # CPU thunks run on several threads: no nesting to mark
        trace.devices["host-xla"] = host_ops
        trace.rehearsal = True
    trace.spans.sort(key=lambda s: s.start)
    return trace
