"""Unified telemetry: span tracer, on-device round metrics, xprof hooks,
and the per-fit manifest/metrics sink.

The observability layer the ROADMAP's production north star needs (r10).
Before this package, a run's only windows were the level-gated stdout
logger (trainer/logs.py), ad-hoc timers in bench.py, and
scripts/profile_epoch.py's one-off attribution. Now:

- :mod:`.tracer` — thread-safe host-side **span tracer**: monotonic nested
  spans (safe across the trainer/prefetch.py planner thread), emitted as
  JSONL and as Chrome trace-event JSON (load in Perfetto / chrome://tracing).
  A span has two sinks: the tracer's own event buffer (its own clock; on when
  ``TrainConfig.telemetry="on"``) and, for an annotating tracer, the host
  plane of a running ``jax.profiler`` session (the device trace's clock;
  the fit loop's tracer always annotates: ``PROFILER_TRACER`` when telemetry
  is off). Also the home of the ONE ``duration`` bookkeeping helper (formerly
  trainer/logs.py) and of bench.py's feed timing.
- :mod:`.metrics` — **on-device round metrics** riding the epoch's rounds
  scan (trainer/steps.py): per-site grad/update norms, engine aggregation
  residual, modeled collective payload bytes — accumulated in
  ``TrainState.telemetry`` (sharded ``P(site)`` like ``health``, donation-
  and checkpoint-safe, statically compiled out when
  ``TrainConfig.telemetry="off"``).
- :mod:`.xprof` — ``jax.profiler`` capture hooks: a start/stop window over a
  configurable epoch range (``TrainConfig.xprof_dir`` / ``xprof_window``,
  CLI ``--xprof-dir``) plus the device-op trace summarizer
  scripts/profile_epoch.py consumes.
- :mod:`.scopes` — the names the program gives its own work. The **host
  half**: the seven spans of the fit loop (``plan-wait``, ``plan-build``,
  ``epoch-inputs``, ``epoch-dispatch``, ``loss-fetch``, ``epoch-account``,
  ``inventory-upload``; trainer/loop.py ``run_epoch``, trainer/prefetch.py),
  each opened with ``epoch=`` and written as ``dinunet/<name>`` into any
  profiler session, whatever ``cfg.telemetry`` says: under ``--xprof-dir`` they
  lie above the device's operations on one clock, and the benchmark's
  ``loop_*_idle_ms_*`` metrics put the device's idle gaps down to them. The
  **device half**, the device scopes: the names of the five
  ``jax.named_scope``s the epoch program always wears (``data/gather``,
  ``model/fwd_bwd``, ``engine/aggregate`` with ``poweriter`` nested in it,
  ``optimizer/update``; trainer/steps.py, engines/lowrank.py) beside the two
  Pallas kernel names of ops/lstm_pallas.py (``lstm_fwd``, ``lstm_bwd``).
  Metadata only — no switch, the lowering is the same program with and
  without them (tests/test_scopes.py). Under ``--xprof-dir`` an operator sees
  every device op's scope as its ``tf_op`` / op name in xprof's op profile
  and trace viewer (``jit(epoch_fn_impl)/while/body/.../model/fwd_bwd/
  jvp(ICALstm)/lstm/fwd/lstm_fwd/pallas_call``; a transform wraps the scope
  it maps: ``vmap(engine/aggregate)/poweriter/while/...``), and the Mosaic
  calls as instructions ``%lstm_fwd.N`` / ``%lstm_bwd.N``. The scope lives in
  the profile's per-instruction event METADATA, which
  ``jax.profiler.ProfileData`` does not expose (PERF.md §3): the benchmark
  reads the kernel names today, the scopes once its extractor reads metadata.
- :mod:`.sink` — the per-fit ``manifest.json`` (config hash, jax versions,
  mesh topology, engine, git rev) and ``metrics.jsonl`` artifact writers,
  with the schema validators CI gates on.
- :mod:`.report` — ``python -m dinunet_implementations_tpu.telemetry.report``
  renders a run summary (phase time table, per-site rollup, compile/transfer
  counters) from those artifacts.

The LIVE plane (r16) — everything above is post-hoc; these answer questions
about a RUNNING process:

- :mod:`.hist` — fixed log-spaced mergeable latency histograms (exact merge
  associativity, bounded-error p50/p95/p99).
- :mod:`.bus` — the process-wide MetricsBus of named counters/gauges/
  histograms (snapshot-consistent reads; :data:`~.bus.NULL_BUS` keeps the
  off path free).
- :mod:`.exporter` — stdlib HTTP endpoints ``/metrics`` (Prometheus text),
  ``/healthz``, ``/statusz`` (incl. SLO error-budget burn), ``/tracez``,
  behind ``--statusz-port`` on the daemon and serving CLIs.
- :mod:`.flight` — the crash-safe flight recorder: a bounded ring of recent
  spans/events that dumps ``flight_<pid>.json`` (with a final bus snapshot)
  on unhandled exception or SIGTERM.

The POD plane (r23) — the live plane is per-process; a supervised
multislice run is many processes. These merge them:

- :mod:`.collector` — the PodCollector: discovers workers from their
  heartbeat-advertised ``/statusz`` ports, scrapes and merges their bus
  snapshots (counters summed, gauges/histograms stamped
  ``{process,slice}``), and duck-types the bus read API so one
  StatusExporter serves pod scope unchanged.
- :mod:`.assemble` — ``python -m …telemetry.assemble <pod-dir>`` merges
  per-process trace.jsonl files into ONE clock-aligned Perfetto timeline
  (heartbeat-exchanged monotonic→wall offsets).
- :mod:`.postmortem` — ``python -m …telemetry.postmortem <pod-dir>``
  reconstructs an ordered incident timeline from flight dumps, heartbeat
  history, the slice-liveness spool, consensus decisions, and scheduler
  grant logs; ``--validate`` asserts the story is complete.

Distinct from ``DINUNET_SANITIZE`` (checks/sanitize.py): the sanitizer is a
debug mode that FAILS a run violating invariants; telemetry OBSERVES healthy
runs and writes artifacts. They compose — the sanitizer's compile counter is
one of the counters telemetry exports.
"""

from .bus import NULL_BUS, MetricsBus, global_bus
from .hist import LogHistogram
from .tracer import (
    NULL_TRACER,
    PROFILER_TRACER,
    SpanTracer,
    duration,
    new_trace_id,
)

__all__ = [
    "NULL_TRACER",
    "PROFILER_TRACER",
    "SpanTracer",
    "duration",
    "new_trace_id",
    "LogHistogram",
    "MetricsBus",
    "NULL_BUS",
    "global_bus",
    "StatusExporter",
    "FlightRecorder",
    "FitTelemetry",
    "default_round_telemetry",
    "payload_bytes_of",
    "telemetry_summary",
    "validate_manifest",
    "validate_metrics_rows",
    "XprofWindow",
    "summarize_device_ops",
    "PodCollector",
    "LabelCollisionError",
    "merge_snapshots",
    "stamp_snapshot",
]


def __getattr__(name):
    # jax-adjacent halves load lazily: the tracer must stay importable from
    # stdlib-only contexts (the report CLI on a bare box, bench's host-side
    # feed timing) without pulling jax in.
    if name in ("FitTelemetry", "validate_manifest", "validate_metrics_rows"):
        from . import sink

        return getattr(sink, name)
    if name in ("default_round_telemetry", "payload_bytes_of",
                "telemetry_summary"):
        from . import metrics

        return getattr(metrics, name)
    if name in ("XprofWindow", "summarize_device_ops"):
        from . import xprof

        return getattr(xprof, name)
    if name == "StatusExporter":
        from .exporter import StatusExporter

        return StatusExporter
    if name == "FlightRecorder":
        from .flight import FlightRecorder

        return FlightRecorder
    if name in ("PodCollector", "LabelCollisionError", "merge_snapshots",
                "stamp_snapshot"):
        from . import collector

        return getattr(collector, name)
    raise AttributeError(name)
