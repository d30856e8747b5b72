"""ReplicaSet — the serving fleet: N InferenceEngines, sharded sessions.

The serving path production-shaped (r21): instead of ONE engine on one
device, a :class:`ReplicaSet` runs N :class:`~.engine.InferenceEngine`
replicas, each pinned to its own device (round-robin over
``jax.devices()``), behind one routing front door. Three disciplines, all
reused from the training side rather than invented:

- **Replica membership is a MembershipTable** (robustness/membership.py):
  replica slots are the fixed axis, each (re)start of a replica joins at a
  bumped GENERATION — the auditable record that incarnation N+1 started
  with fresh state (a rebuilt engine: new session table, new zeroed carry
  rows, current live weights). The table's epoch bumps on every transition,
  exactly like site churn in the elastic-rounds daemon.
- **Sessions SHARD by id hash — never broadcast.** A streaming session's
  home replica is ``crc32(session_id) % capacity``; its chunks all route
  there, so its O(1) carry lives on exactly one device and the per-replica
  SessionTables partition the session space (capacity scales with the
  fleet instead of being replicated N times). When the home replica is
  down, routing probes forward to the next live slot; when a session MOVES
  (re-home on crash, or home coming back), the router closes it on the
  replica it left — the stale-carry kill: without the close, a session
  that bounced A→B→A and then loses A again would resolve on B as KNOWN
  and stream onto B's stale carry from its earlier sojourn. With it, every
  re-home re-enters through the fresh gate (carry zeroed in-trace, bumped
  session generation), so a re-homed stream replays bit-exact as a fresh
  session — the property tests/test_fleet.py pins.
- **Supervision is the PR 14 pattern in-process**: a supervisor thread
  probes each replica's lane threads (the in-process heartbeat) on an
  interval; a dead replica leaves the table, its engine is torn down, and
  a fresh engine rejoins at the next generation — with the CURRENT live
  weights, so a replica restarted after a hot-swap serves the published
  params, not the boot checkpoint.

Batched (sessionless) requests route to the least-loaded live replica
(queue depth, ties to the lowest slot). Params hot-swaps fan out to every
live replica (serving/publish.py drives them); each engine's donated-graft
swap keeps its own CompileGuard at zero, and :meth:`assert_no_compiles`
is the fleet-wide proof.
"""

from __future__ import annotations

import threading
import time
import zlib

from ..core.config import TrainConfig
from ..robustness.membership import MembershipTable
from ..telemetry.tracer import NULL_TRACER
from .engine import InferenceEngine, ServingError


def home_slot(session_id: str, capacity: int) -> int:
    """The session's home replica slot: a stable hash of the id over the
    fixed replica axis (crc32 — cheap, deterministic across processes, no
    PYTHONHASHSEED dependence)."""
    return zlib.crc32(str(session_id).encode()) % capacity


class ReplicaSet:
    """See module docstring. Construct, :meth:`warmup`, submit/stream,
    :meth:`close` (or use as a context manager)."""

    def __init__(self, cfg: TrainConfig, *, replicas: int = 2,
                 checkpoint: str | None = None, params=None,
                 batch_stats=None, supervise_interval_s: float = 0.2,
                 tracer=None, sink=None, bus=None, devices=None,
                 **engine_kwargs):
        import jax

        from ..telemetry.bus import NULL_BUS
        from ..trainer.checkpoint import load_inference_state

        if replicas < 1:
            raise ServingError(f"need >= 1 replica, got {replicas}")
        self.cfg = cfg
        self.tracer = tracer or NULL_TRACER
        self.sink = sink
        self.bus = bus if bus is not None else NULL_BUS
        self.meta: dict = {}
        if checkpoint is not None:
            params, batch_stats, self.meta = load_inference_state(checkpoint)
        if params is None:
            raise ServingError("need a checkpoint path or explicit params")
        # ONE host-side copy of the live weights;每 replica device_puts its
        # own. Updated on every successful swap so a restarted replica
        # serves the published weights, not the boot checkpoint.
        self._host_weights = (params, batch_stats or {})
        self._engine_kwargs = dict(engine_kwargs)
        # device pinning (r22): the fleet scheduler backfills idle slices
        # with serving replicas by handing the set the slice band's devices;
        # default (None) keeps the r21 behavior — replicas round-robin over
        # every visible device
        self._devices = list(devices) if devices else jax.devices()
        self.capacity = int(replicas)
        self.table = MembershipTable(capacity=self.capacity)
        self._engines: list = [None] * self.capacity
        # session id -> replica slot currently hosting it (the router's
        # memory — what lets a MOVE close the session at its old host)
        self._routes: dict = {}
        # one lock for table + engines + routes + weights: membership
        # transitions, routing and swaps are rare next to dispatches, and
        # dispatches don't take it (they run inside each engine)
        self._lock = threading.RLock()
        self._warm = False
        self.restarts = 0
        self.supervise_interval_s = float(supervise_interval_s)
        self._supervisor_stop = threading.Event()
        self._supervisor = threading.Thread(
            target=self._supervise, name="fleet-supervisor", daemon=True
        )
        self._t0 = time.monotonic()

    # -- replica lifecycle -----------------------------------------------

    def _replica_id(self, slot: int) -> str:
        return f"replica-{slot}"

    def _build_engine(self, slot: int) -> InferenceEngine:
        params, stats = self._host_weights
        return InferenceEngine(
            self.cfg, params=params, batch_stats=stats,
            device=self._devices[slot % len(self._devices)],
            bus_labels={"replica": str(slot)},
            tracer=self.tracer, sink=self.sink, bus=self.bus,
            close_sink=False, **self._engine_kwargs,
        )

    def _start_replica(self, slot: int) -> dict:
        """Build + warm one replica, THEN join it into the membership table
        at a bumped generation (a failed build leaves the table showing the
        slot down — consistent with reality, and the supervisor retries).
        Returns the warmup times. Caller holds the lock."""
        eng = self._build_engine(slot)
        times = eng.warmup()
        self.table, _, gen = self.table.join(self._replica_id(slot))
        self._engines[slot] = eng
        self.bus.gauge("serving_replicas_live", self.table.occupied)
        self.bus.counter("serving_replica_starts_total", replica=str(slot))
        if self.sink is not None:
            self.sink.append({
                "kind": "event", "name": "replica-start",
                "replica": slot, "generation": gen,
                "membership_epoch": self.table.epoch,
            })
        return times

    def warmup(self) -> dict:
        """Warm every replica (each AOT-compiles its own executable set on
        its own device) and start the supervisor. Returns
        ``{"replica-<i>/<lane>/<bucket>": seconds}``."""
        times = {}
        with self._lock:
            for slot in range(self.capacity):
                for k, v in self._start_replica(slot).items():
                    times[f"{self._replica_id(slot)}/{k}"] = v
            self._warm = True
        self._supervisor.start()
        return times

    def _replica_alive(self, slot: int) -> bool:
        eng = self._engines[slot]
        if eng is None or not eng._warm:
            return False
        try:
            return all(probe() for probe in eng.health_probes().values())
        except Exception:
            return False

    def kill_replica(self, slot: int) -> None:
        """Simulate a replica crash (tests, CI fault drills): wedge its
        lanes closed WITHOUT the orderly engine close. The supervisor's
        next probe sees the dead lanes and restarts the slot."""
        with self._lock:
            eng = self._engines[slot]
            if eng is None:
                return
            for lane in (getattr(eng, "_infer_lane", None),
                         getattr(eng, "_stream_lane", None)):
                if lane is not None:
                    lane.close(timeout=2.0)

    def restart_replica(self, slot: int) -> None:
        """Leave + rejoin the slot at a bumped generation with a FRESH
        engine on the current live weights. Every session homed or re-homed
        there loses its route (their next chunk re-resolves through the
        new, empty session table — the fresh gate)."""
        with self._lock:
            old = self._engines[slot]
            self._engines[slot] = None
            rid = self._replica_id(slot)
            if self.table.slot_of(rid) is not None:
                self.table, _ = self.table.leave(rid)
            self._routes = {
                sid: s for sid, s in self._routes.items() if s != slot
            }
            if old is not None:
                for lane in (getattr(old, "_infer_lane", None),
                             getattr(old, "_stream_lane", None)):
                    if lane is not None:
                        lane.close(timeout=2.0)
            self.restarts += 1
            self.bus.counter(
                "serving_replica_restarts_total", replica=str(slot)
            )
            self._start_replica(slot)

    def _supervise(self) -> None:
        """The PR 14 supervisor loop, in-process: probe every slot's lane
        threads; restart dead replicas at the next generation."""
        while not self._supervisor_stop.wait(self.supervise_interval_s):
            with self._lock:
                if not self._warm:
                    continue
                dead = [
                    slot for slot in range(self.capacity)
                    if not self._replica_alive(slot)
                ]
            for slot in dead:
                if self._supervisor_stop.is_set():
                    return
                try:
                    self.restart_replica(slot)
                except Exception:
                    # build/warmup failed — the slot stays down and the
                    # next probe retries; never kill the supervisor
                    self.bus.counter(
                        "serving_replica_restart_failures_total",
                        replica=str(slot),
                    )

    # -- routing ---------------------------------------------------------

    def _live_slots(self) -> list:
        return [
            s for s in range(self.capacity) if self._replica_alive(s)
        ]

    def _route_session(self, session_id: str) -> int:
        """The session's CURRENT replica: its home slot when live, else the
        next live slot (linear probe). A move closes the session at the
        replica it left — see the module docstring's stale-carry kill."""
        with self._lock:
            home = home_slot(session_id, self.capacity)
            slot = None
            for probe in range(self.capacity):
                cand = (home + probe) % self.capacity
                if self._replica_alive(cand):
                    slot = cand
                    break
            if slot is None:
                raise ServingError("no live replica to route to")
            prev = self._routes.get(session_id)
            if prev is not None and prev != slot:
                prev_eng = self._engines[prev]
                if prev_eng is not None and self._replica_alive(prev):
                    try:
                        prev_eng.close_session(session_id)
                    except Exception:
                        pass  # never resolved there (or already closed)
                self.bus.counter(
                    "serving_session_rehomes_total", replica=str(slot)
                )
            self._routes[session_id] = slot
            return slot

    def _least_loaded(self) -> int:
        """Batched requests have no affinity: lowest queue depth wins,
        ties to the lowest slot."""
        with self._lock:
            live = self._live_slots()
            if not live:
                raise ServingError("no live replica to route to")
            return min(
                live,
                key=lambda s: (self._engines[s]._infer_lane.depth(), s),
            )

    # -- request front door ----------------------------------------------

    def submit(self, rows, weights=None, trace_id=None, priority: int = 0,
               deadline_ms=None):
        self._ensure_warm()
        slot = self._least_loaded()
        return self._engines[slot].submit(
            rows, weights=weights, trace_id=trace_id, priority=priority,
            deadline_ms=deadline_ms,
        )

    def stream(self, session_id: str, windows, trace_id=None,
               priority: int = 0):
        self._ensure_warm()
        slot = self._route_session(session_id)
        return self._engines[slot].stream(
            session_id, windows, trace_id=trace_id, priority=priority
        )

    def close_session(self, session_id: str) -> None:
        with self._lock:
            slot = self._routes.pop(session_id, None)
            if slot is not None and self._engines[slot] is not None:
                self._engines[slot].close_session(session_id)

    def replica_of(self, session_id: str):
        """Where the router last placed a session (None = never routed)."""
        with self._lock:
            return self._routes.get(session_id)

    def _ensure_warm(self) -> None:
        if not self._warm:
            raise ServingError("call warmup() before submitting requests")

    @property
    def streaming(self) -> bool:
        """Whether the replicas run a streaming lane (uniform with the
        single-engine surface for the CLI)."""
        return any(
            e.streaming for e in self._engines if e is not None
        )

    @property
    def warmup_seconds(self) -> float:
        return round(sum(
            e.warmup_seconds for e in self._engines if e is not None
        ), 4)

    def drain(self, timeout: float = 30.0) -> None:
        deadline = time.monotonic() + timeout
        while time.monotonic() < deadline:
            with self._lock:
                engines = [e for e in self._engines if e is not None]
            if all(
                L.depth() == 0
                for e in engines
                for L in (getattr(e, "_infer_lane", None),
                          getattr(e, "_stream_lane", None)) if L
            ):
                return
            time.sleep(0.002)

    # -- publish plane (serving/publish.py drives these) ------------------

    def weights(self) -> tuple:
        """Host-side (params, batch_stats) — the rollback retention target
        (device-agnostic: a later swap-back device_puts per replica)."""
        with self._lock:
            return self._host_weights

    def shadow_score(self, params, batch_stats=None) -> dict:
        """Score a candidate on ONE live replica's mirrored traffic (the
        executables are identical across replicas — one shadow pass proves
        the candidate for the fleet)."""
        with self._lock:
            live = self._live_slots()
            if not live:
                raise ServingError("no live replica to shadow-score on")
            eng = self._engines[live[0]]
        return eng.shadow_score(params, batch_stats)

    def swap_params(self, params, batch_stats=None) -> dict:
        """Fan the donated hot-swap out to every live replica; the host
        weight copy updates so later restarts serve the new params.
        Returns per-replica pause plus the max (the fleet's publish-window
        pause figure).

        The candidate is snapshotted to HOST arrays first: each engine's
        swap donates the buffers it is handed, and when the candidate
        already lives on some replica's device the first swap would delete
        the very arrays the next replica needs. From the host snapshot,
        every engine device_puts (and donates) its own private copy."""
        import jax
        import numpy as np

        params = jax.tree.map(np.asarray, params)
        batch_stats = (
            jax.tree.map(np.asarray, batch_stats)
            if batch_stats is not None else None
        )
        with self._lock:
            self._ensure_warm()
            pauses = {}
            for slot in self._live_slots():
                got = self._engines[slot].swap_params(params, batch_stats)
                pauses[self._replica_id(slot)] = got["pause_ms"]
            self._host_weights = (params, batch_stats or {})
        return {
            "pause_ms": max(pauses.values()) if pauses else 0.0,
            "per_replica": pauses,
        }

    # -- proofs + rollup --------------------------------------------------

    def assert_no_compiles(self) -> None:
        """The fleet-wide zero-compile proof — every replica's guard, so N
        replicas and K swaps later the request path still never traced."""
        with self._lock:
            engines = [e for e in self._engines if e is not None]
        for eng in engines:
            eng.assert_no_compiles()

    def compiles_after_warmup(self) -> dict:
        with self._lock:
            engines = list(enumerate(self._engines))
        return {
            f"replica-{i}/{k}": v
            for i, e in engines if e is not None
            for k, v in e.compiles_after_warmup().items()
        }

    def health_probes(self) -> dict:
        probes = {"warm": lambda: self._warm}
        for slot in range(self.capacity):
            probes[f"replica_{slot}"] = (
                lambda s=slot: self._replica_alive(s)
            )
        return probes

    def status(self) -> dict:
        with self._lock:
            statuses = {
                self._replica_id(i): e.status()
                for i, e in enumerate(self._engines) if e is not None
            }
            return {
                "task_id": self.cfg.task_id,
                "warm": self._warm,
                "replicas": self.capacity,
                # the device band the replicas round-robin over (r22: the
                # scheduler pins backfill lanes to idle slices' devices)
                "devices": [str(d) for d in self._devices],
                "replicas_live": self.table.occupied,
                "membership": self.table.to_json(),
                "routed_sessions": len(self._routes),
                "restarts": self.restarts,
                "per_replica": statuses,
            }

    def summary(self) -> dict:
        """The fleet rollup serve_summary row: per-replica summaries merged
        (requests/samples summed, latency percentiles over the union via
        the merged bus histogram when available)."""
        with self._lock:
            parts = [
                e.summary() for e in self._engines if e is not None
            ]
        agg = {
            "kind": "serve_summary",
            "task_id": self.cfg.task_id,
            "replica": "fleet",
            "replicas": self.capacity,
            "restarts": self.restarts,
            "swaps": sum(p["swaps"] for p in parts),
            "nonfinite_rows": sum(p["nonfinite_rows"] for p in parts),
            "requests": sum(p["requests"] for p in parts),
            "samples": sum(p["samples"] for p in parts),
            "stream_chunks": sum(p["stream_chunks"] for p in parts),
            "dispatches": sum(p["dispatches"] for p in parts),
            "deferrals": sum(p["deferrals"] for p in parts),
            "shed": sum(p["shed"] for p in parts),
            "warmup_seconds": round(
                sum(p["warmup_seconds"] for p in parts), 4
            ),
            "compiles_after_warmup": sum(
                p["compiles_after_warmup"] for p in parts
            ),
            "max_queue_depth": max(
                (p["max_queue_depth"] for p in parts), default=0
            ),
        }
        elapsed = max(time.monotonic() - self._t0, 1e-9)
        agg["requests_per_s"] = round(agg["requests"] / elapsed, 2)
        agg["samples_per_s"] = round(agg["samples"] / elapsed, 2)
        # pad waste + bucket hit rate: dispatch-weighted means of the
        # per-replica figures
        disp = max(agg["dispatches"], 1)
        agg["bucket_hit_rate"] = round(
            sum(p["bucket_hit_rate"] * p["dispatches"] for p in parts)
            / disp, 4,
        )
        agg["pad_waste_pct"] = round(
            sum(p["pad_waste_pct"] * p["dispatches"] for p in parts)
            / disp, 2,
        )
        hist = self.bus.merged_histogram("serving_request_latency_ms")
        if hist is not None and hist.count:
            pct = hist.percentiles()
            agg["latency_ms_p50"] = pct["p50"]
            agg["latency_ms_p95"] = pct["p95"]
            agg["latency_ms_p99"] = pct["p99"]
        else:
            lat = sorted(
                v for p in parts
                for v in [p["latency_ms_p50"], p["latency_ms_p95"],
                          p["latency_ms_p99"]]
                if v is not None
            )
            agg["latency_ms_p50"] = lat[0] if lat else None
            agg["latency_ms_p95"] = lat[len(lat) // 2] if lat else None
            agg["latency_ms_p99"] = lat[-1] if lat else None
        agg["per_replica"] = parts
        return agg

    def close(self) -> dict:
        """Stop supervision, close every replica (each appends its own
        serve_summary row), emit the fleet rollup row, close the shared
        sink once, and re-assert the fleet-wide zero-compile proof."""
        self._supervisor_stop.set()
        if self._supervisor.is_alive():
            self._supervisor.join(5.0)
        with self._lock:
            engines = [e for e in self._engines if e is not None]
        for eng in engines:
            eng.close()
        summary = self.summary()
        if self.sink is not None:
            self.sink.append(summary)
            self.sink.close()
        self.assert_no_compiles()
        return summary

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        self.close()
        return False
