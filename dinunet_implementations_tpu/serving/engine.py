"""InferenceEngine — AOT-compiled, continuously-batched serving of trained
checkpoints.

The first inference surface of the build (ROADMAP item 5): load a trained
checkpoint (params + batch_stats ONLY — optimizer/engine/buffer state
stripped by trainer/checkpoint.py ``load_inference_state``), compile every
program the server will ever run at startup, and answer requests through the
continuous microbatcher. Three invariants the tests and the semantic tier
pin:

- **Compile-free request path.** Warmup ``.lower().compile()``s ONE
  executable per (lane, shape bucket) — against the persistent XLA compile
  cache when one is placed (``JAX_COMPILATION_CACHE_DIR``, else
  ``TrainConfig.compile_cache_dir``; core/jaxcompat.py), so a restart
  loads machine code from disk instead of recompiling (the cold/warm gap
  ``bench.py --serve`` measures). The request path only ever invokes those
  stored ``Compiled`` executables: a shape outside the bucket set is a loud
  error, never a silent retrace. A :class:`~..checks.sanitize.CompileGuard`
  snapshots the engine's jitted entry points AFTER warmup with
  ``max_compiles=0`` — :meth:`assert_no_compiles` is the zero-compile proof
  the CI smoke and tests gate on.
- **Bit-exactness with the trainer.** The batched lane compiles the SAME
  ``eval_forward`` the trainer's eval path runs (trainer/steps.py) — served
  probabilities on a batch reproduce the trainer's recorded eval outputs
  bit-for-bit (tests/test_serving.py; checks/semantic.py S005 serving cell
  proves the programs lower identically).
- **O(1) streaming.** The ICA-LSTM lane keeps per-session ``(h, c, pooled,
  count)`` carry in a device-resident ``[slots+1, …]`` table
  (serving/session.py); the streaming executable gathers carries by slot
  index, advances only the chunk's NEW windows (models/icalstm.py
  ICALstmStream), and scatters back — per-chunk cost independent of how long
  the session has been running. The table is the executable's DONATED input
  buffer: it updates in place (input/output aliased, proven by the S003
  serving cell), so session state never double-resides in HBM.
"""

from __future__ import annotations

import threading
import time

import numpy as np

from ..core.config import TrainConfig
from ..telemetry.tracer import NULL_TRACER

#: serving shape buckets: row capacities the batched lane compiles (requests
#: pad into the smallest bucket that fits — a small closed set keeps warmup
#: cheap and the compiled-program set finite)
DEFAULT_ROW_BUCKETS = (1, 2, 4, 8, 16)
#: session capacities per streaming dispatch
DEFAULT_STREAM_BUCKETS = (1, 4)
#: windows per streaming chunk executable (longer runs split; shorter pad
#: with step_valid=0 — exact identities on the carry)
DEFAULT_STREAM_CHUNK = 8


class ServingError(RuntimeError):
    """The serving engine cannot honor a request/configuration."""


def _nonfinite_rows(probs) -> int:
    """Rows of an already-fetched ``[n, C]`` probability block holding a
    NaN or an infinity."""
    return int((~np.isfinite(probs).all(axis=-1)).sum())


class _Req:
    """One queued request (either lane)."""

    __slots__ = ("rows", "weights", "future", "session", "slot", "generation",
                 "fresh", "step_valid", "trace_id", "_submit_t", "_seq",
                 "priority", "deadline_ms")

    def __init__(self, rows, weights=None, session=None, step_valid=None,
                 trace_id=None, priority: int = 0, deadline_ms=None):
        from ..telemetry.tracer import new_trace_id
        from .microbatch import RequestFuture

        self.rows = rows
        self.weights = weights
        self.session = session
        self.step_valid = step_valid
        self.slot = self.generation = 0
        self.fresh = False
        # admission (r21): higher priority collects first; deadline_ms is
        # the submit-relative staleness bound past which the request is
        # SHED instead of dispatched (None = never)
        self.priority = int(priority)
        self.deadline_ms = deadline_ms
        self._seq = 0
        # cross-process trace propagation: a caller-supplied id (a client's
        # request id, a spool event's trace) or a fresh one — it lands in
        # the dispatch row and the serve span, so one request is followable
        # across the telemetry artifacts
        self.trace_id = trace_id or new_trace_id()
        self.future = RequestFuture()
        self.future.trace_id = self.trace_id
        self._submit_t = 0.0


class InferenceEngine:
    """See module docstring. Construct, :meth:`warmup`, then submit; always
    :meth:`close` (or use as a context manager) — it stops the lane threads
    and finalizes the serving telemetry rows."""

    def __init__(self, cfg: TrainConfig, *, checkpoint: str | None = None,
                 params=None, batch_stats=None,
                 row_buckets=DEFAULT_ROW_BUCKETS,
                 stream_buckets=DEFAULT_STREAM_BUCKETS,
                 stream_chunk: int = DEFAULT_STREAM_CHUNK,
                 stream_slots: int = 32,
                 max_delay_ms: float = 2.0,
                 max_queue: int | None = None,
                 streaming: bool | None = None,
                 device=None, bus_labels: dict | None = None,
                 close_sink: bool = True,
                 tracer=None, sink=None, bus=None):
        import jax

        from ..runner.registry import get_task
        from ..trainer.checkpoint import load_inference_state
        from ..trainer.steps import FederatedTask

        from ..telemetry.bus import NULL_BUS

        self.cfg = cfg
        self.tracer = tracer or NULL_TRACER
        self.sink = sink
        self.bus = bus if bus is not None else NULL_BUS
        self.spec = get_task(cfg.task_id)
        if self.spec.serving is None:
            raise ServingError(
                f"task {cfg.task_id!r} has no serving spec "
                "(runner/registry.py ServingSpec)"
            )
        self.meta: dict = {}
        if checkpoint is not None:
            params, batch_stats, self.meta = load_inference_state(checkpoint)
        if params is None:
            raise ServingError("need a checkpoint path or explicit params")
        from ..core.jaxcompat import enable_compile_cache

        enable_compile_cache(cfg.compile_cache_dir)
        self.model = self.spec.build_model(cfg)
        self.task = FederatedTask(
            self.model, has_batch_stats=bool(batch_stats)
        )
        # every device-resident buffer of this engine — params, batch stats,
        # the streaming carry table, and all AOT executables — pins to ONE
        # device (``device=None`` keeps jax's default, the single-engine
        # behavior). A ReplicaSet (serving/fleet.py) hands each replica its
        # own device, so N replicas are N independent single-device servers:
        # the request path stays collective-free per replica (S001).
        self.device = device
        self._bus_labels = dict(bus_labels or {})
        self._close_sink = close_sink
        # params + batch_stats live as ONE tuple bound by a single attribute
        # store/read (atomic under the GIL): a hot-swap (swap_params) rebinds
        # the tuple while dispatch threads are mid-flight, and a dispatch
        # must never pair new params with old stats
        self._live = (
            jax.device_put(params, device),
            jax.device_put(batch_stats or {}, device),
        )
        self.sample_shape = tuple(self.spec.serving.sample_shape(cfg))
        self.row_buckets = tuple(sorted(set(int(b) for b in row_buckets)))
        self.stream_chunk = int(stream_chunk)
        self.stream_buckets = tuple(sorted(set(int(b) for b in stream_buckets)))
        # streaming lane: auto (the task/config supports it) unless the
        # caller opts out (streaming=False — a batched-only deployment)
        self.streaming = self.spec.serving.supports_streaming(cfg)
        if streaming is False:
            self.streaming = False
        elif streaming is True and not self.streaming:
            raise ServingError(
                f"task {cfg.task_id!r} with this config cannot stream "
                "(needs a causal recurrent head)"
            )
        self._warm = False
        self._exec: dict = {}  # (lane, bucket) -> Compiled
        self._guard = None
        self._lock = threading.Lock()  # stats + latency list
        # SessionTable bookkeeping is mutated by the stream lane's dispatch
        # thread (resolve) AND the caller's thread (close_session, summary's
        # occupancy read) — every access goes through this lock
        self._session_lock = threading.Lock()
        self._latencies: list = []  # (lane, seconds) per request
        self._t0 = time.monotonic()
        self.warmup_seconds = 0.0
        self.stats = {
            "requests": 0, "samples": 0, "stream_chunks": 0, "swaps": 0,
            # answered rows whose probabilities were not all finite
            "nonfinite_rows": 0,
        }
        self._max_delay_ms = max_delay_ms
        self._max_queue = max_queue
        # mirror ring: the last few batched dispatch payloads, kept for
        # shadow-lane scoring of a publish candidate against REAL recent
        # traffic (serving/publish.py) — the candidate runs through the same
        # stored executables these payloads already ran through
        self._mirror: list = []
        self._mirror_cap = 4

        # -- the two jitted entry points (warmup traces them; the request
        # path only runs their stored AOT executables)
        from ..trainer.steps import eval_forward

        def infer_fn(params, stats, x, w):
            return eval_forward(self.task, params, stats, x, None, w)

        self._infer_jit = jax.jit(infer_fn)
        # the hot-swap graft: an identity over (params, batch_stats) with
        # BOTH arguments donated — XLA aliases every input leaf straight into
        # the output (the S003 fleet cell proves it), so installing a
        # published candidate is a zero-copy buffer donation onto this
        # engine's device, never a recompile (executables are keyed by shape,
        # and swap_params refuses shape drift loudly). Compiled AOT at warmup
        # and counted by the same CompileGuard as the request lanes: the
        # zero-compile proof extends ACROSS publishes.
        self._swap_jit = jax.jit(
            lambda p, s: (p, s), donate_argnums=(0, 1)
        )

        self._stream_jit = None
        self._table = None
        self.sessions = None
        if self.streaming:
            from ..models.icalstm import ICALstmStream
            from .session import SessionTable, init_carry_table

            if stream_slots < self.stream_buckets[-1]:
                # a dispatch of B sessions needs B distinct slots: with
                # fewer, resolving request k can LRU-evict a session
                # resolved EARLIER IN THE SAME BATCH — duplicate slot
                # indices in one scatter, two live streams sharing (and
                # corrupting) one carry row
                raise ServingError(
                    f"stream_slots={stream_slots} is below the largest "
                    f"stream bucket ({self.stream_buckets[-1]}); a single "
                    "dispatch could evict its own batch's sessions"
                )
            a = cfg.ica_args
            self._stream_model = ICALstmStream(
                input_size=a.input_size, hidden_size=a.hidden_size,
                num_cls=a.num_class, num_comps=a.num_components,
                window_size=a.window_size,
                compute_dtype=a.compute_dtype or None,
            )
            self.sessions = SessionTable(stream_slots)
            self._table = jax.device_put(
                init_carry_table(stream_slots, a.hidden_size), device
            )
            self._stream_jit = jax.jit(
                self._stream_step, donate_argnums=(2,)
            )

    # the pre-swap names, kept as views of the atomic live tuple (tests,
    # bench and the semantic cells read them)
    @property
    def _params(self):
        return self._live[0]

    @property
    def _stats(self):
        return self._live[1]

    # -- traced programs -------------------------------------------------

    def _stream_step(self, params, stats, table, slot_ix, fresh, x,
                     step_valid, valid):
        """The streaming executable: gather carries by slot, zero fresh
        sessions in-trace, advance the chunk, scatter back (valid-gated, so
        padded request slots are exact identities on their — trash — row).
        ``table`` is donated: the update aliases in place."""
        import jax
        import jax.numpy as jnp

        h, c, pooled = (
            table["h"][slot_ix], table["c"][slot_ix], table["pooled"][slot_ix]
        )
        count = table["count"][slot_ix]
        keep = (1.0 - fresh)[:, None]
        h, c, pooled = h * keep, c * keep, pooled * keep
        count = count * (1.0 - fresh)
        variables = {"params": params}
        if self.task.has_batch_stats:
            variables["batch_stats"] = stats
        logits, (h2, c2, p2, n2) = self._stream_model.apply(
            variables, x, h, c, pooled, count, step_valid
        )
        probs = jax.nn.softmax(logits, -1)
        vg = valid[:, None] > 0
        new_table = {
            "h": table["h"].at[slot_ix].set(jnp.where(vg, h2, h)),
            "c": table["c"].at[slot_ix].set(jnp.where(vg, c2, c)),
            "pooled": table["pooled"].at[slot_ix].set(jnp.where(vg, p2, pooled)),
            "count": table["count"].at[slot_ix].set(
                jnp.where(valid > 0, n2, count)
            ),
        }
        return probs, new_table

    # -- warmup (the only place anything compiles) -----------------------

    def warmup(self) -> dict:
        """AOT-compile every (lane, bucket) executable; returns
        ``{lane/bucket: seconds}``. After this, the engine is armed: the
        CompileGuard snapshot makes any later compilation a hard failure.

        With a persistent compile cache enabled (core/jaxcompat.py) a
        restart loads every executable, streaming lane included, from disk
        instead of recompiling."""
        import jax.numpy as jnp

        from ..checks.sanitize import CompileGuard

        t0 = time.monotonic()
        times = {}
        with self.tracer.span("serve-warmup"):
            for b in self.row_buckets:
                tb = time.monotonic()
                x = jnp.zeros((b,) + self.sample_shape, jnp.float32)
                w = jnp.ones((b,), jnp.float32)
                self._exec[("infer", b)] = self._infer_jit.lower(
                    self._params, self._stats, x, w
                ).compile()
                times[f"infer/{b}"] = round(time.monotonic() - tb, 4)
            if self.streaming:
                a = self.cfg.ica_args
                t = self.stream_chunk
                for b in self.stream_buckets:
                    tb = time.monotonic()
                    args = (
                        self._params, self._stats, self._table,
                        jnp.zeros((b,), jnp.int32),
                        jnp.zeros((b,), jnp.float32),
                        jnp.zeros(
                            (b, t, a.num_components, a.window_size),
                            jnp.float32,
                        ),
                        jnp.zeros((b, t), jnp.float32),
                        jnp.zeros((b,), jnp.float32),
                    )
                    self._exec[("stream", b)] = self._stream_jit.lower(
                        *args
                    ).compile()
                    times[f"stream/{b}"] = round(
                        time.monotonic() - tb, 4
                    )
            tb = time.monotonic()
            self._exec[("swap", 0)] = self._swap_jit.lower(
                *self._live
            ).compile()
            times["swap/0"] = round(time.monotonic() - tb, 4)
        self.warmup_seconds = round(time.monotonic() - t0, 4)
        # zero-compile proof: the jitted entries must gain NO cached programs
        # from here on (the request path runs only the stored executables —
        # any growth means a silent fallback traced). swap_fn is in the set
        # ON PURPOSE: the proof holds ACROSS params hot-swaps, N publishes
        # included.
        self._guard = CompileGuard(
            {"infer_fn": self._infer_jit, "stream_fn": self._stream_jit,
             "swap_fn": self._swap_jit},
            max_compiles=0, label="serving",
        )
        self._start_lanes()
        self._warm = True
        return times

    def _start_lanes(self) -> None:
        from .microbatch import Microbatcher

        self._infer_lane = Microbatcher(
            self._dispatch_infer, self.row_buckets,
            max_delay_ms=self._max_delay_ms, max_queue=self._max_queue,
            name="infer", on_dispatch=self._record_dispatch, bus=self.bus,
            labels=self._bus_labels,
        )
        self._stream_lane = None
        if self.streaming:
            self._stream_lane = Microbatcher(
                self._dispatch_stream, self.stream_buckets,
                rows_of=lambda req: 1,
                conflict_key=lambda req: req.session,
                max_delay_ms=self._max_delay_ms, max_queue=self._max_queue,
                name="stream", on_dispatch=self._record_dispatch,
                bus=self.bus, labels=self._bus_labels,
            )

    # -- request path (Compiled executables only) ------------------------

    def _record_dispatch(self, lane, batch, bucket, rows, depth) -> None:
        if self.sink is not None:
            self.sink.append({
                "kind": "dispatch", "lane": lane, "bucket": int(bucket),
                "rows": int(rows), "pad_rows": int(bucket - rows),
                "queue_depth": int(depth),
                "trace_ids": [r.trace_id for r in batch],
                **self._bus_labels,
            })

    def _finish(self, reqs, lane: str) -> None:
        now = time.monotonic()
        with self._lock:
            for r in reqs:
                self._latencies.append((lane, now - r._submit_t))
            self.stats["requests"] += len(reqs)
        for r in reqs:
            self.bus.observe(
                "serving_request_latency_ms", (now - r._submit_t) * 1e3,
                lane=lane, **self._bus_labels,
            )
        self.bus.counter(
            "serving_requests_total", len(reqs), lane=lane,
            **self._bus_labels,
        )

    def _dispatch_infer(self, reqs, bucket: int) -> None:
        """Pack collected requests into the bucket's padded batch and run its
        pre-compiled executable. Pad rows carry weight 0 — for batch-stat
        models (MSANNet) the mask keeps them out of the BatchNorm statistics,
        exactly like eval-plan padding."""
        x = np.zeros((bucket,) + self.sample_shape, np.float32)
        w = np.zeros((bucket,), np.float32)
        at = 0
        spans = []
        for r in reqs:
            n = len(r.rows)
            x[at:at + n] = r.rows
            w[at:at + n] = 1.0 if r.weights is None else r.weights
            spans.append((r, at, n))
            at += n
        params, stats = self._live
        with self.tracer.span("serve-infer", bucket=bucket, rows=at,
                              trace_ids=[r.trace_id for r in reqs]):
            probs = np.asarray(self._exec[("infer", bucket)](
                params, stats, x, w
            ))
        with self._lock:
            # mirror the dispatch payload for shadow-lane scoring (a small
            # ring; the arrays are already padded host copies)
            self._mirror.append((bucket, x, w))
            del self._mirror[:-self._mirror_cap]
        for r, lo, n in spans:
            r.future.set_result(probs[lo:lo + n])
        bad = _nonfinite_rows(probs[:at])
        with self._lock:
            self.stats["samples"] += at
            self.stats["nonfinite_rows"] += bad
        self._finish(reqs, "infer")

    def _dispatch_stream(self, reqs, bucket: int) -> None:
        """One streaming step over up to ``bucket`` sessions: resolve slots
        (assign/evict on the host table), run the chunk executable, rebind
        the donated carry table."""
        a = self.cfg.ica_args
        t = self.stream_chunk
        slot_ix = np.full((bucket,), self.sessions.trash_slot, np.int32)
        fresh = np.zeros((bucket,), np.float32)
        x = np.zeros(
            (bucket, t, a.num_components, a.window_size), np.float32
        )
        sv = np.zeros((bucket, t), np.float32)
        valid = np.zeros((bucket,), np.float32)
        for i, r in enumerate(reqs):
            with self._session_lock:
                slot, gen, is_fresh = self.sessions.resolve(r.session)
            r.slot, r.generation, r.fresh = slot, gen, is_fresh
            slot_ix[i] = slot
            fresh[i] = 1.0 if (is_fresh or r.fresh) else 0.0
            n = len(r.rows)
            x[i, :n] = r.rows
            sv[i, :n] = 1.0 if r.step_valid is None else r.step_valid
            valid[i] = 1.0
        params, stats = self._live
        with self.tracer.span("serve-stream", bucket=bucket, rows=len(reqs),
                              trace_ids=[r.trace_id for r in reqs]):
            probs, self._table = self._exec[("stream", bucket)](
                params, stats, self._table,
                slot_ix, fresh, x, sv, valid,
            )
            probs = np.asarray(probs)
        for i, r in enumerate(reqs):
            r.future.set_result(
                {"probs": probs[i], "session": r.session,
                 "generation": r.generation, "restarted": bool(r.fresh),
                 "trace_id": r.trace_id}
            )
        bad = _nonfinite_rows(probs[:len(reqs)])
        with self._lock:
            self.stats["samples"] += len(reqs)
            self.stats["stream_chunks"] += len(reqs)
            self.stats["nonfinite_rows"] += bad
        with self._session_lock:
            occupied, evictions = self.sessions.occupied, self.sessions.evictions
        self.bus.gauge(
            "serving_sessions_occupied", occupied, **self._bus_labels
        )
        self.bus.gauge(
            "serving_session_evictions", evictions, **self._bus_labels
        )
        self._finish(reqs, "stream")

    # -- public API ------------------------------------------------------

    def submit(self, rows, weights=None, trace_id=None, priority: int = 0,
               deadline_ms=None):
        """Batched inference: ``rows [n, ...sample_shape]`` → future of
        ``probs [n, C]``. ``weights`` masks rows (eval semantics);
        ``trace_id`` propagates a caller's request id into the dispatch
        row + span (auto-minted when absent; readable on the returned
        future's ``.trace_id``). ``priority`` (higher first) and
        ``deadline_ms`` (shed when staler than this at collection — the
        future then raises :class:`~.microbatch.RequestError`) feed the
        microbatcher's admission (r21)."""
        self._ensure_warm()
        rows = np.asarray(rows, np.float32)
        if rows.shape[1:] != self.sample_shape:
            raise ServingError(
                f"request rows shaped {rows.shape[1:]} but task "
                f"{self.cfg.task_id!r} serves {self.sample_shape}"
            )
        req = _Req(rows, weights=weights, trace_id=trace_id,
                   priority=priority, deadline_ms=deadline_ms)
        self._infer_lane.submit(req)
        return req.future

    def stream(self, session_id: str, windows, trace_id=None,
               priority: int = 0):
        """Streaming inference: feed ``windows [t, C, W]`` (the session's NEW
        timesteps) and get a future of the classification over everything
        the session has seen. Runs longer than one chunk are split into
        in-order chunk submissions (all sharing one ``trace_id``); the
        returned future is the LAST chunk's (the full-prefix answer).
        ``priority`` raises the chunks in the lane's admission order; there
        is deliberately NO deadline on stream chunks — shedding a middle
        chunk would silently drop windows from the carry, breaking the
        chunked == full-replay exactness contract."""
        self._ensure_warm()
        if not self.streaming:
            raise ServingError(
                "this checkpoint has no streaming lane (streaming needs a "
                "causal recurrent head: ICA-Classification with "
                "bidirectional=false — the reverse direction of a biLSTM "
                "reads the future, so no O(1) carry can serve it)"
            )
        windows = np.asarray(windows, np.float32)
        a = self.cfg.ica_args
        if windows.ndim != 3 or windows.shape[1:] != (
                a.num_components, a.window_size):
            raise ServingError(
                f"stream windows must be [t, {a.num_components}, "
                f"{a.window_size}], got {windows.shape}"
            )
        if len(windows) == 0:
            raise ServingError(
                "stream() needs at least one window (an empty chunk has "
                "nothing to advance the session with)"
            )
        from ..telemetry.tracer import new_trace_id
        from .microbatch import ChainedFuture

        trace_id = trace_id or new_trace_id()
        links = []
        for lo in range(0, len(windows), self.stream_chunk):
            req = _Req(windows[lo:lo + self.stream_chunk], session=session_id,
                       trace_id=trace_id, priority=priority)
            self._stream_lane.submit(req)
            links.append(req.future)
        # the chain surfaces ANY chunk's dispatch error — a failed middle
        # chunk must not be masked by a later chunk succeeding on a carry
        # that silently missed its windows
        if len(links) == 1:
            return links[0]
        chain = ChainedFuture(links)
        chain.trace_id = trace_id
        return chain

    def close_session(self, session_id: str) -> None:
        with self._session_lock:
            self.sessions.close(session_id)

    # -- params hot-swap (train-to-serve CD, serving/publish.py) ---------

    def _swap_shape_mismatch(self, new_params, new_stats) -> list:
        """Human-readable mismatches between a candidate weight tree and the
        live one (treedef + per-leaf shape/dtype). Executables are keyed by
        these shapes, so ANY mismatch means the candidate cannot ride the
        compiled set — the caller must refuse, never recompile."""
        import jax

        problems = []
        for what, new, cur in (
            ("params", new_params, self._live[0]),
            ("batch_stats", new_stats, self._live[1]),
        ):
            if (jax.tree_util.tree_structure(new)
                    != jax.tree_util.tree_structure(cur)):
                problems.append(f"{what}: tree structure differs")
                continue
            for n, c in zip(jax.tree.leaves(new), jax.tree.leaves(cur)):
                if (tuple(n.shape) != tuple(c.shape)
                        or np.dtype(n.dtype) != np.dtype(c.dtype)):
                    problems.append(
                        f"{what}: leaf {tuple(n.shape)}/{n.dtype} vs live "
                        f"{tuple(c.shape)}/{c.dtype}"
                    )
        return problems

    def weights(self) -> tuple:
        """The live ``(params, batch_stats)`` device arrays. A publish
        controller retains this tuple before a swap — it is the rollback
        target (the swap drops the engine's own reference)."""
        return self._live

    def swap_params(self, params, batch_stats=None) -> dict:
        """Install new weights with the pre-compiled donated graft: the
        candidate's buffers are device_put onto this engine's device and
        DONATED into the swap executable, whose outputs alias them in place
        (zero copy, zero compile — the warmup CompileGuard keeps counting).
        The engine takes ownership of the passed arrays if they already live
        on its device. Shape-keyed: any treedef/shape/dtype drift from the
        live weights raises :class:`ServingError` — a retrain that changed
        the architecture needs a new engine, not a swap. Returns
        ``{"pause_ms": ...}`` (the wall time requests could observe)."""
        import jax

        self._ensure_warm()
        new = (
            jax.device_put(params, self.device),
            jax.device_put(batch_stats or {}, self.device),
        )
        problems = self._swap_shape_mismatch(*new)
        if problems:
            raise ServingError(
                "hot-swap refused — candidate weights do not match the "
                "compiled executables' shapes (publish a same-architecture "
                "checkpoint, or stand up a new engine): "
                + "; ".join(problems)
            )
        t0 = time.monotonic()
        grafted = self._exec[("swap", 0)](*new)
        jax.block_until_ready(grafted)
        self._live = tuple(grafted)
        pause_ms = (time.monotonic() - t0) * 1e3
        with self._lock:
            self.stats["swaps"] += 1
        self.bus.counter("serving_swaps_total", **self._bus_labels)
        self.bus.observe(
            "serving_swap_pause_ms", pause_ms, **self._bus_labels
        )
        return {"pause_ms": round(pause_ms, 4)}

    def shadow_score(self, params, batch_stats=None) -> dict:
        """Score a publish candidate against MIRRORED live traffic: replay
        the last few batched dispatch payloads through the same stored
        executables with the candidate's weights (donation-free lane — the
        live state is untouched). Returns finiteness plus the max
        probability shift vs the live weights; the publish controller
        rejects non-finite candidates before any swap. Shape drift raises
        like :meth:`swap_params`."""
        import jax

        self._ensure_warm()
        cand = (
            jax.device_put(params, self.device),
            jax.device_put(batch_stats or {}, self.device),
        )
        problems = self._swap_shape_mismatch(*cand)
        if problems:
            raise ServingError(
                "shadow-score refused — candidate weights do not match the "
                "compiled executables' shapes: " + "; ".join(problems)
            )
        with self._lock:
            ring = list(self._mirror)
        if not ring:
            # no traffic mirrored yet (publish before first dispatch):
            # score on a zero payload at the smallest bucket — still proves
            # the candidate produces finite probabilities
            b = self.row_buckets[0]
            ring = [(
                b, np.zeros((b,) + self.sample_shape, np.float32),
                np.ones((b,), np.float32),
            )]
        live = self._live
        finite = True
        max_delta = 0.0
        rows = 0
        for bucket, x, w in ring:
            got = np.asarray(self._exec[("infer", bucket)](*cand, x, w))
            ref = np.asarray(self._exec[("infer", bucket)](*live, x, w))
            mask = np.asarray(w) > 0
            rows += int(mask.sum())
            if not np.isfinite(got[mask]).all():
                finite = False
            else:
                max_delta = max(
                    max_delta, float(np.abs(got[mask] - ref[mask]).max())
                )
        return {
            "batches": len(ring), "rows": rows, "finite": finite,
            "max_abs_delta": round(max_delta, 6),
        }

    def _ensure_warm(self) -> None:
        if not self._warm:
            raise ServingError("call warmup() before submitting requests")

    def drain(self, timeout: float = 30.0) -> None:
        """Block until both lanes' queues are empty (best effort — used by
        the request-script runner between phases)."""
        deadline = time.monotonic() + timeout
        lanes = [L for L in (self._infer_lane, self._stream_lane) if L]
        while time.monotonic() < deadline:
            if all(L.depth() == 0 for L in lanes):
                return
            time.sleep(0.002)

    # -- proofs + rollup -------------------------------------------------

    def compiles_after_warmup(self) -> dict:
        return self._guard.counts() if self._guard is not None else {}

    def assert_no_compiles(self) -> None:
        """The zero-compile proof: raises
        :class:`~..checks.sanitize.SanitizerViolation` if any jitted serving
        entry compiled a program since warmup."""
        if self._guard is not None:
            self._guard.check(context="serving request path")

    def health_probes(self) -> dict:
        """Per-subsystem readiness probes for the ``/healthz`` endpoint."""
        probes = {
            "warm": lambda: self._warm,
            "infer_lane": lambda: (
                self._warm and self._infer_lane._thread.is_alive()
            ),
        }
        if self.streaming:
            probes["stream_lane"] = lambda: (
                self._warm and self._stream_lane._thread.is_alive()
            )
        return probes

    def status(self) -> dict:
        """The live ``/statusz`` payload: a cheap subset of
        :meth:`summary` plus the served checkpoint's provenance (including
        any ``traces`` the daemon embedded in the checkpoint meta — the
        serve end of cross-process trace propagation)."""
        lanes = [
            L for L in (getattr(self, "_infer_lane", None),
                        getattr(self, "_stream_lane", None)) if L
        ]
        with self._session_lock:
            occupied = self.sessions.occupied if self.sessions else 0
        return {
            "task_id": self.cfg.task_id,
            "warm": self._warm,
            "streaming": self.streaming,
            "requests": self.stats["requests"],
            "samples": self.stats["samples"],
            "swaps": self.stats["swaps"],
            "stream_sessions": occupied,
            "queue_depth": sum(L.depth() for L in lanes),
            "deferrals": sum(L.stats["deferrals"] for L in lanes),
            "shed": sum(L.stats["shed"] for L in lanes),
            "compiles_after_warmup": sum(
                self.compiles_after_warmup().values()
            ),
            "checkpoint_epoch": self.meta.get("epoch"),
            "checkpoint_traces": self.meta.get("traces") or {},
        }

    def summary(self) -> dict:
        with self._lock:
            lats = sorted(s for _, s in self._latencies)
        with self._session_lock:
            occupied = self.sessions.occupied if self.sessions else 0
            evictions = self.sessions.evictions if self.sessions else 0
        lanes = [
            L for L in (getattr(self, "_infer_lane", None),
                        getattr(self, "_stream_lane", None)) if L
        ]
        rows = sum(L.stats["rows"] for L in lanes)
        pads = sum(L.stats["pad_rows"] for L in lanes)
        disp = sum(L.stats["dispatches"] for L in lanes)
        hits = sum(L.stats["bucket_hits"] for L in lanes)
        elapsed = max(time.monotonic() - self._t0, 1e-9)

        def pct(p):
            if not lats:
                return None
            return round(
                1e3 * lats[min(int(p * len(lats)), len(lats) - 1)], 4
            )

        return {
            "kind": "serve_summary",
            "task_id": self.cfg.task_id,
            "requests": self.stats["requests"],
            "samples": self.stats["samples"],
            "stream_chunks": self.stats["stream_chunks"],
            "dispatches": disp,
            "latency_ms_p50": pct(0.50),
            "latency_ms_p95": pct(0.95),
            "latency_ms_p99": pct(0.99),
            "requests_per_s": round(self.stats["requests"] / elapsed, 2),
            "samples_per_s": round(self.stats["samples"] / elapsed, 2),
            "pad_waste_pct": round(100.0 * pads / max(rows + pads, 1), 2),
            "bucket_hit_rate": round(hits / max(disp, 1), 4),
            "max_queue_depth": max(
                (L.stats["max_queue_depth"] for L in lanes), default=0
            ),
            "deferrals": sum(L.stats["deferrals"] for L in lanes),
            "shed": sum(L.stats["shed"] for L in lanes),
            "swaps": self.stats["swaps"],
            "nonfinite_rows": self.stats["nonfinite_rows"],
            **self._bus_labels,
            "checkpoint_traces": self.meta.get("traces") or {},
            "warmup_seconds": self.warmup_seconds,
            "buckets": {
                "infer": list(self.row_buckets),
                "stream": list(self.stream_buckets) if self.streaming else [],
                "stream_chunk": self.stream_chunk if self.streaming else 0,
            },
            "stream_sessions": occupied,
            "stream_evictions": evictions,
            "compiles_after_warmup": sum(self.compiles_after_warmup().values()),
        }

    def close(self) -> dict:
        """Stop the lanes, verify the zero-compile invariant, emit the
        serve_summary telemetry row; returns the summary."""
        for lane in (getattr(self, "_infer_lane", None),
                     getattr(self, "_stream_lane", None)):
            if lane is not None:
                lane.close()
        summary = self.summary()
        if self.sink is not None:
            self.sink.append(summary)
            if self._close_sink:
                # a fleet shares one sink across replicas and closes it
                # once itself (close_sink=False per replica)
                self.sink.close()
        self.assert_no_compiles()
        return summary

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        self.close()
        return False
