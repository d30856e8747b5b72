"""Double-buffered epoch-plan prefetch — the host never blocks the device.

With the device-resident pipeline (trainer/steps.py ``pipeline="device"``)
the only per-epoch host work is building the compact int32 index plan
(data/batching.py) and dispatching its KB-sized transfer. This module moves
that work off the critical path: a single background thread builds epoch
``N+1``'s plan (and dispatches its device put) while epoch ``N``'s fused XLA
dispatch runs — the Podracer split of host-side orchestration from
device-side compute (PAPERS.md).

Plans are keyed by VIRTUAL site throughout: the ``[S, steps, B]`` grid is
indexed by global site id regardless of the mesh's pack factor
(parallel/mesh.py site packing) — ``P(site)`` placement hands each device
its contiguous ``[K, steps, B]`` block, so a pack-factor change never
touches the planner.

Design constraints honored here:

- plans are pure functions of ``(epoch, global round window)`` — the builder
  needs NO feedback from the training state, so prefetching never changes
  results (resume included: the round window extrapolates linearly from the
  resume point exactly as the epoch program advances it);
- a bounded queue (depth 1) keeps at most one epoch in flight — double
  buffering, not an unbounded plan pile;
- shutdown is cooperative and prompt: ``close()`` unblocks the builder,
  joins the thread, and is safe to call twice — the trainer calls it in a
  ``finally`` so a ``Preempted`` (SIGTERM / FaultPlan kill) never leaks a
  thread into the resumed run;
- a builder crash re-raises in the consumer (``get``), not silently in the
  thread.
"""

from __future__ import annotations

import queue
import threading
import time

from ..telemetry import scopes
from ..telemetry.tracer import PROFILER_TRACER
from .logs import log_warning


class EpochPlanPrefetcher:
    """Build epoch plans one epoch ahead on a background thread.

    ``build(epoch)`` must return the (already device-dispatched) plan payload
    for that epoch. Epochs are consumed strictly in order ``first..last`` via
    :meth:`get`; a mismatch (defensive — the trainer consumes sequentially)
    falls back to building synchronously.

    Telemetry: the prefetcher keeps its own counters — time the consumer
    spent BLOCKED waiting on the builder (``stall_s``: the double-buffering
    failure signal), gets served, inline-build fallbacks, and the summed
    queue depth at get time — surfaced via :meth:`stats` into the fit's
    ``metrics.jsonl`` summary row (telemetry/sink.py). ``stall_s`` is a sum
    over the fit on the host's clock; beside it every :meth:`get` runs under
    the ``plan-wait`` span (``scopes.PLAN_WAIT``) of ``tracer``, which by
    default only annotates: in a ``jax.profiler`` session each wait lies, with
    its ``epoch``, on the clock of the device's operations, so a device idle
    gap can be laid over it.
    """

    def __init__(self, build, first_epoch: int, last_epoch: int, *,
                 tracer=PROFILER_TRACER):
        self._build = build
        self._tracer = tracer
        self._queue: queue.Queue = queue.Queue(maxsize=1)
        self._stop = threading.Event()
        self._error: BaseException | None = None
        self._stall_s = 0.0
        self._gets = 0
        self._inline_builds = 0
        self._depth_sum = 0
        self._thread = threading.Thread(
            target=self._run, args=(first_epoch, last_epoch),
            name="dinunet-epoch-prefetch", daemon=True,
        )
        self._thread.start()

    # -- producer (background thread) ------------------------------------

    def _run(self, first: int, last: int) -> None:
        try:
            for epoch in range(first, last + 1):
                if self._stop.is_set():
                    return
                payload = self._build(epoch)
                while not self._stop.is_set():
                    try:
                        self._queue.put((epoch, payload), timeout=0.1)
                        break
                    except queue.Full:
                        continue
        except Exception as exc:
            # surface in the consumer: stored for re-raise from get(); the
            # warning covers the case where the consumer never calls get()
            # again (e.g. it is mid-epoch and about to be preempted)
            self._error = exc
            log_warning(f"[warn] epoch-plan prefetch thread failed: {exc!r}")

    # -- consumer (training loop) ----------------------------------------

    def get(self, epoch: int):
        """The prefetched payload for ``epoch`` (blocking briefly if the
        builder is still working on it). Re-raises a builder crash."""
        with self._tracer.span(scopes.PLAN_WAIT, epoch=epoch):
            return self._get(epoch)

    def _get(self, epoch: int):
        t0 = time.perf_counter()
        self._gets += 1
        self._depth_sum += self._queue.qsize()
        try:
            while True:
                if self._error is not None:
                    err, self._error = self._error, None
                    self.close()
                    raise err
                if not self._thread.is_alive() and self._queue.empty():
                    # builder finished (or died after its warning): build inline
                    self._inline_builds += 1
                    return self._build(epoch)
                try:
                    got_epoch, payload = self._queue.get(timeout=0.1)
                except queue.Empty:
                    continue
                if got_epoch == epoch:
                    return payload
                # out-of-order consumption (defensive): drop and build inline
                self._inline_builds += 1
                return self._build(epoch)
        finally:
            self._stall_s += time.perf_counter() - t0

    def stats(self) -> dict:
        """Counters for the telemetry summary row: consumer-blocked seconds,
        gets served, inline-build fallbacks, mean queue depth at get."""
        return {
            "stall_s": round(self._stall_s, 6),
            "gets": self._gets,
            "inline_builds": self._inline_builds,
            "mean_queue_depth": round(
                self._depth_sum / max(self._gets, 1), 3
            ),
        }

    def close(self) -> None:
        """Stop the builder and join the thread. Idempotent; called from the
        trainer's ``finally`` so early stopping / ``Preempted`` / crashes all
        leave zero threads behind."""
        self._stop.set()
        # drain so a producer blocked on put() observes the stop event
        while True:
            try:
                self._queue.get_nowait()
            except queue.Empty:
                break
        if self._thread.is_alive():
            self._thread.join(timeout=5.0)
