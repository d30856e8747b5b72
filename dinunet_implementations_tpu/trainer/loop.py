"""Federated training driver — the capability fold-in of COINNLocal +
COINNRemote + COINNTrainer (SURVEY.md §2.3, §3.2).

One :class:`FederatedTrainer` drives, per fold:

- optional pretrain warm start on the largest site (``pretrain_args``;
  ``compspec.json:120-127`` "Use the site with maximum data to pre-train
  locally as starting point") — realized in SPMD by zero-weighting every other
  site's batches, so the same compiled epoch program serves both phases;
- the epoch loop: one jitted SPMD epoch per call (trainer/steps.py), metric
  validation every ``validation_epochs``, early stopping on
  ``monitor_metric``/``metric_direction`` with ``patience``
  (``local.py:34-36``), best-state tracking + checkpoint;
- final test on the best state; ``logs.json`` / ``test_metrics.csv`` /
  zipped global results, byte-compatible with the reference notebooks
  (trainer/logs.py).
"""

from __future__ import annotations

import dataclasses
import os
import time

import jax
import jax.numpy as jnp
import numpy as np

from ..core.config import TrainConfig
from ..data.api import SiteArrays, stack_site_inventory
from ..data.batching import (
    epoch_steps,
    plan_epoch,
    plan_epoch_positions,
    plan_eval,
)
from ..engines import make_engine
from .checkpoint import (
    load_checkpoint,
    load_eval_state,
    load_params,
    save_checkpoint,
)
from ..robustness.faults import poison_inputs
from ..robustness.health import health_summary
from ..robustness.preemption import Preempted, PreemptionGuard
from ..telemetry import scopes
from ..telemetry.tracer import PROFILER_TRACER, SpanTracer, duration
from .logs import (
    fold_dir,
    health_log_fields,
    log_info,
    log_warning,
    privacy_log_fields,
    telemetry_log_fields,
    write_logs_json,
    write_test_metrics_csv,
    zip_global_results,
)
from .metrics import (
    Averages,
    ClassificationMetrics,
    MulticlassMetrics,
    NoClassMetrics,
    is_improvement,
)
from .prefetch import EpochPlanPrefetcher
from .steps import (
    FederatedTask,
    TrainState,
    init_train_state,
    make_eval_fn,
    make_optimizer,
    make_train_epoch_fn,
)


class FederatedTrainer:
    def __init__(self, cfg: TrainConfig, model, mesh=None, out_dir: str | None = None,
                 fault_plan=None, bus=None, attack_plan=None):
        """``mesh=None`` folds all sites onto the local device via vmap (one
        chip simulating N sites); a mesh with a ``site`` axis runs the sites
        across its members — one per device slice, or PACKED ``K = S /
        mesh_sites`` per device with two-level aggregation when there are
        more sites than mesh members (parallel/mesh.py packed_site_mesh,
        trainer/steps.py). ``fault_plan`` is an
        optional :class:`~..robustness.faults.FaultPlan` — deterministic
        chaos injection (site drops / NaN poisoning / kill-at-round) threaded
        through the data layer and epoch inputs; masks are traced arrays, so
        injecting faults never changes the compiled program. ``attack_plan``
        is the hostile twin (robustness/attacks.py AttackPlan, r17):
        byzantine gradient transforms injected as a traced ``[S, rounds]``
        code mask — composes with the fault plan; defenses ride
        ``cfg.robust_agg``."""
        self.cfg = cfg
        self.mesh = mesh
        self.out_dir = out_dir
        self.fault_plan = fault_plan
        self.attack_plan = attack_plan
        self.task = FederatedTask(model)
        task_args = dataclasses.asdict(cfg.task_args())
        self.engine = make_engine(
            cfg.agg_engine, precision_bits=cfg.precision_bits, seed=cfg.seed,
            wire_quant=cfg.wire_quant, wire_stochastic=cfg.wire_stochastic,
            robust_agg=cfg.robust_agg,
            robust_trim_frac=cfg.robust_trim_frac,
            robust_clip_mult=cfg.robust_clip_mult,
            dcn_wire_quant=cfg.dcn_wire_quant,
            secure_agg=cfg.secure_agg,
            secure_agg_seed=cfg.secure_agg_seed,
            **task_args
        )
        # privacy plane (r20, privacy/): validate the DP knobs up front
        # (noise without a clip is rejected — no sensitivity, no guarantee)
        # and open the host-side RDP ledger when the mechanism is noisy.
        # The accountant lives on the trainer so both the batch fit and the
        # daemon's epoch loop step ONE ledger; _fit_impl round-trips it
        # through the checkpoint meta so a resumed fit continues ε
        # accumulation exactly (no double count, no reset).
        from ..privacy import RdpAccountant, dp_enabled

        self._dp_on = dp_enabled(cfg.dp_clip, cfg.dp_noise_multiplier)
        self._dp_noisy = self._dp_on and cfg.dp_noise_multiplier > 0.0
        if not 0.0 < cfg.dp_delta < 1.0:
            raise ValueError(f"dp_delta must be in (0, 1), got {cfg.dp_delta}")
        if cfg.dp_epsilon_budget < 0.0:
            raise ValueError(
                f"dp_epsilon_budget must be >= 0, got {cfg.dp_epsilon_budget}"
            )
        if cfg.dp_epsilon_budget > 0.0 and not self._dp_noisy:
            raise ValueError(
                "dp_epsilon_budget needs dp_noise_multiplier > 0 — a "
                "noiseless mechanism never exhausts any finite ε budget"
            )
        self.dp_accountant = RdpAccountant() if self._dp_noisy else None
        self._dp_epsilon = None  # last reported ε (None = dp off/noiseless)
        # modeled per-round inter-slice (DCN) bytes for the bus rollup —
        # filled at fit time once the site count / pack factor are known;
        # stays 0.0 on single-slice meshes (r18, telemetry/metrics.py)
        self._dcn_bytes_round = 0.0
        self.optimizer = make_optimizer(cfg.optimizer, cfg.learning_rate)
        if cfg.pipeline not in ("device", "host"):
            raise ValueError(
                f"cfg.pipeline must be 'device' or 'host', got {cfg.pipeline!r}"
            )
        # device pipeline (the default): inventory uploaded once per fit,
        # epochs driven by compact index plans gathered on-device; the carried
        # state is donated to the epoch program (see run_epoch/_snapshot)
        self._pipeline = cfg.pipeline
        self._donate = bool(cfg.donate_epoch_state)
        from ..core.jaxcompat import enable_compile_cache

        enable_compile_cache(cfg.compile_cache_dir)
        # unified telemetry (telemetry/): span tracer + on-device round
        # metrics + manifest/metrics artifacts. Off = a tracer that records
        # nothing and a telemetry-free epoch program (bitwise-equal to the
        # pre-telemetry one). The loop's host spans (telemetry/scopes.py,
        # host half) do not hang on the switch: either tracer writes them
        # into a running jax.profiler session.
        if cfg.telemetry not in ("on", "off"):
            raise ValueError(
                f"cfg.telemetry must be 'on' or 'off', got {cfg.telemetry!r}"
            )
        self._telemetry_on = cfg.telemetry == "on"
        if cfg.xprof_dir and cfg.profile_dir:
            raise ValueError(
                "profile_dir (whole-fit trace) and xprof_dir (windowed "
                "capture) are mutually exclusive — jax.profiler supports one "
                "active trace"
            )
        self.tracer = (
            SpanTracer(annotate=True) if self._telemetry_on else PROFILER_TRACER
        )
        # live metrics (telemetry/bus.py): published into the process-wide
        # bus when telemetry is on (the /statusz exporter's read side), the
        # NULL bus otherwise. Publishing is host-side bookkeeping over
        # values the loop already fetched — it never adds a device sync and
        # never touches the traced program (bus=NULL keeps the epoch
        # program bitwise-identical; the S005 identity gate covers it).
        if bus is not None:
            self.bus = bus
        else:
            from ..telemetry.bus import NULL_BUS, global_bus

            self.bus = global_bus() if self._telemetry_on else NULL_BUS
        self.epoch_fn = make_train_epoch_fn(
            self.task, self.engine, self.optimizer, mesh, cfg.local_iterations,
            rounds_scan_xs=cfg.rounds_scan_xs,
            quarantine_rounds=cfg.quarantine_rounds,
            pipeline=self._pipeline,
            donate_state=self._donate,
            telemetry=self._telemetry_on,
            staleness_bound=cfg.staleness_bound,
            staleness_decay=cfg.staleness_decay,
            overlap_rounds=cfg.overlap_rounds,
            attack_plan=attack_plan,
            robust_agg=cfg.robust_agg,
            reputation_z=cfg.reputation_z,
            reputation_rounds=cfg.reputation_rounds,
            min_slices=cfg.min_slices,
            dp_clip=cfg.dp_clip,
            dp_noise_multiplier=cfg.dp_noise_multiplier,
            dp_seed=cfg.dp_seed,
            personalize=tuple(cfg.personalize),
        )
        self.eval_fn = make_eval_fn(
            self.task, mesh, personalize=tuple(cfg.personalize)
        )
        self._inventory = None  # device-resident site inventory, one per fit
        self._inventory_src = None  # content fingerprint it was built from
        # ship inputs to the device pre-cast to the model's compute dtype
        # (e.g. bf16): the model casts them anyway, and feeding f32 made XLA
        # convert + layout-copy the whole epoch input on-device every epoch
        # (profiled ~10% of the 32-site ICA bench epoch). Labels/weights
        # stay full precision.
        self._input_dtype = getattr(model, "compute_dtype", None) or None
        self._cache: dict = {}  # duration bookkeeping, reference-keyed
        self._last_transfer_bytes = 0  # per-epoch host→device traffic
        # -- elastic-rounds hooks (runner/fed_runner.py FedDaemon, r13) --
        # [S] occupancy mask from the membership table: folded into every
        # epoch's liveness mask (an unoccupied slot is a site whose update
        # never arrives). None = classic batch-job semantics. Setting it
        # forces the liveness input to be FED even without a FaultPlan, so
        # the daemon runs one compiled program whether or not faults are
        # also injected.
        self.membership_mask = None
        # pinned per-epoch step-grid height: the daemon sets this so churn
        # (a bigger site joining) can never change the plan's [S, steps, B]
        # shape and force a retrace. None = derive steps from the site set.
        self.fixed_steps = None
        # pinned inventory row budget ([S, N_max, ...] grid height), same
        # retrace-proofing for the device-resident inventory upload
        self.fixed_inventory_rows = None
        # [num_slices] scheduler grant mask (runner/scheduler.py, r22): a
        # slice the fleet scheduler has not granted to this fit never
        # arrives — folded into the r19 slice-liveness window exactly like
        # membership_mask folds into site liveness. Setting it forces the
        # slice-liveness input to be FED even without a FaultPlan, so one
        # compiled program covers every grow/shrink/preempt/restore grant
        # flip (CompileGuard-assertable). None = no scheduler, r19 behavior.
        self.slice_grant = None

    def _coordinator(self) -> bool:
        """Multi-host runs: only process 0 writes logs/checkpoints (every
        process computes the identical replicated results; concurrent writers
        to a shared output dir would race)."""
        return jax.process_index() == 0

    def _put_batch(self, fb):
        """Device-side epoch arrays: floating inputs pre-cast to the compute
        dtype; on a mesh, committed ``P(site)`` arrays (multi-host aware)."""
        from ..parallel.distributed import input_cast_dtype, put_site_batch

        dtype = input_cast_dtype(fb.inputs, self._input_dtype)
        if self.mesh is not None:
            return (
                put_site_batch(self.mesh, fb.inputs, dtype),
                put_site_batch(self.mesh, fb.labels),
                put_site_batch(self.mesh, fb.weights),
            )
        return (
            jnp.asarray(fb.inputs, dtype=dtype),
            jnp.asarray(fb.labels),
            jnp.asarray(fb.weights),
        )

    # -- building blocks -------------------------------------------------

    def init_state(self, sample_x, num_sites: int | None = None) -> TrainState:
        rng = jax.random.PRNGKey(self.cfg.seed)
        n = num_sites or getattr(self, "_num_sites", 1)
        state = init_train_state(
            self.task, self.engine, self.optimizer, rng, sample_x,
            num_sites=n,
            telemetry=self._telemetry_on,
            staleness_bound=self.cfg.staleness_bound,
            overlap_rounds=self.cfg.overlap_rounds,
            reputation=self.cfg.robust_agg != "none",
            personalize=tuple(self.cfg.personalize),
        )
        from ..parallel.mesh import SITE_AXIS, pack_factor, slice_count

        if self.mesh is not None and slice_count(self.mesh) > 1:
            # per-tier wire accounting for the bus rollup (r18): the modeled
            # per-slice DCN payload per round from the engine's own model,
            # at this fit's pack factor — a static figure the sliced
            # semantic cells verify against the traced program
            from ..telemetry.metrics import dcn_bytes_of

            k = pack_factor(self.mesh, n)
            self._dcn_bytes_round = dcn_bytes_of(
                self.engine, state.params, pack=k,
                sites_per_slice=k * dict(self.mesh.shape)[SITE_AXIS],
                slices=slice_count(self.mesh),
            )
        return self._place_state(state)

    def _place_state(self, state: TrainState) -> TrainState:
        """Commit a host-built state to the mesh's steady-state sharding (the
        one the compiled epoch emits). Freshly-initialized / checkpoint-
        restored states are otherwise uncommitted, and the first epoch_fn
        call after init or resume would compile a SECOND program for the
        uncommitted layout — one silent warmup recompile per fit. Single-
        process meshes only: multi-host arrays are fed per-process
        (put_site_batch) and keep the legacy behavior."""
        from ..parallel.distributed import spans_processes
        from .steps import _state_specs

        if self.mesh is None or spans_processes(self.mesh):
            return state
        from jax.sharding import NamedSharding

        from ..parallel.mesh import site_axis_of

        return jax.tree.map(
            lambda a, spec: jax.device_put(a, NamedSharding(self.mesh, spec)),
            state, _state_specs(state, site_axis_of(self.mesh)),
        )

    def _put_live(self, live):
        """Ship a ``[S, rounds]`` liveness mask like the epoch batches."""
        if live is None:
            return None
        if self.mesh is not None:
            from ..parallel.distributed import put_site_batch

            return put_site_batch(self.mesh, live)
        return jnp.asarray(live)

    def _snapshot(self, state):
        """An independent copy of a state's buffers. With
        ``cfg.donate_epoch_state`` the NEXT epoch_fn call consumes (donates)
        its input state's buffers in place — so any state kept past that
        call (best-state tracking) must be snapshotted, never aliased."""
        if not self._donate:
            return state
        return jax.tree.map(jnp.copy, state)

    def _ensure_inventory(self, train_sites, epoch=None):
        """Device-resident inventory: uploaded once per fit in its RESIDENT
        FORM (data/api.py SiteInventory: ``[S, rows + 1, *stored_sample_shape]``
        — every site's subjects, then one all-zero row that the plan's ``-1``
        padding slots gather, each sample with its narrow trailing dimensions
        merged and its rows padded to whole tiles), inputs pre-cast to the
        compute dtype and every pad element written as zero at placement
        (parallel/distributed.py put_site_inventory). That form is what the
        round's gather writes and the model's first contraction reads, so a
        round's batch is one row gather (trainer/steps.py _gather_batch).
        Keyed by a content fingerprint
        (per-site array identities + sizes), not list identity, so a caller
        rebuilding its site LIST per run_epoch call (``list(sites)``) still
        reuses the resident upload — re-uploading per epoch would silently
        reinstate the dataset-sized transfer this pipeline removes."""
        key = tuple(
            (id(s.inputs), id(s.labels), len(s)) for s in train_sites
        )
        if self._inventory is None or self._inventory_src != key:
            from ..parallel.distributed import put_site_inventory

            with self.tracer.span(scopes.INVENTORY_UPLOAD, epoch=epoch):
                self._inventory = put_site_inventory(
                    self.mesh,
                    stack_site_inventory(
                        train_sites, self.fixed_inventory_rows
                    ),
                    self._input_dtype,
                )
            self._inventory_src = key
        return self._inventory

    def _build_epoch_payload(self, train_sites, epoch: int, batch_size: int,
                             round0: int):
        """One epoch's device-pipeline inputs: the compact index plan plus the
        FaultPlan masks for its global round window — the complete per-epoch
        host→device transfer (index-plan bytes, not dataset bytes). Pure
        function of ``(epoch, round0)``, so the prefetch thread can build
        epoch N+1 while epoch N runs without changing results (the tracer's
        ``plan-build`` spans land on whichever thread ran the build — the
        prefetch thread in steady state)."""
        from ..robustness.faults import fault_window

        with self.tracer.span(scopes.PLAN_BUILD, epoch=epoch):
            plan = plan_epoch_positions(
                train_sites, batch_size,
                seed=self.cfg.seed * 100003 + epoch, pad_mode="wrap",
                steps=self.fixed_steps,
            )
            rounds = plan.steps // max(self.cfg.local_iterations, 1)
            live, nan_mask = fault_window(
                self.fault_plan, plan.num_sites, round0, rounds
            )
            live = self._membership_live(live, plan.num_sites, rounds)
            # the NaN gate is fed whenever the PLAN carries nan_at (a
            # fit-static property), not only in windows that poison — the
            # compiled program must not change between epochs
            poison = (
                nan_mask.astype(np.float32)
                if nan_mask is not None and self.fault_plan.nan_at else None
            )
            # hostile-site attack codes for this window (r17,
            # robustness/attacks.py) — fed whenever the plan attacks at all
            # (fit-static), same one-program reasoning as the NaN gate
            from ..robustness.attacks import attack_window

            attack = attack_window(
                self.attack_plan, plan.num_sites, round0, rounds
            )
            # slice-tier faults (r19): the [num_slices, rounds] whole-slice
            # mask for this window — None off sliced meshes / slice-clean
            # plans, so the r18 program is untouched (S005)
            slice_live = self._slice_window(round0, rounds)
            from ..parallel.distributed import put_epoch_plan

            return put_epoch_plan(
                self.mesh, plan.positions, live, poison, attack, slice_live
            )

    def _slice_window(self, round0: int, rounds: int):
        """The FaultPlan's slice-liveness window for this epoch (r19,
        robustness/faults.py): ``[num_slices, rounds]`` or None. Kills are
        rendered into the mask only on single-process emulation — under the
        supervised multi-process runner they are REAL process deaths
        (runner/dcn_worker.py), and masking them too would keep a restarted
        slice dead forever. A scheduler slice grant (``slice_grant``, r22)
        multiplies in — an ungranted slice looks exactly like a dead one
        (renormalized aggregation, min_slices quorum), and forces the mask
        into existence so grant flips share ONE compiled form with fault
        windows."""
        from ..parallel.mesh import slice_count

        n_sl = slice_count(self.mesh)
        if n_sl <= 1 or (self.fault_plan is None and self.slice_grant is None):
            return None
        win = None
        if self.fault_plan is not None:
            from ..parallel.distributed import spans_processes
            from ..robustness.faults import slice_fault_window

            win = slice_fault_window(
                self.fault_plan, n_sl, round0, rounds,
                include_kills=not spans_processes(self.mesh),
            )
        if self.slice_grant is not None:
            grant = np.asarray(self.slice_grant, np.float32)[:n_sl, None]
            if win is None:
                win = np.broadcast_to(grant, (n_sl, rounds)).copy()
            else:
                win = win * grant
        return win

    def _publish_slice_liveness(self, slice_live) -> None:
        """Per-slice liveness gauges for the live bus (r19): how many of
        this epoch's rounds each slice is scheduled live — the /statusz
        surface for "which slice is the chaos plan (or a supervisor-marked
        death) taking out". Host-side values, no device sync of
        consequence (the mask is tiny and replicated)."""
        if slice_live is None or not self._telemetry_on:
            return
        rows = np.asarray(slice_live)
        for sl_i in range(rows.shape[0]):
            self.bus.gauge(
                "train_slice_live_rounds", float(rows[sl_i].sum()),
                slice=str(sl_i),
            )

    def _membership_live(self, live, num_sites: int, rounds: int):
        """Fold the membership occupancy mask (FedDaemon, r13) into an
        epoch's ``[S, rounds]`` liveness mask: an unoccupied slot never
        arrives. Forces a mask into existence when membership is elastic —
        the daemon's epoch program always takes the liveness input, so churn
        and fault patterns share ONE compiled form."""
        if self.membership_mask is None:
            return live
        occ = np.asarray(self.membership_mask, np.float32)[:num_sites, None]
        if live is None:
            return np.broadcast_to(occ, (num_sites, rounds)).copy()
        return live * occ

    def run_epoch(self, state, train_sites, epoch: int, batch_size=None,
                  plan=None):
        """One training epoch. Device pipeline: gathers batches on-device
        from the resident inventory, driven by ``plan`` (a prefetched
        ``_build_epoch_payload`` result; built inline when None). Host
        pipeline: materializes and ships the dense epoch tensor."""
        if self._pipeline == "device":
            with self.tracer.span(scopes.EPOCH_INPUTS, epoch=epoch):
                if plan is None:
                    plan = self._build_epoch_payload(
                        train_sites, epoch, batch_size or self.cfg.batch_size,
                        round0=int(state.round),
                    )
                idx, live, poison, attack, slice_live = plan
                inv_x, inv_y = self._ensure_inventory(train_sites, epoch)
                # the device pipeline's ENTIRE per-epoch host→device traffic
                self._last_transfer_bytes = int(sum(
                    a.nbytes for a in (idx, live, poison, attack, slice_live)
                    if a is not None
                ))
                self._publish_slice_liveness(slice_live)
            with self.tracer.span(scopes.EPOCH_DISPATCH, epoch=epoch):
                state, losses = self.epoch_fn(
                    state, inv_x, inv_y, idx, live, poison, attack, slice_live
                )
            return state, self._fetch_and_account(
                train_sites, losses, epoch, batch_size
            )
        with self.tracer.span(scopes.EPOCH_INPUTS, epoch=epoch):
            batch, live_dev, attack_dev, slice_dev = self._host_epoch_inputs(
                state, train_sites, epoch, batch_size
            )
        with self.tracer.span(scopes.EPOCH_DISPATCH, epoch=epoch):
            state, losses = self.epoch_fn(
                state, *batch, live_dev, attack_dev, slice_dev
            )
        return state, self._fetch_and_account(
            train_sites, losses, epoch, batch_size
        )

    def _fetch_and_account(self, train_sites, losses, epoch: int, batch_size):
        """The end of an epoch on the host: the loss fetch (the wait for the
        device, then the copy), then the epoch's accounting, each under its
        span."""
        with self.tracer.span(scopes.LOSS_FETCH, epoch=epoch):
            losses = np.asarray(losses)
        with self.tracer.span(scopes.EPOCH_ACCOUNT, epoch=epoch):
            return self._account_epoch(train_sites, losses, batch_size)

    def _host_epoch_inputs(self, state, train_sites, epoch: int, batch_size):
        """The host pipeline's epoch inputs, placed: ``(batch, live, attack,
        slice_live)`` — the dense epoch tensor and the fault, attack and
        slice masks of its round window."""
        fb = plan_epoch(
            train_sites,
            batch_size or self.cfg.batch_size,
            seed=self.cfg.seed * 100003 + epoch,
            pad_mode="wrap",
            steps=self.fixed_steps,
        )
        # deterministic chaos: masks/poison are pure functions of the plan
        # and the GLOBAL round window (robustness/faults.py fault_window —
        # shared with the device path), so resume replays the same fault
        # pattern the uninterrupted run saw
        from ..robustness.faults import fault_window

        live = nan_mask = None
        if self.fault_plan is not None and self.fault_plan.injects_faults():
            # (the injects_faults gate also keeps the int(state.round) fetch
            # — a device sync — off the clean path)
            rounds = fb.steps // max(self.cfg.local_iterations, 1)
            live, nan_mask = fault_window(
                self.fault_plan, fb.num_sites, int(state.round), rounds
            )
        if self.membership_mask is not None:
            live = self._membership_live(
                live, fb.num_sites,
                fb.steps // max(self.cfg.local_iterations, 1),
            )
        if nan_mask is not None and nan_mask.any():
            # data-layer injection: real NaN inputs
            fb = dataclasses.replace(
                fb,
                inputs=poison_inputs(
                    fb.inputs, nan_mask, self.cfg.local_iterations
                ),
            )
        # hostile-site attack codes (r17): a traced [S, rounds] input like
        # the liveness mask, windowed on the same global round counter
        attack = None
        if self.attack_plan is not None and self.attack_plan.injects_attacks():
            from ..robustness.attacks import attack_window

            attack = attack_window(
                self.attack_plan, fb.num_sites, int(state.round),
                fb.steps // max(self.cfg.local_iterations, 1),
            )
        # slice-tier faults (r19): the whole-slice mask, windowed on the
        # same global round counter as the site mask
        slice_live = self._slice_window(
            int(state.round), fb.steps // max(self.cfg.local_iterations, 1)
        ) if (self.fault_plan is not None
              or self.slice_grant is not None) else None
        batch = self._put_batch(fb)
        live_dev = self._put_live(live)
        attack_dev = self._put_live(attack)
        slice_dev = None
        if slice_live is not None:
            from ..parallel.distributed import put_replicated

            slice_dev = put_replicated(self.mesh, slice_live)
        self._last_transfer_bytes = int(
            sum(a.nbytes for a in batch)
            + sum(a.nbytes for a in (live_dev, attack_dev, slice_dev)
                  if a is not None)
        )
        self._publish_slice_liveness(slice_live)
        return batch, live_dev, attack_dev, slice_dev

    def _account_epoch(self, train_sites, losses, batch_size=None):
        """Step the RDP ledger by this epoch's executed rounds and publish
        ε (r20, privacy/accounting.py) — run_epoch is the one place both
        the batch fit and the daemon's serve loop train an epoch, so both
        surfaces share ONE ledger. The conversion runs host-side on values
        the loop already has; no device sync."""
        if self.dp_accountant is None:
            return losses
        from ..privacy import effective_noise_multiplier, sampling_fraction

        q = sampling_fraction(
            batch_size or self.cfg.batch_size, self.cfg.local_iterations,
            [len(s) for s in train_sites],
        )
        # clip-of-mean sensitivity is 2C, not C — compose conservatively
        # at σ/2 (privacy/accounting.py MEAN_CLIP_SENSITIVITY_FACTOR)
        self.dp_accountant.step(
            effective_noise_multiplier(self.cfg.dp_noise_multiplier), q,
            steps=len(losses),
        )
        eps, _ = self.dp_accountant.epsilon(self.cfg.dp_delta)
        self._dp_epsilon = float(eps)
        # the (ε, δ) /statusz surface: train_epsilon next to train_loss
        self.bus.gauge("train_epsilon", self._dp_epsilon)
        return losses

    @staticmethod
    def _new_metrics(num_class: int):
        """Binary: score = positive-class probability (reference semantics,
        AUC on prob[:,1], comps/icalstm/__init__.py:64-65); multiclass:
        argmax-based macro metrics."""
        if num_class == 0:  # a task with its own loss and no classes
            return NoClassMetrics()
        return ClassificationMetrics() if num_class == 2 else MulticlassMetrics()

    @staticmethod
    def _add_probs(m, probs, labels, weights):
        if isinstance(m, NoClassMetrics):
            return m
        if isinstance(m, ClassificationMetrics):
            m.add(probs[..., 1].reshape(-1), labels.reshape(-1), weights.reshape(-1))
        else:
            m.add(probs.reshape(-1, probs.shape[-1]), labels.reshape(-1),
                  weights.reshape(-1))
        return m

    def _format_val_line(self, avg, metrics, monitor: str) -> str:
        """Per-epoch validation readout, columns chosen by ``cfg.log_header``
        (the reference's log display header, e.g. ``"Loss|AUC"`` —
        ``local.py:36``, ``compspec.json:256``). Unknown names are skipped;
        falls back to loss + the monitored metric."""
        names = [h.strip().lower() for h in (self.cfg.log_header or "").split("|")]
        parts = []
        for nm in names:
            if nm == "loss":
                parts.append(f"val_loss={avg.avg:.4f}")
            elif nm:
                try:
                    parts.append(f"val_{nm}={metrics.value(nm):.4f}")
                except (KeyError, ValueError):
                    pass
        if not parts:
            score = metrics.value(monitor) if monitor != "loss" else avg.avg
            parts = [f"val_loss={avg.avg:.4f}", f"val_{monitor}={score:.4f}"]
        return " ".join(parts)

    def evaluate(self, state, sites, batch_size=None, per_site: bool = False):
        """Pooled (remote-side) metrics across all sites; with
        ``per_site=True`` also returns each site's own (Averages, metrics) —
        the eval step already computes per-site probs/loss sums, so per-site
        logs (reference ``local{i}/logs.json``) come for free."""
        with self.tracer.span("eval"):
            fb = plan_eval(sites, batch_size or self.cfg.batch_size)
            outs = self.eval_fn(state, *self._put_batch(fb))
            from ..parallel.distributed import fetch_site_outputs

            # [S, steps, B, C] probs + per-site sums; multi-host meshes
            # gather the P(site)-sharded outputs before the host fetch
            probs, loss_sum, wsum = fetch_site_outputs(outs, self.mesh)
        loss = float(loss_sum.sum() / max(wsum.sum(), 1.0))
        m = self._add_probs(
            self._new_metrics(probs.shape[-1]), probs, fb.labels, fb.weights
        )
        avg = Averages().add(loss, wsum.sum())
        if not per_site:
            return avg, m
        site_results = []
        for s in range(probs.shape[0]):
            sm = self._add_probs(
                self._new_metrics(probs.shape[-1]), probs[s], fb.labels[s],
                fb.weights[s],
            )
            savg = Averages().add(
                float(loss_sum[s] / max(wsum[s], 1.0)), wsum[s]
            )
            site_results.append((savg, sm))
        return avg, m, site_results

    # -- the full fit ----------------------------------------------------

    def fit(
        self,
        train_sites: list[SiteArrays],
        val_sites: list[SiteArrays],
        test_sites: list[SiteArrays],
        fold: int = 0,
        verbose: bool = True,
        resume: bool = False,
    ) -> dict:
        cfg = self.cfg
        if cfg.mode.lower() == "test":
            # GUI mode=test (compspec.json mode field): inference only, no
            # training — load the fold's best checkpoint and evaluate.
            return self.test_only(test_sites, fold=fold)
        # telemetry envelope: the whole fit runs under one "fit" span, and
        # the artifact sink (opened inside _fit_impl once paths are known)
        # ALWAYS finalizes — early stop, Preempted, or a crash still leave a
        # complete manifest/metrics.jsonl/trace set on disk.
        self._fit_tel = None
        self._fit_summary: dict = {}
        try:
            with self.tracer.span("fit", fold=fold):
                return self._fit_impl(
                    train_sites, val_sites, test_sites, fold=fold,
                    verbose=verbose, resume=resume,
                )
        finally:
            fit_tel = self._fit_tel
            if fit_tel is not None:
                from ..checks.sanitize import jit_cache_size

                compiles0 = self._fit_summary.pop("_compiles0", 0)
                self._fit_summary["epoch_compiles"] = (
                    (jit_cache_size(self.epoch_fn) or 0) - compiles0
                )
                fit_tel.append(self._fit_summary)
                fit_tel.close()
                self._fit_tel = None

    def _fit_impl(
        self,
        train_sites: list[SiteArrays],
        val_sites: list[SiteArrays],
        test_sites: list[SiteArrays],
        fold: int = 0,
        verbose: bool = True,
        resume: bool = False,
    ) -> dict:
        cfg = self.cfg
        # monotonic clock for every duration (the tracer's clock): wall
        # time can step (NTP, DST) mid-fit and corrupt the checkpointed
        # duration bookkeeping
        t_start = time.perf_counter()
        self._num_sites = len(train_sites)
        if self.mesh is not None:
            from ..parallel.mesh import pack_factor

            # the packed site layout (parallel/mesh.py): S virtual sites
            # shard P(site) into [K, ...] device blocks — fail here with a
            # clear message (not an XLA sharding error) when S doesn't
            # divide over the mesh's site axis
            pack_factor(self.mesh, self._num_sites)
        # Fail fast on splits that are empty at EVERY site; per-site emptiness
        # and too-small sites are handled below (warning / batch-size clamp).
        sizes = [
            (len(a), len(b), len(c))
            for a, b, c in zip(train_sites, val_sites, test_sites)
        ]
        for name, split_sites in (("train", train_sites), ("test", test_sites)):
            if not any(len(s) for s in split_sites):
                raise ValueError(
                    f"the {name} split is empty at every site (site train/"
                    f"val/test sizes: {sizes}; split_ratio="
                    f"{cfg.split_ratio}) — use more subjects per site or a "
                    "split_ratio that gives each split at least one sample "
                    "somewhere"
                )
        # Empty validation EVERYWHERE is a supported configuration
        # (kfold_splits k==2 has no fold left for validation, splits.py:41-45):
        # skip validation-based selection and keep the final state.
        has_val = any(len(s) for s in val_sites)
        min_site = min((len(s) for s in train_sites if len(s)), default=0)
        if 0 < min_site < cfg.batch_size:
            # Heterogeneous-site guard (VERDICT r4 #6): with drop_last train
            # batching, a site smaller than batch_size yields ZERO batches
            # and contributes nothing (or, if every site is small, plan_epoch
            # asserts). Clamp so any demo-sized tree trains, and say so.
            # The clamp stays in the LOCAL cfg only — self.cfg is shared with
            # the caller (FedRunner hands one config object to every fold's
            # trainer), and a fold with small sites must not shrink the batch
            # for later folds (ADVICE r5). The clamped batch size is threaded
            # explicitly to run_epoch/evaluate below.
            if verbose:
                log_warning(
                    f"[warn] batch_size={cfg.batch_size} exceeds the smallest "
                    f"site's train split ({min_site} samples); clamping "
                    f"batch_size to {min_site} for this fold (drop_last "
                    "batching would starve that site). Pass a batch_size <= "
                    f"{min_site} to silence this."
                )
            cfg = cfg.replace(batch_size=min_site)
        if verbose:
            for i, s in enumerate(train_sites):
                if not len(s):
                    log_warning(
                        f"[warn] site {i} has an empty train split "
                        f"(train/val/test sizes: {sizes[i]}) — it will "
                        "contribute nothing to training this fold"
                    )
        state = self.init_state(jnp.ones((cfg.batch_size,) + train_sites[0].inputs.shape[1:], jnp.float32))

        latest_path = best_path = None
        if self.out_dir:
            d = fold_dir(self.out_dir, "remote", cfg.task_id, fold)
            latest_path = os.path.join(d, "checkpoint_latest.msgpack")
            best_path = os.path.join(d, "checkpoint_best.msgpack")
        # a kill inside the rotate window (primary moved to .prev, new primary
        # not yet written) leaves only the .prev generation — still a valid
        # resume point (load_checkpoint falls back to it), so gate on either
        resuming = bool(
            resume and latest_path
            and (os.path.exists(latest_path)
                 or os.path.exists(latest_path + ".prev"))
        )

        # --- telemetry artifact sink (manifest.json + metrics.jsonl +
        # trace files under <out_dir>/telemetry/fold_<k>): one per fit, on
        # the coordinator only (same single-writer rule as checkpoints)
        if self._telemetry_on:
            tel_root = cfg.telemetry_dir or (
                os.path.join(self.out_dir, "telemetry") if self.out_dir else ""
            )
            if tel_root and self._coordinator():
                from ..checks.sanitize import jit_cache_size
                from ..telemetry.sink import FitTelemetry

                self._fit_tel = FitTelemetry.open(
                    os.path.join(tel_root, f"fold_{fold}"), cfg,
                    mesh=self.mesh, fold=fold, tracer=self.tracer,
                    fault_plan=self.fault_plan, attack_plan=self.attack_plan,
                )
                self._fit_summary = {
                    "kind": "summary", "fold": fold, "epochs_run": 0,
                    "best_val_epoch": 0, "best_val_metric": None,
                    # elastic-rounds rollup (robustness/membership.py);
                    # batch-job fits have no membership table → null
                    "membership": None,
                    "_compiles0": jit_cache_size(self.epoch_fn) or 0,
                }
            elif not tel_root and verbose:
                log_warning(
                    "[warn] telemetry='on' but neither out_dir nor "
                    "telemetry_dir is set — spans and device metrics are "
                    "collected but no artifacts will be written"
                )

        # --- warm starts — skipped when resuming: load_checkpoint below
        # replaces the state wholesale, so pretraining first would be pure
        # wasted compute on every restart
        if not resuming:
            # params-only warm start from a saved checkpoint (fresh
            # optimizer/engine state — pretrain-from-file semantics)
            if cfg.pretrained_path:
                state = state.replace(
                    params=load_params(cfg.pretrained_path, state.params)
                )
            # pretrain on the largest site (compspec.json:120-127)
            if cfg.pretrain and cfg.pretrain_args and cfg.pretrain_args.epochs > 0:
                state = self._pretrain(state, train_sites, val_sites, verbose)

        best_metric = None
        best_epoch = 0
        # snapshot, never alias: with donate_epoch_state the next epoch_fn
        # call consumes `state`'s buffers in place (trainer/steps.py)
        best_state = self._snapshot(state)
        since_best = 0
        epoch_losses = []
        iter_durations = []
        start_epoch = 1

        # --- fold resume: restore trainer state + selection/duration
        # bookkeeping from the last validation-boundary checkpoint (meta is
        # embedded in the msgpack, atomically paired with the state)
        if resuming:
            state, meta = load_checkpoint(latest_path, state, with_meta=True)
            state = self._place_state(state)  # avoid a resume-layout recompile
            start_epoch = int(meta.get("epoch", 0)) + 1
            best_metric = meta.get("best_val_metric")
            best_epoch = int(meta.get("best_val_epoch", 0))
            since_best = int(meta.get("since_best", 0))
            epoch_losses = list(meta.get("epoch_losses", []))
            iter_durations = list(meta.get("iter_durations", []))
            self._cache["time_spent_on_computation"] = list(
                meta.get("time_spent_on_computation", [])
            )
            cum = list(meta.get("cumulative_total_duration", []))
            self._cache["cumulative_total_duration"] = cum
            # continue the cumulative wall-clock line from its stored total
            if cum:
                t_start = time.perf_counter() - cum[-1]
            # privacy ledger (r20): resume continues ε accumulation EXACTLY
            # — the checkpointed RDP state replaces the fresh ledger, so an
            # interrupted fit spends the same budget as an uninterrupted
            # one (no double count, no reset; tests/test_privacy.py)
            if self.dp_accountant is not None and meta.get("dp_accountant"):
                from ..privacy import RdpAccountant

                self.dp_accountant = RdpAccountant.from_json(
                    meta["dp_accountant"]
                )
                eps, _ = self.dp_accountant.epsilon(cfg.dp_delta)
                self._dp_epsilon = float(eps)
            # snapshot either way: a load falling back to template leaves
            # (engine-structure change) would otherwise alias `state`
            best_state = self._snapshot(
                load_checkpoint(best_path, state)
                if (os.path.exists(best_path)
                    or os.path.exists(best_path + ".prev"))
                else state
            )

        monitor = cfg.monitor_metric
        direction = cfg.metric_direction

        # opt-in device trace (SURVEY.md §5): TensorBoard-compatible profile
        # of the whole epoch loop, one trace per fold
        if cfg.profile_dir:
            jax.profiler.start_trace(
                os.path.join(cfg.profile_dir, f"fold_{fold}")
            )
        # windowed jax.profiler capture (telemetry/xprof.py): trace only the
        # cfg.xprof_window epoch range — mutually exclusive with profile_dir
        # (checked at construction)
        xprof = None
        if cfg.xprof_dir:
            from ..telemetry.xprof import XprofWindow

            xprof = XprofWindow(
                cfg.xprof_dir, cfg.xprof_window, label=f"fold_{fold}"
            )
        stop_epoch = cfg.epochs
        # kill-at-round chaos arm: track the global round window per epoch so
        # the kill fires exactly once, when training CROSSES the round (a
        # resumed run starts past it and sails through)
        kill_round = (
            self.fault_plan.kill_at_round if self.fault_plan is not None else None
        )
        round_before = int(state.round) if kill_round is not None else 0
        prefetch = None
        if self._pipeline == "device" and start_epoch <= cfg.epochs:
            # double-buffered planner (trainer/prefetch.py): a background
            # thread builds epoch N+1's index plan and dispatches its
            # KB-sized transfer while epoch N's fused dispatch runs. Plans
            # are pure functions of (epoch, global round window) — the round
            # counter extrapolates linearly from here, resume included — so
            # prefetching cannot change results.
            rpe = epoch_steps(train_sites, cfg.batch_size) // max(
                cfg.local_iterations, 1
            )
            round0, first = int(state.round), start_epoch
            prefetch = EpochPlanPrefetcher(
                lambda e: self._build_epoch_payload(
                    train_sites, e, cfg.batch_size, round0 + (e - first) * rpe
                ),
                start_epoch, cfg.epochs, tracer=self.tracer,
            )
        guard = PreemptionGuard()
        try:
            with guard:
                for epoch in range(start_epoch, cfg.epochs + 1):
                    e_start = time.perf_counter()
                    if xprof is not None:
                        xprof.epoch_begin(epoch)
                    with self.tracer.span("epoch", epoch=epoch):
                        state, losses = self.run_epoch(
                            state, train_sites, epoch,
                            batch_size=cfg.batch_size,
                            plan=(None if prefetch is None
                                  else prefetch.get(epoch)),
                        )
                    if xprof is not None:
                        xprof.epoch_end(epoch)
                    # all-dead rounds report NaN loss (trainer/steps.py) —
                    # average over the rounds that actually trained
                    lived = losses[np.isfinite(losses)]
                    epoch_loss = float(lived.mean()) if lived.size else float("nan")
                    epoch_losses.append(epoch_loss)
                    # per-iteration durations (reference local_iter_duration is
                    # per-round, NB.ipynb cells 34-36). All rounds of an epoch run in
                    # ONE fused XLA dispatch here, so per-round host timing does not
                    # exist; the truthful equivalent is the epoch time amortized over
                    # its rounds.
                    rounds = max(len(losses), 1)
                    e_seconds = time.perf_counter() - e_start
                    iter_durations.extend([e_seconds / rounds] * rounds)
                    # live metrics: values already on the host (losses were
                    # fetched above) — no extra device sync
                    self.bus.gauge("train_epoch", epoch)
                    self.bus.gauge("train_loss", epoch_loss)
                    self.bus.counter("train_epochs_total")
                    self.bus.counter("train_rounds_total", rounds)
                    self.bus.observe("epoch_ms", e_seconds * 1e3)
                    if self._dcn_bytes_round > 0:
                        # per-tier wire accounting (r18): modeled inter-slice
                        # (DCN) bytes this epoch shipped — the /statusz
                        # surface for "what is the slow hop carrying". A
                        # static per-round model (verified by the sliced
                        # semantic cells), so no device sync.
                        self.bus.counter(
                            "train_dcn_bytes_total",
                            self._dcn_bytes_round * rounds,
                        )
                    if (
                        self._telemetry_on and state.health is not None
                        and "anomaly" in state.health
                    ):
                        # reputation scores onto the live bus (r17): the
                        # /statusz surface for "is a site drifting hostile".
                        # The losses fetch above already synchronized the
                        # epoch, so these tiny [S] reads add no extra
                        # device round trip of consequence.
                        from ..parallel.distributed import fetch_site_outputs

                        anom = fetch_site_outputs(
                            state.health["anomaly"], self.mesh
                        )
                        quar = fetch_site_outputs(
                            state.health["quarantined"], self.mesh
                        )
                        self.bus.gauge(
                            "train_anomaly_max", float(np.max(anom))
                        )
                        self.bus.gauge(
                            "train_quarantined_sites",
                            int(np.sum(np.asarray(quar) > 0)),
                        )
                    if self._fit_tel is not None:
                        self._epoch_row(fold, epoch, epoch_loss, e_start,
                                        state)
                        self._fit_summary["epochs_run"] = len(epoch_losses)

                    if epoch % cfg.validation_epochs == 0:
                        if has_val:
                            val_avg, val_metrics = self.evaluate(
                                state, val_sites, batch_size=cfg.batch_size
                            )
                            score = val_metrics.value(monitor) if monitor != "loss" else val_avg.avg
                            if is_improvement(
                                score, best_metric, direction if monitor != "loss" else "minimize"
                            ):
                                best_metric, best_epoch = score, epoch
                                best_state = self._snapshot(state)
                                since_best = 0
                                if best_path and self._coordinator():  # save-on-best
                                    with self.tracer.span("checkpoint"):
                                        save_checkpoint(
                                            best_path, best_state,
                                            meta={"best_val_epoch": best_epoch,
                                                  "best_val_metric": best_metric, "fold": fold},
                                            rotate=True,
                                        )
                                    if self._fit_tel is not None:
                                        self._fit_tel.event(
                                            "checkpoint", epoch=epoch,
                                            which="best",
                                        )
                            else:
                                since_best += cfg.validation_epochs
                            if verbose:
                                log_info(
                                    f"[fold {fold}] epoch {epoch}: train_loss={epoch_loss:.4f} "
                                    + self._format_val_line(val_avg, val_metrics, monitor)
                                    + (" *" if best_epoch == epoch else "")
                                )
                        else:
                            # no validation anywhere (kfold k==2): the latest
                            # state is the selected state; no early stopping
                            best_epoch, best_state = epoch, self._snapshot(state)
                            if verbose:
                                log_info(
                                    f"[fold {fold}] epoch {epoch}: "
                                    f"train_loss={epoch_loss:.4f} (no validation split)"
                                )
                        stop = since_best >= cfg.patience
                    else:
                        stop = False
                    # durations BEFORE the save so the checkpointed meta's
                    # bookkeeping covers the same epochs as its epoch_losses
                    # (and the save's own IO time stays out of compute time)
                    duration(self._cache, e_start, "time_spent_on_computation")
                    duration(self._cache, t_start, "cumulative_total_duration")
                    # rotating resume point EVERY epoch (ckpt + ckpt.prev,
                    # checksummed): preemption granularity is one epoch, and a
                    # torn/corrupt latest falls back to the previous generation
                    if latest_path and self._coordinator():
                        with self.tracer.span("checkpoint"):
                            save_checkpoint(
                                latest_path, state,
                                meta={"epoch": epoch, "best_val_epoch": best_epoch,
                                      "best_val_metric": best_metric,
                                      "since_best": since_best, "fold": fold,
                                      "epoch_losses": epoch_losses,
                                      "iter_durations": iter_durations,
                                      "time_spent_on_computation": self._cache.get(
                                          "time_spent_on_computation", []),
                                      "cumulative_total_duration": self._cache.get(
                                          "cumulative_total_duration", []),
                                      # the RDP ledger rides the atomically-
                                      # paired meta (r20): resume continues
                                      # ε exactly from this boundary
                                      "dp_accountant": (
                                          self.dp_accountant.to_json()
                                          if self.dp_accountant is not None
                                          else None)},
                                rotate=True,
                            )
                    # -- preemption: a SIGTERM/SIGINT that landed during the
                    # epoch exits here, AFTER the rotating checkpoint, so
                    # resume=True continues bit-exact from this boundary
                    if guard.requested is not None:
                        if self._fit_tel is not None:
                            self._fit_tel.event(
                                "preempted", epoch=epoch,
                                signum=int(guard.requested),
                            )
                        raise Preempted(
                            f"signal {guard.requested} during epoch {epoch}; "
                            f"state saved to {latest_path or '(no out_dir)'}",
                            signum=guard.requested, epoch=epoch,
                        )
                    if kill_round is not None:
                        round_after = int(state.round)
                        if round_before <= kill_round < round_after:
                            if self._fit_tel is not None:
                                self._fit_tel.event(
                                    "preempted", epoch=epoch,
                                    kill_at_round=int(kill_round),
                                )
                            raise Preempted(
                                f"FaultPlan kill_at_round={kill_round} crossed "
                                f"during epoch {epoch}; state saved to "
                                f"{latest_path or '(no out_dir)'}",
                                epoch=epoch,
                            )
                        round_before = round_after
                    # ε-budget exhaustion (r20): the Preempted-style
                    # checkpointed exit, minus the nonzero exit code — the
                    # epoch's rotating checkpoint is already on disk above,
                    # so the fit stops cleanly here and proceeds to the
                    # best-state test with the budget respected
                    if (
                        cfg.dp_epsilon_budget > 0.0
                        and self._dp_epsilon is not None
                        and self._dp_epsilon >= cfg.dp_epsilon_budget
                    ):
                        if self._fit_tel is not None:
                            self._fit_tel.event(
                                "dp-budget", epoch=epoch,
                                epsilon=self._dp_epsilon,
                                budget=cfg.dp_epsilon_budget,
                            )
                        if verbose:
                            log_info(
                                f"[fold {fold}] epoch {epoch}: privacy "
                                f"budget exhausted (ε="
                                f"{self._dp_epsilon:.3f} ≥ "
                                f"{cfg.dp_epsilon_budget}); stopping"
                            )
                        stop_epoch = epoch
                        break
                    if stop:
                        stop_epoch = epoch
                        break
        finally:
            # prompt, leak-free shutdown on EVERY exit — early stop,
            # Preempted (SIGTERM / FaultPlan kill), or a crash: a resumed run
            # must never inherit a live prefetch thread
            if prefetch is not None:
                if self._fit_tel is not None:
                    # stall/queue-depth counters into the summary row, read
                    # BEFORE close() while the stats are final-but-live
                    self._fit_summary.update({
                        f"prefetch_{k}": v
                        for k, v in prefetch.stats().items()
                    })
                prefetch.close()
            if xprof is not None:
                xprof.close()
            if cfg.profile_dir:
                jax.profiler.stop_trace()

        # If the epoch count never hit a validation boundary (epochs <
        # validation_epochs), best_state would be the untrained init — run a
        # final validation so the trained weights compete for selection.
        if best_metric is None and cfg.epochs > 0:
            if has_val:
                val_avg, val_metrics = self.evaluate(
                    state, val_sites, batch_size=cfg.batch_size
                )
                score = val_metrics.value(monitor) if monitor != "loss" else val_avg.avg
                best_metric, best_epoch, best_state = score, stop_epoch, state
            else:
                best_epoch, best_state = stop_epoch, state

        # --- test with the best state (reference: best-epoch checkpoint)
        with self.tracer.span("test"):
            results = self._test_results(best_state, test_sites, best_epoch,
                                         best_metric, stop_epoch, epoch_losses,
                                         batch_size=cfg.batch_size)
        # per-site fault-tolerance counters from the FINAL state (best_state
        # may predate a quarantine event): rounds skipped, quarantine flags
        if state.health is not None:
            from ..parallel.distributed import fetch_site_outputs

            results["site_health"] = health_summary(
                fetch_site_outputs(state.health, self.mesh)
            )
        # per-site round-metric rollup from the FINAL state, same rationale
        if state.telemetry is not None:
            from ..parallel.distributed import fetch_site_outputs
            from ..telemetry.metrics import telemetry_summary

            results["site_telemetry"] = telemetry_summary(
                fetch_site_outputs(state.telemetry, self.mesh)
            )
        # privacy surfaces (r20): the spent (ε, δ) lands in the results
        # dict, logs.json (via _write_outputs) and the telemetry summary
        if self._dp_epsilon is not None:
            results["dp_epsilon"] = self._dp_epsilon
            results["dp_delta"] = cfg.dp_delta
        if self._fit_tel is not None:
            from ..telemetry.sink import tree_devices

            self._fit_summary.update(
                best_val_epoch=int(best_epoch),
                best_val_metric=best_metric,
                dp_epsilon=self._dp_epsilon,
                # where the final state lives: the replicated model, and
                # the per-site leaves (engine state, health, round metrics)
                # that a site mesh shards one block per member
                params_devices=tree_devices(state.params),
                site_state_devices=tree_devices(
                    (state.engine_state, state.health, state.telemetry)
                ),
            )
            for key in ("site_skipped_rounds", "site_quarantined"):
                if results.get("site_health"):
                    self._fit_summary[key] = results["site_health"][key]
        if self.out_dir:
            with self.tracer.span("write-outputs"):
                self._write_outputs(results, iter_durations, best_state, fold)
        results["state"] = best_state
        return results

    def test_only(self, test_sites: list[SiteArrays], fold: int = 0) -> dict:
        """``mode="test"``: load the fold's best checkpoint and evaluate —
        reproduces the stored ``test_metrics`` without training."""
        cfg = self.cfg
        if not self.out_dir:
            raise ValueError('mode="test" needs out_dir (to find the checkpoint)')
        d = fold_dir(self.out_dir, "remote", cfg.task_id, fold)
        ckpt = os.path.join(d, "checkpoint_best.msgpack")
        if not os.path.exists(ckpt):
            raise FileNotFoundError(
                f'mode="test" but no trained checkpoint at {ckpt}'
            )
        self._num_sites = len(test_sites)
        state = self.init_state(
            jnp.ones((cfg.batch_size,) + test_sites[0].inputs.shape[1:], jnp.float32)
        )
        # eval needs only params + batch_stats; a full-state restore would tie
        # mode="test" to the training run's site count via engine-state shapes
        params, stats, meta = load_eval_state(ckpt, state.params, state.batch_stats)
        state = state.replace(params=params, batch_stats=stats)
        results = self._test_results(
            state, test_sites,
            int(meta.get("best_val_epoch", 0)), meta.get("best_val_metric"),
            stop_epoch=0, epoch_losses=[],
        )
        results["state"] = state
        return results

    def _test_results(self, state, test_sites, best_epoch, best_metric,
                      stop_epoch, epoch_losses, batch_size=None) -> dict:
        # batch_size threads the fold-local clamp (fit) through to the test
        # eval: values are identical either way (plan_eval mask-pads), but
        # reusing the validation evals' batch shape avoids a second XLA
        # compilation of the eval step at the unclamped shape.
        monitor = self.cfg.monitor_metric
        test_avg, test_metrics, site_results = self.evaluate(
            state, test_sites, batch_size=batch_size, per_site=True
        )
        monitored = test_metrics.value(monitor) if monitor != "loss" else test_avg.avg
        return {
            "agg_engine": self.cfg.agg_engine,
            "best_val_epoch": best_epoch,
            "best_val_metric": best_metric,
            "stopped_epoch": stop_epoch,
            "test_metrics": [[round(test_avg.avg, 5), round(monitored, 5)]],
            "test_scores": {
                n: test_metrics.value(n) for n in test_metrics.NAMES
            },
            "site_test_metrics": [
                [[round(a.avg, 5),
                  round(m.value(monitor) if monitor != "loss" else a.avg, 5)]]
                for a, m in site_results
            ],
            "epoch_losses": epoch_losses,
        }

    # -- internals -------------------------------------------------------

    def _epoch_row(self, fold, epoch, epoch_loss, e_start, state):
        """One per-epoch metrics.jsonl record: loss/timing/transfer plus the
        on-device per-site accumulators. The losses fetch in run_epoch
        already synchronized the epoch, so reading the small [S] telemetry
        arrays here adds no extra device round trip of consequence."""
        from ..parallel.distributed import fetch_site_outputs

        row = {
            "kind": "epoch", "fold": fold, "epoch": epoch,
            "train_loss": epoch_loss,
            "epoch_seconds": round(time.perf_counter() - e_start, 6),
            "transfer_bytes": self._last_transfer_bytes,
            # spent privacy so far (r20, privacy/accounting.py): null when
            # the DP mechanism is off or noiseless (ε = ∞ is reported as
            # null by the strict-JSON contract anyway) — a REQUIRED epoch
            # key, so a DP run's per-epoch ε trail is schema-guaranteed
            "dp_epsilon": self._dp_epsilon,
        }
        t = (
            fetch_site_outputs(state.telemetry, self.mesh)
            if state.telemetry is not None else None
        )
        if t is not None:
            row.update(
                site_grad_sq_last=[float(v) for v in t["grad_sq_last"]],
                site_grad_sq_sum=[float(v) for v in t["grad_sq_sum"]],
                site_grad_sq_max=[float(v) for v in t["grad_sq_max"]],
                site_residual_sq_sum=[
                    float(v) for v in t["residual_sq_sum"]
                ],
                update_sq_last=float(t["update_sq_last"][0]),
                payload_bytes=float(t["payload_bytes"][0]),
                # per-tier split (r18): inter-slice (DCN) bytes shipped so
                # far — 0.0 on single-slice runs
                dcn_bytes=float(t.get("dcn_bytes", [0.0])[0]),
                rounds=int(t["rounds"][0]),
                # slice-quorum holds (r19): rounds the min_slices floor
                # declined so far — 0 off sliced/fault-free runs
                held_rounds=int(t.get("held_rounds", [0])[0]),
            )
        else:  # epoch rows keep one schema even if metrics are absent
            row.update(
                site_grad_sq_last=[], site_grad_sq_sum=[],
                site_grad_sq_max=[], site_residual_sq_sum=[],
                update_sq_last=0.0, payload_bytes=0.0, dcn_bytes=0.0,
                rounds=0, held_rounds=0,
            )
        self._fit_tel.append(row)

    def _pretrain(self, state, train_sites, val_sites, verbose):
        pa = self.cfg.pretrain_args
        largest = int(np.argmax([len(s) for s in train_sites]))
        # zero every other site's examples: same SPMD program, one active site
        masked = [
            s if i == largest else SiteArrays(s.inputs[:0], s.labels[:0], s.indices[:0])
            for i, s in enumerate(train_sites)
        ]
        pre_opt = make_optimizer(self.cfg.optimizer, pa.learning_rate)
        # Pretrain is a single-site warm start: use exact (dSGD) gradients
        # regardless of the configured engine — rankDAD/powerSGD compression
        # during warm-up would diverge from the reference's plain local SGD.
        pre_engine = make_engine("dSGD", precision_bits=self.cfg.precision_bits)
        pre_epoch_fn = make_train_epoch_fn(
            self.task, pre_engine, pre_opt, self.mesh, pa.local_iterations,
            rounds_scan_xs=self.cfg.rounds_scan_xs,
        )
        pre_state = TrainState(
            params=state.params,
            batch_stats=state.batch_stats,
            opt_state=pre_opt.init(state.params),
            engine_state=jax.tree.map(
                lambda a: jnp.stack([a] * self._num_sites), pre_engine.init(state.params)
            ),
            rng=state.rng,
            round=state.round,
            health=state.health,
            # pre_epoch_fn is built telemetry-off (warm-up metrics would
            # pollute the federated accumulators); None matches its program
            telemetry=None,
        )
        with self.tracer.span("pretrain"):
            for epoch in range(1, pa.epochs + 1):
                fb = plan_epoch(
                    masked, pa.batch_size, seed=self.cfg.seed * 7 + epoch,
                    pad_mode="mask",
                )
                pre_state, losses = pre_epoch_fn(pre_state, *self._put_batch(fb))
                if verbose:
                    log_info(f"[pretrain site {largest}] epoch {epoch}: "
                             f"loss={np.asarray(losses).mean():.4f}")
        # warm-started params; fresh optimizer (and health) for the federated
        # phase — pretrain skips/quarantines must not leak into the real run
        return TrainState(
            params=pre_state.params,
            batch_stats=pre_state.batch_stats,
            opt_state=self.optimizer.init(pre_state.params),
            engine_state=state.engine_state,
            rng=state.rng,
            round=pre_state.round,
            health=state.health,
            telemetry=state.telemetry,
            # personalized head rows (r20) survive the warm start untouched
            # — they are fresh common-model rows at this point anyway
            personal=state.personal,
        )

    def _write_outputs(self, results, iter_durations, best_state, fold):
        if not self._coordinator():
            return  # every process computes identical replicated results;
            # only process 0 touches the (shared) output directory
        cfg = self.cfg
        comp = self._cache.get("time_spent_on_computation", [])
        cum = self._cache.get("cumulative_total_duration", [])
        site_tm = results.get("site_test_metrics") or []
        for i in range(self._num_sites):
            d = fold_dir(self.out_dir, f"local{i}", cfg.task_id, fold)
            # Each site's log carries ITS OWN test metrics (reference
            # local.py:51-52 writes genuinely per-site logs). The duration
            # lists are shared by design: all sites execute as one fused SPMD
            # program, so wall-clock is common — the extra key records that.
            write_logs_json(
                d, cfg.agg_engine,
                site_tm[i] if i < len(site_tm) else results["test_metrics"],
                results["best_val_epoch"],
                cum, comp, iter_durations, side="local",
                extra={"site_index": i, "pooled_test_metrics": results["test_metrics"],
                       "durations_shared_across_sites": True,
                       **health_log_fields(results.get("site_health"), i),
                       **telemetry_log_fields(results.get("site_telemetry"), i),
                       **privacy_log_fields(results)},
            )
        d = fold_dir(self.out_dir, "remote", cfg.task_id, fold)
        write_logs_json(
            d, cfg.agg_engine, results["test_metrics"], results["best_val_epoch"],
            cum, comp, iter_durations, side="remote",
            extra={**health_log_fields(results.get("site_health")),
                   **telemetry_log_fields(results.get("site_telemetry")),
                   **privacy_log_fields(results)},
        )
        write_test_metrics_csv(d, fold, results["test_scores"])
        save_checkpoint(
            os.path.join(d, "checkpoint_best.msgpack"),
            best_state,
            meta={"best_val_epoch": results["best_val_epoch"],
                  "best_val_metric": results["best_val_metric"], "fold": fold},
        )
        zip_global_results(
            self.out_dir, num_sites=self._num_sites, task_id=cfg.task_id
        )
