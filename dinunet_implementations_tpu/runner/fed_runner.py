"""Runners: single-site (SiteRunner parity) and federated over a dataset tree.

- :class:`SiteRunner` — the reference's standalone debug harness
  (``comps/fs/site_run.py:4-6``, ``comps/icalstm/site_run.py:5-9``): train one
  site from a ``datasets/<name>`` folder + its ``inputspec.json``, no
  aggregation (a 1-site federation).
- :class:`FedRunner` — the replacement for the COINSTAC simulator (SURVEY.md
  §4.1): discovers ``input/local*/simulatorRun`` site dirs (the reference's
  fixture convention), builds per-site datasets/splits, and trains them as one
  SPMD program on a site mesh (or folded onto one chip with ``mesh=None``).
  Supports split-ratio and k-fold drivers.
- :class:`FedDaemon` — the long-running SERVICE form (elastic rounds, r13):
  a persistent loop over one compiled epoch program with a fixed
  ``[capacity]`` virtual-site axis, absorbing site joins / leaves / rejoins
  from a filesystem ingest spool (``robustness/membership.py``
  MembershipTable), holding rounds below a quorum floor, checkpointing on
  membership epochs, and — with ``TrainConfig.staleness_bound > 0`` —
  aggregating under the staleness-bounded buffered-async semantics so
  stragglers fade instead of stalling. CLI: ``dinunet-tpu --serve``.
"""

from __future__ import annotations

import glob
import json
import os
import re
import time

import numpy as np

from ..core.config import TrainConfig, resolve_site_configs
from ..data.api import SiteArrays, build_site_dataset
from ..data.splits import resolve_splits
from ..parallel.mesh import SITE_AXIS, host_mesh, packed_site_mesh
from ..trainer.loop import FederatedTrainer
from .registry import get_task, task_cache


def _site_dir_key(path: str):
    """Numeric-then-lexicographic sort key for a ``local*`` site dir.

    The site number is taken from the ``local*`` path segment ONLY (not the
    whole path — a digit elsewhere in the tree must not reorder sites), via
    ``re.search``: mixed trees with a bare ``local`` dir (no digits) or
    decorated names (``local_backup``, unicode digit lookalikes that
    ``str.isdigit`` accepts but ``int()`` rejects) sort first instead of
    crashing the runner. The full path tie-breaks duplicates
    deterministically.
    """
    segment = os.path.basename(os.path.dirname(path))
    m = re.search(r"([0-9]+)", segment)
    return (int(m.group(1)) if m else -1, path)


def discover_site_dirs(dataset_dir: str) -> list[str]:
    """Reference fixture layout: ``<dataset_dir>/input/local{i}/simulatorRun``
    (``datasets/test_fsl``); falls back to ``dataset_dir`` itself as a single
    site when no local* dirs exist."""
    pattern = os.path.join(dataset_dir, "input", "local*", "simulatorRun")
    dirs = sorted(glob.glob(pattern), key=_site_dir_key)
    return dirs or [dataset_dir]


def auto_site_mesh(cfg: TrainConfig, num_sites: int):
    """Resolve the ``mesh="auto"`` topology for ``num_sites`` virtual sites:
    multi-host hybrid mesh when a distributed runtime is up, the packed
    ``(site, model)`` mesh when the devices fit (k = cfg.sites_per_device
    virtual sites per member, r12), CPU host devices as the simulator
    fallback, and ``None`` (fold every site onto one device via vmap)
    otherwise. ``cfg.num_slices > 1`` (r18) lays the outer DCN slice axis
    over either form — processes map to slices on a multi-host runtime,
    virtual devices emulate them in one process. Shared by the batch
    :class:`FedRunner` and the daemon-mode :class:`FedDaemon`, so both
    resolve churn-capacity and fold topologies identically.

    Logs the devices the resolved topology uses, and warns — naming the idle
    devices and ``--sites-per-device`` — when it folds onto one device while
    more than one accelerator is visible."""
    import jax

    from ..trainer.logs import log_info, log_warning

    mesh = _resolve_site_mesh(cfg, num_sites)
    devs = jax.devices()
    if mesh is not None:
        used = list(mesh.devices.flat)
        log_info(
            f"[mesh] {num_sites} sites on {len(used)} of {len(devs)} "
            f"{used[0].platform} device(s), axes {dict(mesh.shape)}: "
            + ", ".join(str(d) for d in used)
        )
        return mesh
    log_info(
        f"[mesh] {num_sites} sites folded onto one device: {devs[0]} "
        f"({devs[0].device_kind})"
    )
    idle = [d for d in devs[1:] if d.platform != "cpu"]
    if idle:
        k = max(cfg.sites_per_device, 1)
        log_warning(
            f"[warn] {len(idle)} of {len(devs)} {devs[0].platform} devices "
            f"stay idle ({', '.join(str(d) for d in idle)}): {num_sites} "
            f"sites at --sites-per-device {k} need {num_sites // k} devices, "
            f"so every site folds onto {devs[0]}. Pass --sites-per-device "
            f"N with {num_sites}/N <= {len(devs)} to spread the sites."
        )
    return None


def _resolve_site_mesh(cfg: TrainConfig, num_sites: int):
    """The topology decision behind :func:`auto_site_mesh`: a mesh, or
    ``None`` for the one-device vmap fold."""
    import jax

    m = max(cfg.model_axis_size, 1)
    k = max(cfg.sites_per_device, 1)
    n_slices = max(cfg.num_slices, 1)
    if num_sites % k:
        raise ValueError(
            f"sites_per_device={k} must divide the site count ({num_sites})"
        )
    n_mesh = num_sites // k  # mesh site-axis size; k sites pack per device
    if n_slices > 1 and num_sites % (k * n_slices):
        raise ValueError(
            f"num_slices={n_slices} × sites_per_device={k} must divide the "
            f"site count ({num_sites})"
        )
    devs = jax.devices()
    cpus = [d for d in devs if d.platform == "cpu"]
    if jax.process_count() > 1:
        # multi-host runtime (distributed_init): hybrid mesh — the model
        # axis stays on each host's ICI, sites span DCN; with num_slices > 1
        # processes become slice granules and the inter-slice hop is the
        # only per-round DCN traffic (the multi-slice deployment shape,
        # one runner/dcn_worker.py process per slice)
        if n_slices > 1:
            from ..parallel.distributed import multihost_sliced_site_mesh

            return multihost_sliced_site_mesh(
                num_slices=n_slices,
                sites_per_slice=num_sites // n_slices,
                sites_per_device=k,
                model_axis_size=m,
            )
        from ..parallel.distributed import multihost_site_mesh

        if n_mesh % jax.process_count():
            raise ValueError(
                f"{n_mesh} mesh sites must divide evenly over "
                f"{jax.process_count()} processes"
            )
        return multihost_site_mesh(
            sites_per_process=n_mesh // jax.process_count(),
            model_axis_size=m,
        )
    if n_slices > 1:
        # single-process emulation of the sliced topology over virtual
        # devices — the whole tier-1 suite exercises the DCN tier this way
        from ..parallel.mesh import sliced_site_mesh

        if len(devs) < n_mesh * m and len(cpus) >= n_mesh * m:
            devs = cpus
        return sliced_site_mesh(
            n_slices, num_sites // n_slices, k, devs, model_axis_size=m
        )
    if len(devs) >= n_mesh * m:
        # the packed topology (parallel/mesh.py): k virtual sites per mesh
        # member, two-level aggregation in the epoch
        return packed_site_mesh(num_sites, k, devs, model_axis_size=m)
    if len(cpus) >= n_mesh * m:
        return host_mesh(n_mesh, model_axis_size=m)
    if m > 1:
        raise ValueError(
            f"model_axis_size={m} with {n_mesh} mesh sites needs "
            f"{n_mesh * m} devices (have {len(devs)}); sequence "
            "parallelism cannot fold onto one device"
        )
    return None  # fold all sites onto the local device via vmap


def load_site_splits(
    cfg: TrainConfig, site_dirs: list[str], site_cfgs: list[TrainConfig] | None = None
):
    """Build per-site datasets and per-fold splits.

    Returns ``folds``: list (per fold) of dicts with ``train``/``validation``/
    ``test`` lists of :class:`SiteArrays` (one entry per site).
    """
    site_cfgs = site_cfgs or [cfg] * len(site_dirs)
    spec = get_task(cfg.task_id)
    site_arrays = []
    site_splits = []
    for i, (d, scfg) in enumerate(zip(site_dirs, site_cfgs)):
        ds = build_site_dataset(
            spec.dataset_cls, spec.handle_cls, task_cache(scfg), {"baseDirectory": d},
            mode=scfg.mode,
        )
        arrs = ds.as_arrays()
        site_arrays.append(arrs)
        args = scfg.task_args()
        site_splits.append(
            resolve_splits(
                len(arrs),
                split_ratio=scfg.split_ratio,
                num_folds=scfg.num_folds,
                split_files=tuple(getattr(args, "split_files", ()) or ()),
                base_dir=d,
                seed=scfg.seed + i,
            )
        )
    num_folds = min(len(s) for s in site_splits)
    folds = []
    for k in range(num_folds):
        fold = {"train": [], "validation": [], "test": []}
        for arrs, splits in zip(site_arrays, site_splits):
            for key in fold:
                fold[key].append(arrs.take(splits[k][key]))
        folds.append(fold)
    return folds


class FedRunner:
    """Federated training over a reference-style dataset tree."""

    def __init__(
        self,
        cfg: TrainConfig | None = None,
        data_path: str = ".",
        out_dir: str | None = None,
        mesh="auto",
        fault_plan=None,
        attack_plan=None,
        **overrides,
    ):
        cfg = (cfg or TrainConfig()).with_overrides(overrides)
        self.data_path = data_path
        # deterministic chaos injection (robustness/faults.py), threaded into
        # every fold's trainer; None = no faults. attack_plan is the hostile
        # twin (robustness/attacks.py, r17) — byzantine gradient transforms.
        self.fault_plan = fault_plan
        self.attack_plan = attack_plan
        self.site_dirs = discover_site_dirs(data_path)
        self.site_cfgs = resolve_site_configs(cfg, data_path, num_sites=len(self.site_dirs))
        # owner-scoped fields come from site 0 (the reference GUI sends one
        # owner config; per-site inputspecs override member fields)
        self.cfg = self.site_cfgs[0].replace(num_sites=len(self.site_dirs))
        self.out_dir = out_dir or os.path.join(data_path, "output")
        if mesh == "auto":
            mesh = auto_site_mesh(self.cfg, len(self.site_dirs))
        self.mesh = mesh

    def run(self, folds=None, verbose: bool = True, resume: bool = False) -> list[dict]:
        """``resume=True`` continues each fold from its last
        validation-boundary checkpoint; ``cfg.mode == "test"`` skips training
        and evaluates each fold's best checkpoint."""
        all_folds = load_site_splits(self.cfg, self.site_dirs, self.site_cfgs)
        fold_ids = list(range(len(all_folds)))
        if folds is not None:
            all_folds = [all_folds[k] for k in folds]
            fold_ids = list(folds)
        from ..checks.sanitize import sanitized_fit

        results = []
        for k, fold in zip(fold_ids, all_folds):
            trainer = FederatedTrainer(
                self.cfg, get_task(self.cfg.task_id).build_model(self.cfg),
                self.mesh, out_dir=self.out_dir, fault_plan=self.fault_plan,
                attack_plan=self.attack_plan,
            )
            # DINUNET_SANITIZE=1 (or CLI --sanitize): compile-counter guard +
            # leak/NaN checking around the fit — each fold's trainer is one
            # (engine, topology) program, so the per-fit guard IS the
            # one-compilation-per-program gate. No-op when disabled.
            with sanitized_fit(
                trainer, label=f"{self.cfg.agg_engine}/fold{k}"
            ) as report:
                res = trainer.fit(
                    fold["train"], fold["validation"], fold["test"], fold=k,
                    verbose=verbose, resume=resume,
                )
                if report is not None:
                    report.note_result(res)
            results.append(res)
        return results


class SiteRunner:
    """Single-site harness (reference ``SiteRunner``; the ``taks_id`` typo is
    the library's kwarg — accepted here for drop-in parity)."""

    def __init__(
        self,
        taks_id: str | None = None,
        task_id: str | None = None,
        data_path: str = ".",
        mode: str = "train",
        seed: int = 0,
        site_index: int = 0,
        split_ratio=(0.8, 0.1, 0.1),
        monitor_metric: str = "auc",
        metric_direction: str = "maximize",
        log_header: str = "Loss|AUC",
        batch_size: int = 16,
        out_dir: str | None = None,
        **kw,
    ):
        # the reference's taks_id is a short name ('FSL', 'ICA'); map to tasks
        tid = task_id or {"FSL": "FS-Classification", "ICA": "ICA-Classification"}.get(
            taks_id, taks_id
        )
        self.site_index = site_index
        self.cfg = TrainConfig(
            task_id=tid,
            mode=mode,
            seed=seed,
            split_ratio=tuple(split_ratio),
            monitor_metric=monitor_metric,
            metric_direction=metric_direction,
            log_header=log_header,
            batch_size=batch_size,
        ).with_overrides(kw)
        self.data_path = data_path
        self.out_dir = out_dir

    def run(self, trainer_cls=None, dataset_cls=None, handle_cls=None, verbose=True):
        """Positional (Trainer, Dataset, DataHandle) accepted for reference
        signature parity; the registry supplies defaults."""
        site_dirs = discover_site_dirs(self.data_path)
        site_cfgs = resolve_site_configs(
            self.cfg, self.data_path, num_sites=len(site_dirs)
        )
        ix = min(self.site_index, len(site_dirs) - 1)
        cfg = site_cfgs[ix]
        spec = get_task(cfg.task_id)
        dataset_cls = dataset_cls or spec.dataset_cls
        handle_cls = handle_cls or spec.handle_cls
        ds = build_site_dataset(
            dataset_cls, handle_cls, task_cache(cfg),
            {"baseDirectory": site_dirs[ix]}, mode=cfg.mode,
        )
        arrs = ds.as_arrays()
        args = cfg.task_args()
        splits = resolve_splits(
            len(arrs),
            split_ratio=cfg.split_ratio,
            num_folds=cfg.num_folds,
            split_files=tuple(getattr(args, "split_files", ()) or ()),
            base_dir=site_dirs[ix],
            seed=cfg.seed,
        )
        from ..checks.sanitize import sanitized_fit

        results = []
        for k, split in enumerate(splits):
            trainer = FederatedTrainer(
                cfg, spec.build_model(cfg), mesh=None, out_dir=self.out_dir
            )
            with sanitized_fit(
                trainer, label=f"{cfg.agg_engine}/site{ix}/fold{k}"
            ) as report:
                res = trainer.fit(
                    [arrs.take(split["train"])],
                    [arrs.take(split["validation"])],
                    [arrs.take(split["test"])],
                    fold=k,
                    verbose=verbose,
                )
                if report is not None:
                    report.note_result(res)
            results.append(res)
        return results


# ---------------------------------------------------------------------------
# daemon mode — elastic rounds (r13)
# ---------------------------------------------------------------------------

#: spool event files are JSON objects with an "event" key:
#:   {"event": "join", "site": "<id>", "data_dir": "<path>"}
#:   {"event": "leave", "site": "<id>"}
#:   {"event": "shutdown"}
#: plus an optional "after_epoch": N — the event is held in the spool until
#: the daemon has trained N epochs (deterministic churn scheduling for tests
#: and the CI smoke). Files are processed in sorted-filename order and
#: removed once applied.
SPOOL_EVENTS = ("join", "leave", "shutdown")


class FedDaemon:
    """Daemon-mode federated training: a persistent service over ONE
    compiled epoch program.

    The virtual-site axis is pinned at ``capacity`` slots for the life of
    the service; logical sites float over it through a
    :class:`~..robustness.membership.MembershipTable`. Membership events
    arrive as JSON files in ``spool_dir`` (see :data:`SPOOL_EVENTS`);
    admission (dataset load) is deadline-bounded via
    :func:`~..robustness.retry.with_retry` so a half-written site directory
    fails fast instead of wedging the service. Every traced shape — the
    ``[capacity, N + 1, ...]`` inventory grid (its last row the zero row
    that padding slots gather), the ``[capacity, steps, B]``
    index plan, the liveness mask — is pinned at service start, so churn
    NEVER retraces (CompileGuard-assertable: one epoch compile across any
    join → straggle → leave → rejoin sequence).

    Degradation: below ``quorum`` occupied slots the service HOLDS — rounds
    are counted but not aggregated — rather than training on a sliver of
    the federation. Checkpoints rotate every epoch and on every membership
    epoch, with the table (and each member's data dir) embedded in the
    atomically-paired meta, so ``resume=True`` restores the exact slot map
    and re-admits the members' data.
    """

    def __init__(
        self,
        cfg: TrainConfig | None = None,
        capacity: int = 8,
        spool_dir: str | None = None,
        out_dir: str | None = None,
        data_path: str | None = None,
        quorum: int = 1,
        poll_s: float = 0.5,
        mesh="auto",
        fault_plan=None,
        attack_plan=None,
        admission_deadline_s: float = 10.0,
        inventory_rows: int | None = None,
        steps: int | None = None,
        resume: bool = False,
        verbose: bool = True,
        bus=None,
        flight=None,
        sink_tags: dict | None = None,
        **overrides,
    ):
        from ..robustness.membership import MembershipTable
        from ..telemetry.bus import global_bus
        from ..telemetry.flight import FlightRecorder

        cfg = (cfg or TrainConfig()).with_overrides(overrides)
        if capacity < 1:
            raise ValueError(f"capacity must be >= 1, got {capacity}")
        if not 1 <= quorum <= capacity:
            raise ValueError(
                f"quorum must be in [1, capacity={capacity}], got {quorum}"
            )
        self.cfg = cfg.replace(num_sites=capacity)
        self.capacity = capacity
        self.quorum = quorum
        self.poll_s = poll_s
        self.fault_plan = fault_plan
        self.attack_plan = attack_plan
        self.admission_deadline_s = admission_deadline_s
        self.verbose = verbose
        self.spool_dir = spool_dir or (
            os.path.join(data_path, "spool") if data_path else "spool"
        )
        self.out_dir = out_dir or (
            os.path.join(data_path, "output") if data_path else "output"
        )
        os.makedirs(self.spool_dir, exist_ok=True)
        # live observability (r16): the daemon always publishes into a
        # MetricsBus (the process-wide one unless injected) — host-side
        # bookkeeping only, readable by the /statusz exporter — and always
        # keeps a flight recorder ring so a crash/SIGTERM dumps the final
        # seconds even when file telemetry is off
        self.bus = bus if bus is not None else global_bus()
        if mesh == "auto":
            mesh = auto_site_mesh(self.cfg, capacity)
        self.mesh = mesh
        # multi-slice (r18): slot → slice mapping for membership events /
        # gauges — one trace id is then followable spool→slice→aggregation→
        # publish. 1 on single-slice meshes (every slot reads slice 0).
        from ..parallel.mesh import slice_count

        self.num_slices = slice_count(mesh)
        self.trainer = FederatedTrainer(
            self.cfg, get_task(self.cfg.task_id).build_model(self.cfg),
            mesh, out_dir=self.out_dir, fault_plan=fault_plan, bus=self.bus,
            attack_plan=attack_plan,
        )
        self.flight = flight if flight is not None else FlightRecorder(
            self.out_dir, bus=self.bus, tracer=self.trainer.tracer,
        )
        self.trainer._num_sites = capacity
        self.table = MembershipTable(capacity)
        self.state = None  # built lazily at first admission (needs shapes)
        self.epochs_run = 0
        self.held_rounds = 0
        self._stop = False
        self._preempted = False
        self._idle = False  # held-state latch (serve loop + ingest release)
        self._data: dict = {}  # site id -> SiteArrays
        self._dirs: dict = {}  # site id -> data dir (for resume re-admission)
        # site id -> flat config-override dict (a join event's "config" key /
        # the tree's inputspec entry): JSON-able, checkpointed in meta so
        # resume re-admits each member under its own labels/data columns
        self._overrides: dict = {}
        # site id -> trace id (a join event's "trace_id"): cross-process
        # trace propagation — flows into the membership telemetry events
        # and the checkpoint meta, so a served checkpoint can name the
        # spool events whose data trained it
        self._traces: dict = {}
        # ONE cached zero-row placeholder for free slots: _ensure_inventory's
        # content fingerprint is id()-keyed, and fresh placeholders per epoch
        # would silently re-stack + re-upload the whole inventory grid every
        # epoch whenever any slot is free
        self._empty_site = None
        self._feat = None  # feature shape, fixed at first admission
        self._rows = inventory_rows  # pinned inventory grid height
        self._steps = steps  # pinned per-epoch step-grid height
        self._compiles0 = None
        self._sink = None
        ckpt_dir = os.path.join(self.out_dir, "serve")
        self.ckpt_path = os.path.join(ckpt_dir, "checkpoint_latest.msgpack")
        if self.cfg.telemetry == "on":
            from ..telemetry.sink import FitTelemetry

            self._sink = FitTelemetry.open(
                os.path.join(
                    self.cfg.telemetry_dir
                    or os.path.join(self.out_dir, "telemetry"),
                    "serve",
                ),
                self.cfg, mesh=self.mesh, fold=0, tracer=self.trainer.tracer,
                fault_plan=fault_plan, attack_plan=attack_plan,
                tags=sink_tags,
            )
        resumed = self._resume() if resume else False
        if not resumed and data_path:
            # pre-join the tree's existing local* sites (the batch runner's
            # discovery + per-site inputspec overrides), so `--serve` on a
            # simulator tree starts training immediately and the spool only
            # carries the churn
            from ..core.config import load_inputspec

            spec_path = os.path.join(data_path, "inputspec.json")
            per_site = (
                load_inputspec(spec_path) if os.path.exists(spec_path)
                else [{}]
            )
            for i, d in enumerate(discover_site_dirs(data_path)):
                self.apply_event({
                    "event": "join", "site": f"local{i}", "data_dir": d,
                    "config": per_site[i % len(per_site)],
                })
            if self.table.occupied:
                self._on_membership_change()

    # -- logging / telemetry helpers -------------------------------------

    def _log(self, msg: str) -> None:
        if self.verbose:
            from ..trainer.logs import log_info

            log_info(msg)

    def _event(self, name: str, **attrs) -> None:
        if self._sink is not None:
            # API-boundary forward: NAME is a literal at every call site
            self._sink.event(name, **attrs)  # jaxlint: disable=R007

    # -- admission --------------------------------------------------------

    def _load_site(self, data_dir: str, overrides: dict | None = None):
        """Deadline-bounded dataset load for one joining site: a spool event
        can point at a directory still being rsynced — retry briefly, then
        reject the join instead of wedging the service (with_retry
        deadline_s semantics, robustness/retry.py). ``overrides`` is the
        site's flat config-override dict (its inputspec entry / the join
        event's "config") — per-site labels files and data columns resolve
        exactly as in the batch runner."""
        from ..robustness.retry import with_retry

        scfg = self.cfg.with_overrides(overrides or {})
        spec = get_task(scfg.task_id)

        def load():
            ds = build_site_dataset(
                spec.dataset_cls, spec.handle_cls, task_cache(scfg),
                {"baseDirectory": data_dir}, mode=scfg.mode,
            )
            return ds.as_arrays()

        return with_retry(
            load, attempts=3, base_delay=0.2,
            retry_on=(OSError, ValueError, KeyError, RuntimeError),
            deadline_s=self.admission_deadline_s,
            # per-attempt cap too: a read that HANGS (dead mount) never
            # errors, so the deadline alone would never fire — the abandoned
            # attempt runs on a daemon thread and the serve loop moves on
            timeout_s=self.admission_deadline_s,
            describe=f"site admission {data_dir}",
        )()

    def _admit(self, site: str, data_dir: str, overrides: dict | None = None):
        """Load + shape-gate one joining site's data; returns SiteArrays or
        None (rejected, with the reason logged + a telemetry event)."""
        from ..trainer.logs import log_warning

        try:
            arrays = self._load_site(data_dir, overrides)
        except (OSError, ValueError, KeyError, RuntimeError, TimeoutError) as e:
            log_warning(
                f"[serve] join rejected for {site!r}: admission failed "
                f"within deadline_s={self.admission_deadline_s} ({e})"
            )
            self._event("join-rejected", site=site, reason=str(e))
            return None
        if not len(arrays):
            log_warning(f"[serve] join rejected for {site!r}: empty dataset")
            self._event("join-rejected", site=site, reason="empty dataset")
            return None
        feat = arrays.inputs.shape[1:]
        if self._feat is None:
            self._feat = feat
        elif feat != self._feat:
            log_warning(
                f"[serve] join rejected for {site!r}: feature shape {feat} "
                f"!= the service's {self._feat}"
            )
            self._event("join-rejected", site=site, reason="shape mismatch")
            return None
        if self._rows is None:
            # pin the inventory grid at the first site's size (headroom is
            # the operator's call via inventory_rows) — every traced shape
            # is fixed from here on
            self._rows = max(len(arrays), self.cfg.batch_size)
        if len(arrays) > self._rows:
            log_warning(
                f"[serve] site {site!r} has {len(arrays)} samples; the "
                f"service's inventory grid is pinned at {self._rows} rows — "
                f"truncating (start the daemon with a larger inventory_rows "
                "for headroom)"
            )
            arrays = arrays.take(np.arange(self._rows))
        if len(arrays) < self.cfg.batch_size:
            log_warning(
                f"[serve] site {site!r} has {len(arrays)} samples < "
                f"batch_size={self.cfg.batch_size}: with drop_last batching "
                "it will yield no batches and contribute nothing"
            )
        return arrays

    # -- membership transitions -------------------------------------------

    def apply_event(self, ev: dict) -> bool:
        """Apply one spool event; returns True when membership changed.
        Invalid events are logged and skipped — a malformed spool file must
        not take the service down."""
        from ..robustness.membership import MembershipError
        from ..trainer.logs import log_warning

        kind = ev.get("event")
        if kind == "shutdown":
            self._stop = True
            self._log("[serve] shutdown event received")
            return False
        trace_id = str(ev.get("trace_id") or "") or None
        try:
            if kind == "join":
                site = str(ev["site"])
                data_dir = str(ev.get("data_dir", ""))
                overrides = ev.get("config") or {}
                arrays = self._admit(site, data_dir, overrides)
                if arrays is None:
                    self.bus.counter("serve_spool_events_total",
                                     result="rejected")
                    return False
                self.table, slot, gen = self.table.join(site)
                sl = self.table.slice_of(slot, self.num_slices)
                self._data[site] = arrays
                self._dirs[site] = data_dir
                self._overrides[site] = overrides
                if trace_id:
                    self._traces[site] = trace_id
                self._ensure_state()
                self._reset_slot(slot, site=site, generation=gen)
                self._log(
                    f"[serve] join {site!r} → slot {slot} (slice {sl}, "
                    f"generation {gen})"
                )
                self._event("membership-join", site=site, slot=slot,
                            slice=sl, generation=gen, trace=trace_id)
                self.flight.note("membership-join", site=site, slot=slot,
                                 slice=sl, trace=trace_id)
                self.bus.counter("serve_spool_events_total", result="applied")
                self.bus.gauge("serve_member_generation", gen, site=site)
                self._publish_slice_gauges()
                return True
            if kind == "leave":
                site = str(ev["site"])
                self.table, slot = self.table.leave(site)
                sl = self.table.slice_of(slot, self.num_slices)
                self._data.pop(site, None)
                self._dirs.pop(site, None)
                self._overrides.pop(site, None)
                self._traces.pop(site, None)
                self._log(
                    f"[serve] leave {site!r} (slot {slot}, slice {sl} freed)"
                )
                self._event("membership-leave", site=site, slot=slot,
                            slice=sl, trace=trace_id)
                self.flight.note("membership-leave", site=site, slot=slot,
                                 slice=sl)
                self.bus.counter("serve_spool_events_total", result="applied")
                self.bus.clear_gauge("serve_member_generation", site=site)
                self._publish_slice_gauges()
                return True
        except (MembershipError, KeyError) as e:
            log_warning(f"[serve] bad membership event {ev!r}: {e}")
            self._event("membership-error", reason=str(e))
            self.bus.counter("serve_spool_events_total", result="rejected")
            return False
        log_warning(f"[serve] unknown spool event {ev!r} — ignored")
        self.bus.counter("serve_spool_events_total", result="rejected")
        return False

    def _publish_slice_gauges(self) -> None:
        """Per-slice membership gauges (r18): one ``serve_slice_members``
        gauge per slice, so the /statusz surface shows WHERE on the sliced
        topology the federation sits — a slice draining to 0 is the
        operator's cue before the quorum trips."""
        for sl, n in enumerate(
            self.table.slice_occupancy(self.num_slices)
        ):
            self.bus.gauge("serve_slice_members", n, slice=str(sl))

    def _reset_slot(self, slot: int, site: str = "", generation: int = 0):
        """Fresh state rows for a newly-assigned slot (generation semantics:
        a rejoining site can never resurrect its previous incarnation's
        engine/health/buffer state). Emits quarantine-lift when the slot's
        previous occupant left it quarantined."""
        from ..robustness.membership import reset_slot_state

        if self.state is None:
            return
        if self.state.health is not None:
            quarantined = int(
                np.asarray(self.state.health["quarantined"])[slot]
            )
            if quarantined:
                self._log(
                    f"[serve] slot {slot} was quarantined — lifted for "
                    f"{site!r} generation {generation}"
                )
                self._event("quarantine-lift", site=site, slot=slot)
        self.state = self.trainer._place_state(
            reset_slot_state(self.state, slot, engine=self.trainer.engine)
        )

    def _ensure_state(self):
        if self.state is not None or self._feat is None:
            return
        import jax.numpy as jnp

        self.state = self.trainer.init_state(
            jnp.ones((self.cfg.batch_size,) + self._feat, jnp.float32),
            num_sites=self.capacity,
        )
        if getattr(self, "_pending_ckpt_load", False):
            # empty-membership resume (see _resume): the first join shaped
            # the template — restore the checkpointed params/state now
            from ..trainer.checkpoint import load_checkpoint

            self._pending_ckpt_load = False
            self.state = self.trainer._place_state(
                load_checkpoint(self.ckpt_path, self.state)
            )
        from ..checks.sanitize import jit_cache_size

        self._compiles0 = jit_cache_size(self.trainer.epoch_fn) or 0

    def _on_membership_change(self):
        """Post-transition housekeeping: rebalance packed slot assignment,
        refresh the occupancy mask, and checkpoint the membership epoch."""
        from ..robustness.membership import move_slot_state

        from ..parallel.mesh import slice_count

        # packing granules: one per (slice, site)-axis member — under a
        # sliced mesh rebalancing evens occupancy across slices too (the
        # per-device [K] blocks tile slice-major, parallel/mesh.py)
        num_blocks = (
            dict(self.mesh.shape)[SITE_AXIS] * slice_count(self.mesh)
            if self.mesh is not None else 1
        )
        self.table, moves = self.table.rebalance(num_blocks)
        for site, src, dst in moves:
            self._log(
                f"[serve] rebalance: {site!r} slot {src} → {dst} (packed "
                "block occupancy)"
            )
            if self.state is not None:
                self.state = self.trainer._place_state(move_slot_state(
                    self.state, src, dst, engine=self.trainer.engine
                ))
            self._event("membership-rebalance", site=site, src=src, dst=dst)
        self.trainer.membership_mask = self.table.occupancy()
        self._event("membership-epoch", epoch=self.table.epoch,
                    occupied=self.table.occupied)
        self.checkpoint()

    # -- the ingest spool --------------------------------------------------

    def ingest(self) -> bool:
        """Drain applicable spool events (sorted-filename order); an event
        with ``after_epoch`` > epochs trained stays queued. Returns True
        when membership changed."""
        from ..trainer.logs import log_warning

        changed = False
        # while HELD, release scheduled events (epochs_run is frozen; see
        # below) — but only until the first applied transition: that may be
        # the join that lifts the hold, and later-scheduled events (e.g. a
        # shutdown) must then wait for their trained-epoch mark again
        release = self._idle
        for name in sorted(os.listdir(self.spool_dir)):
            if not name.endswith(".json"):
                continue
            path = os.path.join(self.spool_dir, name)
            try:
                with open(path) as fh:
                    ev = json.load(fh)
            except (OSError, json.JSONDecodeError) as e:
                log_warning(f"[serve] unreadable spool file {path}: {e}")
                try:
                    os.replace(path, path + ".rejected")
                except OSError:
                    pass
                continue
            if not isinstance(ev, dict):
                log_warning(f"[serve] spool file {path} is not an object")
                os.remove(path)
                continue
            try:
                after = int(ev.get("after_epoch", 0) or 0)
            except (TypeError, ValueError):
                log_warning(
                    f"[serve] spool file {path}: bad after_epoch "
                    f"{ev.get('after_epoch')!r}"
                )
                try:
                    os.replace(path, path + ".rejected")
                except OSError:
                    pass
                continue
            # scheduled events wait for N TRAINED epochs — except while the
            # service is HELD (below quorum / nothing trainable): epochs_run
            # is frozen then, and the scheduled join/shutdown may be exactly
            # what lifts or ends the hold
            if after > self.epochs_run and not release:
                continue  # scheduled for later — leave it queued
            os.remove(path)
            applied = self.apply_event(ev)
            changed |= applied
            if applied:
                release = False  # the hold may have lifted — back to strict
            if self._stop:
                break
        self.bus.gauge("serve_spool_ingest_lag_s", self._spool_lag())
        return changed

    def _spool_lag(self) -> float:
        """Age in seconds of the OLDEST spool file still pending after a
        drain (scheduled events waiting their epoch mark, or backlog the
        loop hasn't reached) — the bus gauge an operator watches to see
        ingest falling behind. 0.0 with an empty spool."""
        oldest = None
        try:
            for name in os.listdir(self.spool_dir):
                if not name.endswith(".json"):
                    continue
                try:
                    mtime = os.path.getmtime(
                        os.path.join(self.spool_dir, name)
                    )
                except OSError:
                    continue  # consumed/renamed mid-scan
                if oldest is None or mtime < oldest:
                    oldest = mtime
        except OSError:
            return 0.0
        if oldest is None:
            return 0.0
        return round(max(time.time() - oldest, 0.0), 3)

    # -- scheduler surface (runner/scheduler.py, r22) ----------------------

    def set_slice_grant(self, grant) -> None:
        """Install the fleet scheduler's ``[num_slices]`` slice-grant mask
        (1.0 = this service may aggregate on that slice this round-window).
        The mask folds into the r19 slice-liveness window inside the SAME
        compiled epoch program — growing, shrinking or zeroing the grant is
        a traced-input flip plus renormalized aggregation, never a retrace.
        ``None`` removes scheduler control (full pod, r19 behavior) — but
        flipping between None and a mask CHANGES the traced program, so a
        scheduled tenant keeps a mask for its whole life."""
        self.trainer.slice_grant = (
            None if grant is None else np.asarray(grant, np.float32)
        )

    def trainable(self) -> bool:
        """Would :meth:`train_epoch` train right now (vs HOLD)? The
        scheduler's runnable predicate: granting slices to a tenant that
        would only hold wastes the grant — those slices backfill instead."""
        if self.table.occupied < self.quorum or self.state is None:
            return False
        return any(
            len(self._data[s]) >= self.cfg.batch_size
            for s in self.table.members()
        )

    def reload_checkpoint(self) -> bool:
        """Restore params/engine state from the rotating checkpoint into
        the EXISTING state template (same shapes, same sharding — the
        compiled program is untouched). The scheduler's resume half of
        checkpoint-then-yield: a preempted tenant continues bit-exact from
        what :meth:`checkpoint` saved, through the real CRC-framed msgpack
        path. Returns False when there is nothing to restore."""
        from ..trainer.checkpoint import load_checkpoint

        if self.state is None or not (
            os.path.exists(self.ckpt_path)
            or os.path.exists(self.ckpt_path + ".prev")
        ):
            return False
        self.state = self.trainer._place_state(
            load_checkpoint(self.ckpt_path, self.state)
        )
        return True

    # -- training ----------------------------------------------------------

    def _slot_sites(self) -> list:
        """The padded per-slot site list the epoch trains on: occupants'
        arrays at their slots, the shared empty placeholder (zero samples —
        the plan masks them, the occupancy mask zeroes their liveness)
        elsewhere."""
        if self._empty_site is None:
            self._empty_site = SiteArrays(
                np.zeros((0,) + self._feat, np.float32),
                np.zeros((0,), np.int32), np.zeros((0,), np.int32),
            )
        return [
            self._data[s] if s is not None else self._empty_site
            for s in self.table.slots
        ]

    def train_epoch(self):
        """One training epoch over the current membership; returns the epoch
        loss, or None when the service HELD: below the quorum floor, no
        state yet, or no member large enough to yield a batch. Each hold
        counts one epoch's worth of rounds into ``held_rounds`` (the serve
        loop then idles until membership changes, so the figure counts
        declined epochs, not poll-loop iterations)."""
        rounds = max(
            (self._steps or 1) // max(self.cfg.local_iterations, 1), 1
        )
        if self.table.occupied < self.quorum or self.state is None:
            self.held_rounds += rounds
            self._event("round-hold", occupied=self.table.occupied,
                        quorum=self.quorum)
            self._note_hold(rounds)
            return None
        if not any(
            len(self._data[s]) >= self.cfg.batch_size
            for s in self.table.members()
        ):
            # every member is smaller than the batch: drop_last batching
            # yields zero batches and the plan builder would (rightly)
            # refuse — hold rather than crash the service
            self.held_rounds += rounds
            self._event("round-hold", occupied=self.table.occupied,
                        quorum=self.quorum, reason="no trainable batch")
            self._note_hold(rounds)
            return None
        if self._steps is None:
            # pin the step grid on first contact with data (membership can
            # only change it downward-wrapping/truncating from here)
            from ..data.batching import epoch_steps

            self._steps = epoch_steps(
                [s for s in self._slot_sites() if len(s)],
                self.cfg.batch_size,
            )
            self.trainer.fixed_steps = self._steps
        self.trainer.fixed_steps = self._steps
        self.trainer.fixed_inventory_rows = self._rows
        self.epochs_run += 1
        t0 = time.perf_counter()  # the tracer's clock (duration contract)
        with self.trainer.tracer.span("epoch", epoch=self.epochs_run):
            self.state, losses = self.trainer.run_epoch(
                self.state, self._slot_sites(), self.epochs_run,
                batch_size=self.cfg.batch_size,
            )
        lived = losses[np.isfinite(losses)]
        loss = float(lived.mean()) if lived.size else float("nan")
        if self._sink is not None:
            self.trainer._fit_tel = self._sink
            self.trainer._epoch_row(0, self.epochs_run, loss, t0, self.state)
        # live metrics + flight ring: values already on the host
        self.bus.gauge("serve_epoch", self.epochs_run)
        self.bus.gauge("serve_train_loss", loss)
        self.bus.gauge("serve_members", self.table.occupied)
        self.bus.counter("serve_epochs_total")
        self.bus.observe(
            "serve_epoch_ms", (time.perf_counter() - t0) * 1e3
        )
        self.flight.note("serve-epoch", epoch=self.epochs_run, loss=loss,
                         occupied=self.table.occupied)
        self._log(
            f"[serve] epoch {self.epochs_run}: train_loss={loss:.4f} "
            f"({self.table.occupied}/{self.capacity} slots)"
        )
        # ε-budget exhaustion is a CLEAN stop for THIS daemon only: the
        # ledger (privacy/accounting.py, stepped inside run_epoch) crossing
        # the budget checkpoints the model and latches the service stop —
        # under the fleet scheduler each tenant owns its ledger, so one
        # study exhausting its budget cannot perturb another (isolation
        # proven bit-exact in tests/test_scheduler.py).
        budget = float(getattr(self.cfg, "dp_epsilon_budget", 0.0) or 0.0)
        eps = self.trainer._dp_epsilon
        if budget > 0 and eps is not None and eps >= budget:
            self._event("dp-budget", epsilon=eps, budget=budget)
            self.bus.counter("serve_dp_budget_stops_total")
            self._log(
                f"[serve] dp ε-budget exhausted: ε={eps:.3f} ≥ {budget} "
                f"— checkpointing and stopping"
            )
            self.checkpoint()
            self._stop = True
        return loss

    def _note_hold(self, rounds: int) -> None:
        self.bus.counter("serve_held_rounds_total", rounds)
        self.bus.gauge("serve_members", self.table.occupied)
        self.flight.note("round-hold", occupied=self.table.occupied,
                         quorum=self.quorum)

    def checkpoint(self):
        """Rotating checkpoint with the membership table (and member data
        dirs) embedded in the atomically-paired meta."""
        from ..trainer.checkpoint import save_checkpoint

        if self.state is None or not self.trainer._coordinator():
            return
        with self.trainer.tracer.span("checkpoint"):
            save_checkpoint(
                self.ckpt_path, self.state,
                meta={
                    "epoch": self.epochs_run,
                    "held_rounds": self.held_rounds,
                    "steps": self._steps,
                    "rows": self._rows,
                    "membership": self.table.to_json(),
                    "data_dirs": dict(self._dirs),
                    "site_overrides": dict(self._overrides),
                    # trace propagation: which spool joins' data trained
                    # the published model — the serving engine surfaces
                    # these from the checkpoint it loads
                    "traces": dict(self._traces),
                },
                rotate=True,
            )
        self._event("checkpoint-publish", epoch=self.epochs_run,
                    traces=dict(self._traces))
        self.flight.note("checkpoint-publish", epoch=self.epochs_run)
        self.bus.counter("serve_checkpoints_total")
        self._announce_publish()

    def _announce_publish(self) -> None:
        """Atomically drop ``publish.json`` beside the rotating checkpoint —
        the train-to-serve CD announcement (serving/publish.py
        CheckpointWatcher): the content digest lets a watching fleet skip
        loading the msgpack at all when the weights didn't change (held
        rounds re-checkpoint the same params)."""
        from ..trainer.checkpoint import params_digest

        note = {
            "path": self.ckpt_path,
            "epoch": self.epochs_run,
            "digest": params_digest(
                self.state.params, getattr(self.state, "batch_stats", None)
            ),
            "membership_epoch": self.table.epoch,
        }
        tmp = self.ckpt_path + ".publish.tmp"
        with open(tmp, "w") as fh:
            json.dump(note, fh)
        os.replace(tmp, os.path.join(
            os.path.dirname(self.ckpt_path), "publish.json"
        ))

    def _resume(self) -> bool:
        """Restore the service from its last checkpoint: membership table +
        member data (re-admitted from the recorded dirs) + train state —
        surviving sites' trajectories continue bit-exact. Returns False when
        there is nothing to resume from (the caller then falls back to the
        fresh-start path, pre-joining the tree's sites)."""
        from ..robustness.membership import MembershipTable
        from ..trainer.checkpoint import load_checkpoint, load_meta

        if not (
            os.path.exists(self.ckpt_path)
            or os.path.exists(self.ckpt_path + ".prev")
        ):
            self._log("[serve] resume requested but no checkpoint — "
                      "starting fresh")
            return False
        meta = load_meta(self.ckpt_path)
        self.table = MembershipTable.from_json(meta["membership"])
        if self.table.capacity != self.capacity:
            raise ValueError(
                f"checkpointed capacity {self.table.capacity} != daemon "
                f"capacity {self.capacity} — the virtual-site axis is "
                "pinned for the life of the service"
            )
        self.epochs_run = int(meta.get("epoch", 0))
        self.held_rounds = int(meta.get("held_rounds", 0))
        self._steps = meta.get("steps") or self._steps
        self._rows = meta.get("rows") or self._rows
        self._dirs = dict(meta.get("data_dirs", {}))
        self._overrides = dict(meta.get("site_overrides", {}))
        self._traces = dict(meta.get("traces", {}))
        for site, slot in sorted(
            self.table.members().items(), key=lambda kv: kv[1]
        ):
            arrays = self._admit(
                site, self._dirs.get(site, ""), self._overrides.get(site)
            )
            if arrays is None:
                raise RuntimeError(
                    f"resume: cannot re-admit member {site!r} from "
                    f"{self._dirs.get(site)!r}"
                )
            self._data[site] = arrays
        self._ensure_state()
        if self.state is not None:
            self.state = self.trainer._place_state(
                load_checkpoint(self.ckpt_path, self.state)
            )
        else:
            # a service checkpointed with ZERO members (everyone left) has
            # no data to shape a state template from — resume idle; the
            # first join builds the template and THEN restores the
            # checkpointed params (deferred load below), so the model the
            # departed federation trained is not lost
            self._pending_ckpt_load = True
            self._log("[serve] resumed with an empty membership table — "
                      "idling until a site joins")
        self.trainer.membership_mask = self.table.occupancy()
        self.trainer.fixed_steps = self._steps
        self.trainer.fixed_inventory_rows = self._rows
        self._log(
            f"[serve] resumed at epoch {self.epochs_run} with "
            f"{self.table.occupied}/{self.capacity} slots (membership "
            f"epoch {self.table.epoch})"
        )
        return True

    # -- the service loop --------------------------------------------------

    def serve(self, max_epochs: int | None = None,
              max_wall_s: float | None = None) -> dict:
        """The daemon loop: drain the spool, hold below quorum, train,
        checkpoint — until a shutdown event, SIGTERM/SIGINT (clean
        checkpointed exit), ``max_epochs`` trained epochs or ``max_wall_s``
        wall-clock. Returns a summary dict (and writes the telemetry
        summary row when telemetry is on)."""
        from ..robustness.preemption import PreemptionGuard

        t0 = time.monotonic()
        trained_here = 0
        # held-state latch (self._idle): after a hold (below quorum / no
        # state / nothing trainable) the loop idles on the spool instead of
        # re-holding every poll iteration — held_rounds counts declined
        # EPOCHS, only a membership change lifts the hold, and ingest()
        # releases after_epoch-scheduled events while held (epochs_run is
        # frozen then, and a scheduled join/shutdown may be the lift)
        self._idle = False
        with PreemptionGuard() as guard:
            while not self._stop:
                changed = self.ingest()
                if changed:
                    self._on_membership_change()
                    self._idle = False
                if self._stop:
                    break
                loss = None
                if not self._idle:
                    loss = self.train_epoch()
                    if loss is None:
                        self._idle = True
                    else:
                        trained_here += 1
                        self.checkpoint()
                if guard.requested is not None:
                    self._preempted = True
                    self._log(
                        f"[serve] signal {guard.requested} — checkpointed, "
                        "shutting down"
                    )
                    self.checkpoint()
                    # the guard owns the signal handlers here, so the
                    # flight recorder dumps cooperatively: final spans +
                    # bus snapshot land in flight_<pid>.json before exit
                    self.flight.note("signal", signum=guard.requested)
                    self.flight.dump(f"signal:{guard.requested}")
                    break
                if max_epochs is not None and trained_here >= max_epochs:
                    break
                if max_wall_s is not None and time.monotonic() - t0 >= max_wall_s:
                    break
                if loss is None and not changed:
                    # idle (held below quorum, empty spool): poll gently
                    time.sleep(self.poll_s)
        return self.close()

    # -- live observability (exporter plumbing) ----------------------------

    def health_probes(self) -> dict:
        """Per-subsystem readiness for ``/healthz``: the service is ready
        when it has a state template, meets quorum, and can reach its
        spool."""
        return {
            "state": lambda: self.state is not None,
            "quorum": lambda: self.table.occupied >= self.quorum,
            "spool": lambda: os.path.isdir(self.spool_dir),
        }

    def status(self) -> dict:
        """The live ``/statusz`` payload: what round the service is on,
        who is a member (with generations and propagated trace ids), and
        the hold/ingest state — everything an operator previously had to
        infer from logs after the fact."""
        return {
            "mode": "daemon",
            "task_id": self.cfg.task_id,
            "epoch": self.epochs_run,
            "held_rounds": self.held_rounds,
            "capacity": self.capacity,
            "quorum": self.quorum,
            "occupied": self.table.occupied,
            "holding": self._idle,
            "members": {
                site: {
                    "slot": slot,
                    "slice": self.table.slice_of(slot, self.num_slices),
                    "generation": self.table.generation_of(site),
                    "samples": len(self._data.get(site, ())),
                    "trace_id": self._traces.get(site),
                }
                for site, slot in sorted(self.table.members().items())
            },
            "num_slices": self.num_slices,
            # r19 slice elasticity: the slice-quorum floor (trainer/steps.py
            # holds rounds below it) — surfaced so an operator reading
            # /statusz sees WHY rounds are holding under slice faults
            "min_slices": self.cfg.min_slices,
            # r22 fleet scheduler: the current slice-grant mask (None = the
            # service owns the whole pod) — /statusz shows WHICH slices the
            # scheduler has this tenant on right now
            "slice_grant": (
                None if self.trainer.slice_grant is None
                else [float(g) for g in np.asarray(self.trainer.slice_grant)]
            ),
            "slice_occupancy": self.table.slice_occupancy(self.num_slices),
            "membership_epoch": self.table.epoch,
            "steps": self._steps,
            "inventory_rows": self._rows,
            "spool_dir": self.spool_dir,
            "spool_lag_s": self._spool_lag(),
            "preempted": self._preempted,
        }

    def close(self) -> dict:
        """Final checkpoint + telemetry summary; returns the service
        summary."""
        from ..checks.sanitize import jit_cache_size
        from ..robustness.membership import membership_rollup

        self.checkpoint()
        rollup = membership_rollup(
            self.table, self.state, held_rounds=self.held_rounds
        )
        summary = {
            "epochs_run": self.epochs_run,
            "held_rounds": self.held_rounds,
            "membership": rollup,
            "table": self.table.to_json(),
            "preempted": self._preempted,
        }
        if self._sink is not None:
            compiles = (
                (jit_cache_size(self.trainer.epoch_fn) or 0)
                - (self._compiles0 or 0)
            )
            self._sink.append({
                "kind": "summary", "fold": 0,
                "epochs_run": self.epochs_run,
                "epoch_compiles": compiles,
                "best_val_epoch": 0,
                "membership": rollup,
            })
            self._sink.close()
            self._sink = None
        return summary
