"""Per-fit telemetry artifacts: ``manifest.json`` + ``metrics.jsonl`` +
trace files.

One :class:`FitTelemetry` per fit (fold), rooted at
``<out_dir>/telemetry/fold_<k>/`` (or ``TrainConfig.telemetry_dir``):

- ``manifest.json`` — written at open: config hash, jax/jaxlib versions,
  backend with its device kind and count, mesh topology, engine/task, git
  rev, package version. The "what exactly ran" record every
  perf/robustness claim should ship with.
- ``metrics.jsonl`` — appended as the fit runs (one fsync-free line per
  record, crash-tolerant): per-epoch rows (loss, per-site grad/residual
  norms, transfer bytes, epoch seconds), instant events (checkpoint,
  preempted, quarantine), and a final summary row (compile count, prefetch
  stall, site health).
- ``trace.jsonl`` / ``trace.chrome.json`` — the span tracer's two output
  forms, written at close (open the chrome one in Perfetto).

The validators at the bottom are the schema contract: the report CLI's
``--validate`` mode (and the CI telemetry smoke job) gate on them.
"""

from __future__ import annotations

import dataclasses
import hashlib
import json
import math
import numbers
import os
import subprocess

from .tracer import SpanTracer

SCHEMA_VERSION = 1

MANIFEST_FILE = "manifest.json"
METRICS_FILE = "metrics.jsonl"
TRACE_JSONL_FILE = "trace.jsonl"
TRACE_CHROME_FILE = "trace.chrome.json"

#: manifest keys every consumer may rely on. fault_plan / attack_plan are
#: REQUIRED (null when no chaos/attack was injected): a fault- or
#: attack-arm's artifact must be reproducible from the manifest alone —
#: before r17 only the config hash landed there and the active plan JSON
#: lived in the shell history. privacy (r20) is the same contract for the
#: DP/secure-agg/personalization knobs: a DP run's artifact carries the
#: exact mechanism parameters its ε claim depends on (null when the whole
#: privacy plane is off).
#: tags (r22) is the fleet-scheduler identity contract: a tenant's
#: artifact carries {"tenant": "<id>"} so a pod packing many studies
#: yields per-study artifacts that self-identify (null for solo fits).
MANIFEST_REQUIRED = frozenset({
    "schema_version", "config_hash", "task_id", "agg_engine", "num_sites",
    "pipeline", "fold", "jax_version", "jaxlib_version", "backend",
    "device_kind", "device_count", "mesh", "package_version", "git_rev",
    "fault_plan", "attack_plan", "privacy", "tags",
})

#: required metrics.jsonl keys by row kind
ROW_REQUIRED = {
    "epoch": frozenset({
        "kind", "fold", "epoch", "train_loss", "epoch_seconds",
        "transfer_bytes", "site_grad_sq_last", "site_grad_sq_sum",
        "site_residual_sq_sum", "update_sq_last", "payload_bytes",
        # r18 per-tier wire split: inter-slice (DCN) bytes, 0.0 off-slice
        "dcn_bytes", "rounds",
        # r20 privacy plane: spent ε so far (null = DP off/noiseless) —
        # required, so a DP run's per-epoch ε trail cannot silently vanish
        "dp_epsilon",
    }),
    "event": frozenset({"kind", "name"}),
    "summary": frozenset({
        "kind", "fold", "epochs_run", "epoch_compiles", "best_val_epoch",
        # elastic-rounds rollup (robustness/membership.py membership_rollup):
        # a dict for daemon-mode serves, null for batch-job fits — the key
        # itself is part of the schema contract
        "membership",
    }),
    # serving path (r15, serving/engine.py): one row per microbatch dispatch
    # (queue/padding visibility) ...
    "dispatch": frozenset({
        "kind", "lane", "bucket", "rows", "pad_rows", "queue_depth",
    }),
    # ... and the run's rollup. The latency percentiles are REQUIRED keys —
    # the CI serving smoke gates on `report --validate`, so a serving run
    # that lost its latency record cannot validate.
    "serve_summary": frozenset({
        "kind", "task_id", "requests", "samples", "dispatches",
        "latency_ms_p50", "latency_ms_p95", "latency_ms_p99",
        "requests_per_s", "samples_per_s", "pad_waste_pct",
        "bucket_hit_rate", "warmup_seconds", "compiles_after_warmup",
    }),
    # train-to-serve CD (r21, serving/publish.py): one row per attempted
    # publish — outcome is "swapped" / "rejected-shadow" / "rejected-stale";
    # pause_ms is the donated-swap wall time (null when nothing swapped)
    "publish": frozenset({
        "kind", "digest", "outcome", "pause_ms", "shadow",
    }),
    # ... and one per SLO-burn rollback decision after a swap: burn is the
    # post-swap window's error-budget burn, rolled_back whether the previous
    # weights were grafted back
    "rollback": frozenset({
        "kind", "digest", "burn", "rolled_back", "window_samples",
    }),
}


def _finite(value):
    """Recursively replace non-finite reals with ``None`` (see
    :meth:`FitTelemetry.append` — strict-JSON output contract). Covers
    numpy float scalars too, so a stray un-cast ``np.float32(nan)`` cannot
    slip past to ``allow_nan=False`` and crash the append."""
    if isinstance(value, numbers.Real) and not isinstance(
            value, numbers.Integral):
        f = float(value)
        return f if math.isfinite(f) else None
    if isinstance(value, dict):
        return {k: _finite(v) for k, v in value.items()}
    if isinstance(value, (list, tuple)):
        return [_finite(v) for v in value]
    return value


def _git_rev(repo_hint: str | None = None) -> str:
    """Best-effort ``git rev-parse HEAD`` of the code's checkout; "" when the
    package runs from a wheel / outside any repo."""
    cwd = repo_hint or os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    try:
        out = subprocess.run(
            ["git", "rev-parse", "HEAD"], cwd=cwd, capture_output=True,
            text=True, timeout=5,
        )
        return out.stdout.strip() if out.returncode == 0 else ""
    except (OSError, subprocess.SubprocessError):
        # no git binary / not a checkout: the manifest simply records ""
        return ""


def config_hash(cfg) -> str:
    """Stable hash of a TrainConfig (or any jsonable mapping/dataclass)."""
    if dataclasses.is_dataclass(cfg):
        cfg = dataclasses.asdict(cfg)
    blob = json.dumps(cfg, sort_keys=True, default=str)
    return hashlib.sha256(blob.encode()).hexdigest()[:16]


def mesh_topology(mesh) -> dict | None:
    """``{axis: size}`` for a mesh, ``None`` for the vmap-folded path."""
    if mesh is None:
        return None
    return {str(k): int(v) for k, v in dict(mesh.shape).items()}


def tree_devices(tree) -> list[str]:
    """Sorted ``"<platform>:<id>"`` of every device holding (a shard of) any
    array leaf of ``tree`` — where a state actually lives, as opposed to
    where the mesh says it should."""
    import jax

    return sorted({
        f"{d.platform}:{d.id}"
        for leaf in jax.tree.leaves(tree) if isinstance(leaf, jax.Array)
        for d in leaf.devices()
    })


def privacy_manifest(cfg) -> dict | None:
    """The active privacy-plane configuration, verbatim (r20) — ``None``
    when the whole plane is off (dp off, secure_agg off, no personalized
    heads), so a legacy run's manifest reads exactly like before with one
    extra null key. The dict carries every knob the artifact's ε /
    masked-wire / personalization claims depend on: a DP run is
    reproducible from the manifest alone."""
    dp_clip = float(getattr(cfg, "dp_clip", 0.0) or 0.0)
    dp_noise = float(getattr(cfg, "dp_noise_multiplier", 0.0) or 0.0)
    secure = getattr(cfg, "secure_agg", "off") or "off"
    personalize = tuple(getattr(cfg, "personalize", ()) or ())
    if dp_clip <= 0.0 and dp_noise <= 0.0 and secure == "off" \
            and not personalize:
        return None
    return {
        "dp_clip": dp_clip,
        "dp_noise_multiplier": dp_noise,
        "dp_seed": int(getattr(cfg, "dp_seed", 0) or 0),
        "dp_delta": float(getattr(cfg, "dp_delta", 1e-5)),
        "dp_epsilon_budget": float(
            getattr(cfg, "dp_epsilon_budget", 0.0) or 0.0
        ),
        "secure_agg": secure,
        "secure_agg_seed": int(getattr(cfg, "secure_agg_seed", 0) or 0),
        "personalize": list(personalize),
    }


def build_manifest(cfg, mesh=None, fold: int = 0, fault_plan=None,
                   attack_plan=None, tags: dict | None = None) -> dict:
    import jax
    import jaxlib

    from .. import __version__

    return {
        "schema_version": SCHEMA_VERSION,
        "config_hash": config_hash(cfg),
        "task_id": cfg.task_id,
        "agg_engine": cfg.agg_engine,
        "num_sites": int(getattr(cfg, "num_sites", 1)),
        "pipeline": cfg.pipeline,
        "fold": int(fold),
        "jax_version": jax.__version__,
        "jaxlib_version": jaxlib.__version__,
        "backend": jax.default_backend(),
        # which silicon: a number read off this artifact is a device number
        # only if these say so (a CPU run reads "cpu" / "cpu" / N)
        "device_kind": jax.devices()[0].device_kind,
        "device_count": jax.device_count(),
        "mesh": mesh_topology(mesh),
        "package_version": __version__,
        "git_rev": _git_rev(),
        # the active chaos/attack plans, verbatim (null = none): a fault or
        # attack arm is reproducible from the artifact alone (r17)
        "fault_plan": fault_plan.to_json() if fault_plan is not None else None,
        "attack_plan": (
            attack_plan.to_json() if attack_plan is not None else None
        ),
        # the active privacy-plane knobs, verbatim (r20; null = plane off):
        # DP runs are reproducible from the artifact alone
        "privacy": privacy_manifest(cfg),
        # scheduler identity tags (r22; null = solo fit): which tenant of a
        # packed pod this artifact belongs to — the per-tenant isolation
        # story is auditable from the artifacts alone
        "tags": dict(tags) if tags else None,
        "config": cfg.to_dict(),
    }


class FitTelemetry:
    """The per-fit artifact sink. Construct via :meth:`open`; feed epoch rows
    and events as the fit runs; :meth:`close` writes the trace files (called
    from the trainer's ``finally``, so ``Preempted``/crashes still leave
    complete artifacts)."""

    def __init__(self, dirpath: str, tracer: SpanTracer):
        self.dir = dirpath
        self.tracer = tracer
        self._closed = False
        os.makedirs(dirpath, exist_ok=True)

    @classmethod
    def open(cls, dirpath: str, cfg, mesh=None, fold: int = 0,
             tracer: SpanTracer | None = None, fault_plan=None,
             attack_plan=None, tags: dict | None = None) -> "FitTelemetry":
        sink = cls(dirpath, tracer or SpanTracer())
        manifest = build_manifest(
            cfg, mesh=mesh, fold=fold, fault_plan=fault_plan,
            attack_plan=attack_plan, tags=tags,
        )
        with open(os.path.join(dirpath, MANIFEST_FILE), "w") as fh:
            json.dump(manifest, fh, indent=2, default=str)
        # truncate any stale rows from a previous run of this fold — rows
        # within ONE fit then append crash-tolerantly
        open(os.path.join(dirpath, METRICS_FILE), "w").close()
        return sink

    def append(self, row: dict) -> None:
        """One metrics.jsonl record (kind: epoch | event | summary).

        Strict RFC 8259 output: Python's ``json.dumps`` would happily emit a
        bare ``NaN`` token (valid for json.loads, fatal for JSON.parse / jq /
        most JSONL ingesters), and NaN is exactly what ``grad_sq_last`` and
        an all-dead epoch's ``train_loss`` carry by design — so non-finite
        floats are serialized as ``null`` (null == "non-finite here", the
        blow-up signal survives), enforced by ``allow_nan=False``."""
        if self._closed:
            return
        with open(os.path.join(self.dir, METRICS_FILE), "a") as fh:
            fh.write(
                json.dumps(_finite(row), default=float, allow_nan=False)
                + "\n"
            )

    def event(self, name: str, **attrs) -> None:
        """Instant event, recorded in BOTH artifacts: the trace (timeline
        position) and metrics.jsonl (greppable next to the epoch rows)."""
        # API-boundary forward: the NAME was already a literal/constant at
        # this method's (linted) call site
        self.tracer.event(name, **attrs)  # jaxlint: disable=R007
        self.append({"kind": "event", "name": name, **attrs})

    def close(self) -> None:
        if self._closed:
            return
        self._closed = True
        self.tracer.write_jsonl(os.path.join(self.dir, TRACE_JSONL_FILE))
        self.tracer.write_chrome_trace(
            os.path.join(self.dir, TRACE_CHROME_FILE)
        )


# ---------------------------------------------------------------------------
# schema validation — the contract CI gates on
# ---------------------------------------------------------------------------


def validate_manifest(manifest: dict) -> list[str]:
    """Problems with a manifest dict ([] == valid)."""
    problems = []
    if not isinstance(manifest, dict):
        return [f"manifest is {type(manifest).__name__}, not an object"]
    missing = MANIFEST_REQUIRED - set(manifest)
    if missing:
        problems.append(f"manifest missing keys: {sorted(missing)}")
    if manifest.get("schema_version") not in (SCHEMA_VERSION,):
        problems.append(
            f"manifest schema_version {manifest.get('schema_version')!r} != "
            f"{SCHEMA_VERSION}"
        )
    return problems


def validate_metrics_rows(rows: list[dict]) -> list[str]:
    """Problems with a metrics.jsonl row list ([] == valid). Unknown kinds
    are findings (a typo'd kind would silently vanish from the report)."""
    problems = []
    for i, row in enumerate(rows):
        kind = row.get("kind")
        required = ROW_REQUIRED.get(kind)
        if required is None:
            problems.append(f"row {i}: unknown kind {kind!r}")
            continue
        missing = required - set(row)
        if missing:
            problems.append(f"row {i} ({kind}): missing {sorted(missing)}")
    return problems


def load_metrics(path: str) -> list[dict]:
    rows = []
    with open(path) as fh:
        for line in fh:
            line = line.strip()
            if line:
                rows.append(json.loads(line))
    return rows
