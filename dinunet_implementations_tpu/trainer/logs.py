"""Output/observability writers — byte-compatible with what the reference
notebooks consume (SURVEY.md §5 metrics/logging):

- ``logs.json`` keys: ``agg_engine``, ``test_metrics`` (nested list, e.g.
  ``[[loss, auc]]``), ``best_val_epoch``, ``cumulative_total_duration`` (list,
  cumulative — last entry is the total), ``time_spent_on_computation``
  (per-round list), ``local_iter_duration`` / ``remote_iter_duration``
  (``nnlogs.ipynb`` cell 2; ``NB.ipynb`` cells 2-3, 34-36);
- ``test_metrics.csv``: header + one row where columns [1]=accuracy, [2]=f1
  (parsed by ``NB.ipynb`` cell 6);
- directory layout ``<out>/<site>/simulatorRun/<task_id>/fold_<k>/`` as read
  back by ``NB.ipynb`` cells 33-35, plus the remote's zipped global results
  (``nnlogs.ipynb`` cell 2 unzips it).

The point: the reference's analysis notebooks should run unmodified against
our outputs (SURVEY.md §7 'cheap, strong parity check').
"""

from __future__ import annotations

import json
import logging
import os
import shutil
import sys
import zipfile

# the ONE duration-list helper now lives with the span tracer
# (telemetry/tracer.py); re-exported here for the established import path
from ..telemetry.tracer import duration  # noqa: F401

# ---------------------------------------------------------------------------
# Level-gated logger — the ONE sanctioned output path for library code
# (jaxlint R001: print() is reserved for CLI/demo/report surfaces). Messages
# go to stdout in plain form, byte-compatible with the print() lines they
# replaced, but gated by DINUNET_LOG_LEVEL (default INFO) so hot-path
# progress lines can be silenced without touching verbose flags.
# ---------------------------------------------------------------------------

_LOGGER_NAME = "dinunet_implementations_tpu"


class _StdoutHandler(logging.StreamHandler):
    """StreamHandler that resolves sys.stdout at emit time (pytest capsys /
    notebook redirections swap the stream object after import)."""

    def emit(self, record):
        self.stream = sys.stdout
        super().emit(record)


def get_logger() -> logging.Logger:
    logger = logging.getLogger(_LOGGER_NAME)
    if not logger.handlers:
        handler = _StdoutHandler()
        handler.setFormatter(logging.Formatter("%(message)s"))
        logger.addHandler(handler)
        logger.propagate = False
        level = os.environ.get("DINUNET_LOG_LEVEL", "INFO").upper()
        logger.setLevel(getattr(logging, level, logging.INFO))
    return logger


def log_info(msg: str) -> None:
    """Progress lines (per-epoch readouts, pretrain status)."""
    get_logger().info(msg)


def log_warning(msg: str) -> None:
    """Recoverable-but-noteworthy conditions (clamps, empty splits)."""
    get_logger().warning(msg)


def fold_dir(out_dir: str, site: str, task_id: str, fold: int) -> str:
    d = os.path.join(out_dir, site, "simulatorRun", task_id, f"fold_{fold}")
    os.makedirs(d, exist_ok=True)
    return d


def write_logs_json(
    dirpath: str,
    agg_engine: str,
    test_metrics: list,
    best_val_epoch: int,
    cumulative_total_duration: list,
    time_spent_on_computation: list,
    iter_durations: list,
    side: str = "local",
    extra: dict | None = None,
) -> str:
    log = {
        "agg_engine": agg_engine,
        "test_metrics": test_metrics,
        "best_val_epoch": int(best_val_epoch),
        "cumulative_total_duration": [round(x, 6) for x in cumulative_total_duration],
        "time_spent_on_computation": [round(x, 6) for x in time_spent_on_computation],
        f"{side}_iter_duration": [round(x, 6) for x in iter_durations],
    }
    if extra:
        log.update(extra)
    os.makedirs(dirpath, exist_ok=True)
    path = os.path.join(dirpath, "logs.json")
    with open(path, "w") as fh:
        json.dump(log, fh, indent=2)
    return path


def health_log_fields(site_health: dict | None, site_index: int | None = None) -> dict:
    """``logs.json`` fields for the per-site fault-tolerance counters
    (robustness/health.py): rounds each site skipped (scheduled drop,
    non-finite gradient, or quarantine) and whether it ended the fit
    quarantined. ``site_index=None`` returns the remote-side full lists;
    an index returns that one site's scalars (for ``local{i}/logs.json``).
    Returns ``{}`` when no health state was tracked (e.g. ``mode="test"``)."""
    if not site_health:
        return {}
    if site_index is None:
        out = {
            "site_skipped_rounds": list(site_health["site_skipped_rounds"]),
            "site_quarantined": list(site_health["site_quarantined"]),
        }
        if "site_anomaly_score" in site_health:  # reputation layer (r17)
            out["site_anomaly_score"] = [
                round(v, 6) for v in site_health["site_anomaly_score"]
            ]
            out["site_suspect_streak"] = list(
                site_health["site_suspect_streak"]
            )
        return out
    out = {
        "skipped_rounds": site_health["site_skipped_rounds"][site_index],
        "quarantined": site_health["site_quarantined"][site_index],
    }
    if "site_anomaly_score" in site_health:
        out["anomaly_score"] = round(
            site_health["site_anomaly_score"][site_index], 6
        )
        out["suspect_streak"] = site_health["site_suspect_streak"][site_index]
    return out


def telemetry_log_fields(summary: dict | None, site_index: int | None = None) -> dict:
    """``logs.json`` fields for the per-site telemetry rollup
    (telemetry/metrics.py ``telemetry_summary``): grad-norm statistics next
    to the health counters, so the notebook-facing contract surfaces them
    too. ``site_index=None`` returns the remote-side full lists; an index
    returns that one site's scalars (for ``local{i}/logs.json``). ``{}``
    when telemetry was off."""
    if not summary:
        return {}
    if site_index is None:
        return {
            "site_grad_norm_last": list(summary["site_grad_norm_last"]),
            "site_grad_norm_max": list(summary["site_grad_norm_max"]),
            "site_grad_norm_mean": list(summary["site_grad_norm_mean"]),
            "site_residual_norm_mean": list(summary["site_residual_norm_mean"]),
            "update_norm_last": summary["update_norm_last"],
            "payload_bytes_per_round": summary["payload_bytes_per_round"],
            # r18 per-tier split: the inter-slice hop's per-slice figure
            # (0.0 on single-slice runs)
            "dcn_bytes_per_round": summary.get("dcn_bytes_per_round", 0.0),
        }
    return {
        "grad_norm_last": summary["site_grad_norm_last"][site_index],
        "grad_norm_max": summary["site_grad_norm_max"][site_index],
        "grad_norm_mean": summary["site_grad_norm_mean"][site_index],
        "residual_norm_mean": summary["site_residual_norm_mean"][site_index],
    }


def privacy_log_fields(results: dict) -> dict:
    """``logs.json`` fields for the spent differential privacy (r20,
    privacy/accounting.py): the fit's final (ε, δ) next to the health and
    telemetry rollups — absent entirely when the DP mechanism was off or
    noiseless (no guarantee to misreport)."""
    if "dp_epsilon" not in results:
        return {}
    return {
        "dp_epsilon": results["dp_epsilon"],
        "dp_delta": results["dp_delta"],
    }


def write_test_metrics_csv(dirpath: str, fold: int, metrics: dict) -> str:
    """``metrics``: mapping name → value; accuracy and f1 must be present (the
    notebook indexes columns 1 and 2)."""
    names = ["accuracy", "f1"] + [k for k in metrics if k not in ("accuracy", "f1")]
    names = names if metrics else []  # no classes: trainer/metrics.py NoClassMetrics
    os.makedirs(dirpath, exist_ok=True)
    path = os.path.join(dirpath, "test_metrics.csv")
    with open(path, "w") as fh:
        fh.write(",".join(["fold"] + names) + "\n")
        fh.write(",".join([f"fold_{fold}"] + [f"{metrics[n]:.5f}" for n in names]) + "\n")
    return path


def zip_global_results(
    out_dir: str, remote_site: str = "remote", num_sites: int = 0,
    task_id: str | None = None,
) -> str:
    """Zip the remote's result tree into the transfer output, like the
    reference remote does, and distribute a copy into each local site's
    output dir (the COINSTAC remote's transfer lands in every site's
    output). ``nnlogs.ipynb`` cell 2 walks a site dir, finds the ``.zip``
    NEXT TO the task dir, and extracts ``fold_k/logs.json`` from it — so
    the zip lives inside ``simulatorRun/``, beside ``<task_id>/``, and
    archive paths start at the FOLD level (``fold_k/...``).

    ``task_id`` selects which task dir to archive (two tasks sharing one
    out_dir would otherwise collide on ``fold_k/`` archive names); ``None``
    falls back to the single task dir present and raises when ambiguous.
    """
    remote_dir = os.path.join(out_dir, remote_site, "simulatorRun")
    if task_id is None:
        tasks = [t for t in sorted(os.listdir(remote_dir))
                 if os.path.isdir(os.path.join(remote_dir, t))]
        if len(tasks) != 1:
            raise ValueError(
                f"out_dir holds {len(tasks)} task dirs {tasks}; pass task_id"
            )
        task_id = tasks[0]
    task_dir = os.path.join(remote_dir, task_id)
    zpath = os.path.join(remote_dir, "global_results.zip")
    with zipfile.ZipFile(zpath, "w") as zf:
        for root, _, files in os.walk(task_dir):
            for f in files:
                full = os.path.join(root, f)
                zf.write(full, os.path.relpath(full, task_dir))
    for i in range(num_sites):
        site_dir = os.path.join(out_dir, f"local{i}", "simulatorRun")
        if os.path.isdir(site_dir):
            shutil.copyfile(
                zpath, os.path.join(site_dir, "global_results.zip")
            )
    return zpath
