"""Command-line entry point — the operational surface of the build.

The reference is driven by ``python entry.py`` inside a COINSTAC container
(``Dockerfile:20``) or by the standalone ``comps/*/site_run.py`` scripts.
Here one CLI covers both:

    # federated run over a simulator tree (the COINSTAC-simulator replacement)
    dinunet-tpu --data-path datasets/test_fsl --task FS-Classification \
        --engine dSGD --epochs 101 --out-dir out

    # single-site debug harness (SiteRunner parity)
    dinunet-tpu --data-path datasets/test_fsl --site 0 --epochs 20

    # resume / inference-only
    dinunet-tpu --data-path ... --resume
    dinunet-tpu --data-path ... --mode test

Any TrainConfig field (or task-args field) can be overridden with
``--set key=value`` (repeatable; values parse as JSON when possible, e.g.
``--set split_ratio=[0.7,0.15,0.15]`` or ``--set hidden_size=348``).
"""

from __future__ import annotations

import argparse
import json
import sys

from ..core.config import AggEngine, NNComputation, TrainConfig


def _parse_set(pairs: list[str]) -> dict:
    out = {}
    for pair in pairs:
        if "=" not in pair:
            raise SystemExit(f"--set expects key=value, got {pair!r}")
        k, v = pair.split("=", 1)
        try:
            out[k] = json.loads(v)
        except json.JSONDecodeError:
            out[k] = v  # bare string
    return out


def build_parser() -> argparse.ArgumentParser:
    p = argparse.ArgumentParser(
        prog="dinunet-tpu",
        description="TPU-native federated training (dinunet capabilities).",
    )
    p.add_argument("--data-path", required=True,
                   help="dataset tree (reference simulator layout: "
                        "input/local*/simulatorRun + inputspec.json)")
    p.add_argument("--task", default=None, choices=list(NNComputation.ALL),
                   help="task id (default: TrainConfig/inputspec default)")
    p.add_argument("--engine", default=None, choices=list(AggEngine.ALL),
                   help="aggregation engine")
    p.add_argument("--mode", default=None, choices=["train", "test"])
    p.add_argument("--epochs", type=int, default=None)
    p.add_argument("--batch-size", type=int, default=None)
    p.add_argument("--num-folds", type=int, default=None)
    p.add_argument("--model-axis-size", type=int, default=None,
                   help="sequence parallelism: shard the model's sequence "
                        "axis over this many devices per site")
    p.add_argument("--sites-per-device", type=int, default=None,
                   help="site packing: K virtual sites per mesh device with "
                        "two-level aggregation (512+ sites on an 8-device "
                        "mesh; see docs/ARCHITECTURE.md Site virtualization)")
    p.add_argument("--slices", type=int, default=None,
                   help="multi-slice scale-out (r18): lay the site tier "
                        "over this many slices — intra-slice aggregation "
                        "rides ICI, one inter-slice hop per round crosses "
                        "DCN (docs/ARCHITECTURE.md Multi-slice)")
    p.add_argument("--dcn-wire-quant", default=None,
                   choices=["none", "bf16", "int8", "fp8"],
                   help="inter-slice wire codec, independent of "
                        "--wire-quant (default: follow it); quantizes the "
                        "per-slice partial on the slow DCN hop only")
    p.add_argument("--min-slices", type=int, default=None,
                   help="slice-quorum floor (r19): a round with fewer LIVE "
                        "slices than this HOLDS (params/opt frozen, NaN "
                        "loss, held_rounds telemetry) instead of training "
                        "on a rump cohort; needs --slices > 1 and a "
                        "--faults plan with slice windows "
                        "(slice_drop_at / slice_delay_at / kill_slice_at)")
    p.add_argument("--out-dir", default=None,
                   help="output root (default <data-path>/output)")
    p.add_argument("--site", type=int, default=None,
                   help="single-site mode: run only this site index "
                        "(SiteRunner parity)")
    p.add_argument("--serve", action="store_true",
                   help="daemon mode (elastic rounds, r13): a persistent "
                        "service over one compiled epoch program with a "
                        "fixed virtual-site axis; sites join/leave/rejoin "
                        "via JSON events in the ingest spool "
                        "(runner/fed_runner.py FedDaemon). The tree's "
                        "local* sites pre-join; combine with --set "
                        "staleness_bound=N for buffered-async aggregation")
    p.add_argument("--serve-spool", default=None, metavar="DIR",
                   help="ingest spool directory (default "
                        "<data-path>/spool): join/leave/shutdown events as "
                        "*.json files, processed in sorted order")
    p.add_argument("--serve-capacity", type=int, default=None,
                   help="virtual-site slots (S_max) — fixes every traced "
                        "shape for the life of the service; default: the "
                        "discovered site count")
    p.add_argument("--serve-quorum", type=int, default=1,
                   help="minimum occupied slots; below it rounds HOLD "
                        "rather than aggregate (default 1)")
    p.add_argument("--serve-epochs", type=int, default=None,
                   help="stop after this many trained epochs (default: "
                        "serve until a shutdown event or SIGTERM)")
    p.add_argument("--serve-poll", type=float, default=0.5,
                   help="idle spool poll interval in seconds (default 0.5)")
    p.add_argument("--serve-rows", type=int, default=None,
                   help="pinned inventory rows per slot (headroom for "
                        "bigger sites joining later; default: the first "
                        "admitted site's size)")
    p.add_argument("--schedule", action="store_true",
                   help="fleet-scheduler mode (r22): pack multiple "
                        "concurrent studies (tenants) onto the shared "
                        "slice pool with weighted fair share, "
                        "checkpoint-then-yield preemption and serving "
                        "backfill. --data-path is the scheduler ROOT: "
                        "tenants register via <root>/spool/*.json events "
                        "and live under <root>/tenants/<id>/ "
                        "(runner/scheduler.py FleetScheduler)")
    p.add_argument("--pod-slices", type=int, default=1, metavar="N",
                   help="scheduler mode: width of the shared slice pool "
                        "the fair-share loop allocates (default 1)")
    p.add_argument("--sched-wall-s", type=float, default=None, metavar="S",
                   help="scheduler mode: stop after S wall-clock seconds "
                        "(default: run until every tenant is done or a "
                        "shutdown event/signal arrives)")
    p.add_argument("--sched-ticks", type=int, default=None, metavar="N",
                   help="scheduler mode: stop after N scheduling ticks")
    p.add_argument("--statusz-port", type=int, default=None, metavar="PORT",
                   help="daemon mode: serve live observability endpoints on "
                        "127.0.0.1:PORT — /metrics (Prometheus text), "
                        "/healthz (per-subsystem readiness), /statusz "
                        "(JSON snapshot incl. SLO burn), /tracez (recent "
                        "spans). PORT 0 picks a free port (printed at "
                        "startup). telemetry/exporter.py")
    p.add_argument("--slo-p99-ms", type=float, default=None, metavar="MS",
                   help="p99 target for the /statusz SLO error-budget burn, "
                        "computed over the live epoch-latency histogram "
                        "(daemon) — burn > 1.0 means the error budget is "
                        "being spent faster than allowed")
    p.add_argument("--folds", type=int, nargs="*", default=None,
                   help="run only these fold indices")
    p.add_argument("--resume", action="store_true",
                   help="resume each fold from its latest checkpoint")
    p.add_argument("--faults", default=None, metavar="JSON|@FILE",
                   help="deterministic fault injection (robustness/faults.py "
                        "FaultPlan): inline JSON or @path — e.g. "
                        '\'{"drop": [[3, 10, -1]], "nan_at": [[5, 1]], '
                        '"kill_at_round": 20}\'. Site drops / NaN poisoning / '
                        "simulated preemption replay identically run to run")
    p.add_argument("--profile-dir", default=None,
                   help="write a jax.profiler trace per fold here")
    p.add_argument("--telemetry", default=None, choices=["on", "off"],
                   help="unified telemetry (telemetry/): span tracer + "
                        "on-device per-round per-site metrics + "
                        "manifest.json/metrics.jsonl/Perfetto trace under "
                        "<out-dir>/telemetry/fold_<k>. 'off' (default) "
                        "compiles the device metrics out entirely")
    p.add_argument("--xprof-dir", default=None, metavar="DIR",
                   help="jax.profiler capture around a configurable epoch "
                        "window only (TrainConfig.xprof_window, default "
                        "epoch 1; override via --set xprof_window=[3,5]). "
                        "Windowed alternative to --profile-dir")
    p.add_argument("--pipeline", default=None, choices=["device", "host"],
                   help="input pipeline: 'device' (default) keeps the site "
                        "inventory resident on the mesh and ships only a "
                        "compact int32 index plan per epoch; 'host' is the "
                        "legacy dense per-epoch transfer (A/B fallback)")
    p.add_argument("--compile-cache", default=None, metavar="DIR",
                   help="persistent XLA compilation cache directory: re-runs "
                        "and per-fold re-fits load the compiled epoch from "
                        "disk instead of recompiling (TrainConfig."
                        "compile_cache_dir)")
    p.add_argument("--sanitize", nargs="?", const="1", default=None,
                   metavar="FLAGS",
                   help="runtime sanitizer (checks/sanitize.py): compile-"
                        "counter guard + jax leak checking + debug-NaN "
                        "around every fit. Optional comma subset of "
                        "compile,leaks,nans (default: all). Equivalent to "
                        "DINUNET_SANITIZE=<FLAGS>")
    p.add_argument("--coordinator", default=None, metavar="HOST:PORT",
                   help="multi-host runs: the jax.distributed coordinator "
                        "(the COINSTAC-pipeline-coordinator equivalent); "
                        "every process passes the same address")
    p.add_argument("--num-processes", type=int, default=None,
                   help="multi-host runs: total process count")
    p.add_argument("--process-id", type=int, default=None,
                   help="multi-host runs: this process's rank")
    p.add_argument("--quiet", action="store_true")
    p.add_argument("--attacks", default=None, metavar="JSON|@FILE",
                   help="declarative byzantine-site attack injection "
                        "(robustness/attacks.py AttackPlan): inline JSON or "
                        '@path — e.g. \'{"sign_flip": [[2, 0, -1]], '
                        '"scale": [[5, 10, 20]], "scale_factor": 10}\'. '
                        "Sign-flip / gradient-scaling / additive-noise / "
                        "free-rider / colluding-clique attacks replay "
                        "identically run to run and compose with --faults; "
                        "pair with --robust-agg for the defense")
    p.add_argument("--robust-agg", default=None,
                   choices=["none", "norm_clip", "trimmed_mean",
                            "coordinate_median"],
                   help="byzantine-robust site-axis aggregation "
                        "(parallel/collectives.py): norm_clip bounds each "
                        "site's gradient norm at the robust median "
                        "(psum wire unchanged); trimmed_mean / "
                        "coordinate_median reduce per coordinate over a "
                        "cross-site gather. Non-none also enables the "
                        "anomaly-scored reputation quarantine "
                        "(robustness/health.py)")
    p.add_argument("--wire-quant", default=None,
                   choices=["none", "bf16", "int8", "fp8"],
                   help="quantize collective payloads to this wire grid "
                        "(scale per payload, dequant after reduce; ~4x "
                        "fewer wire bytes at int8/fp8 — "
                        "parallel/collectives.py WireCodec)")
    p.add_argument("--overlap-rounds", action="store_true", default=None,
                   help="overlap round t's aggregation collective with "
                        "round t+1's batch gather + compute (one-round-"
                        "delayed pipelined update; trainer/steps.py)")
    p.add_argument("--dp-clip", type=float, default=None, metavar="C",
                   help="privacy plane (r20, privacy/dpsgd.py): clip each "
                        "site's round-gradient L2 norm to C inside the "
                        "rounds scan (before engine compression); 0 = off")
    p.add_argument("--dp-noise", type=float, default=None, metavar="SIGMA",
                   help="DP-SGD noise multiplier σ: adds σ·C Gaussian "
                        "noise per site per round, counter-keyed by "
                        "(dp_seed, site, round). Needs --dp-clip > 0. The "
                        "RDP accountant surfaces (ε, δ) per epoch in "
                        "telemetry, logs.json, the report CLI and the "
                        "train_epsilon /statusz gauge")
    p.add_argument("--dp-epsilon-budget", type=float, default=None,
                   metavar="EPS",
                   help="stop the fit cleanly (checkpointed, best-state "
                        "test still runs) once the accountant's ε reaches "
                        "this budget; 0 = unbounded")
    p.add_argument("--secure-agg", default=None,
                   choices=["off", "mask", "mask-nopads"],
                   help="secure-aggregation masked wires (r20, "
                        "privacy/secure_agg.py, dSGD only): 'mask' "
                        "one-time-pads each site's fixed-point delta with "
                        "pairwise antisymmetric int32 masks that cancel "
                        "EXACTLY in the unchanged psum wire; "
                        "'mask-nopads' is the pads-zeroed verification "
                        "arm (bit-identical params — the CI smoke asserts "
                        "it). Refuses int8/fp8 wire codecs")
    p.add_argument("--personalize", default=None, metavar="PATTERNS",
                   help="personalized per-site heads (r20, "
                        "privacy/personalize.py): comma-separated "
                        "param-path substrings (e.g. 'cls_fc3' for the "
                        "ICA-LSTM classifier) kept OUT of aggregation — "
                        "each site trains and evaluates its own head row")
    p.add_argument("--set", dest="overrides", action="append", default=[],
                   metavar="KEY=VALUE",
                   help="override any TrainConfig / task-args field "
                        "(repeatable; value parsed as JSON when possible)")
    return p


def main(argv: list[str] | None = None) -> int:
    args = build_parser().parse_args(argv)
    overrides = _parse_set(args.overrides)
    for key, val in (
        ("task_id", args.task), ("agg_engine", args.engine),
        ("mode", args.mode), ("epochs", args.epochs),
        ("batch_size", args.batch_size), ("num_folds", args.num_folds),
        ("model_axis_size", args.model_axis_size),
        ("sites_per_device", args.sites_per_device),
        ("num_slices", args.slices),
        ("dcn_wire_quant", args.dcn_wire_quant),
        ("min_slices", args.min_slices),
        ("profile_dir", args.profile_dir),
        ("telemetry", args.telemetry),
        ("xprof_dir", args.xprof_dir),
        ("pipeline", args.pipeline),
        ("compile_cache_dir", args.compile_cache),
        ("wire_quant", args.wire_quant),
        ("robust_agg", args.robust_agg),
        ("overlap_rounds", args.overlap_rounds),
        ("dp_clip", args.dp_clip),
        ("dp_noise_multiplier", args.dp_noise),
        ("dp_epsilon_budget", args.dp_epsilon_budget),
        ("secure_agg", args.secure_agg),
        ("personalize", (
            None if args.personalize is None
            else tuple(p for p in args.personalize.split(",") if p)
        )),
    ):
        if val is not None:
            overrides[key] = val
    cfg = TrainConfig().with_overrides(overrides)
    verbose = not args.quiet

    if args.sanitize is not None:
        # the runner layer reads the env var, so the flag is just sugar —
        # validate it here for an early, readable error
        import os

        from ..checks.sanitize import ENV_VAR, sanitize_flags

        try:
            sanitize_flags(args.sanitize)
        except ValueError as e:
            raise SystemExit(f"--sanitize: {e}")
        os.environ[ENV_VAR] = args.sanitize

    mh_flags = (args.coordinator, args.num_processes, args.process_id)
    if any(f is not None for f in mh_flags):
        complete = all(f is not None for f in mh_flags)
        solo = (args.num_processes == 1 and args.coordinator is None
                and args.process_id is None)
        if not complete and not solo:
            # a worker with a partial spec must not silently fall back to an
            # independent single-process run on the full data (and a partial
            # spec reaching jax.distributed.initialize dies with an obscure
            # error instead of this one)
            raise SystemExit(
                "multi-host runs need all of --coordinator, --num-processes "
                "and --process-id together (--num-processes 1 alone runs "
                "single-process)"
            )
        from ..parallel.distributed import distributed_init

        distributed_init(
            coordinator_address=args.coordinator,
            num_processes=args.num_processes,
            process_id=args.process_id,
        )

    fault_plan = None
    if args.faults:
        from ..robustness.faults import parse_fault_plan

        try:
            fault_plan = parse_fault_plan(args.faults)
        except (ValueError, OSError, TypeError) as e:
            raise SystemExit(f"--faults: {e}")

    attack_plan = None
    if args.attacks:
        from ..robustness.attacks import parse_attack_plan

        try:
            attack_plan = parse_attack_plan(args.attacks)
        except (ValueError, OSError, TypeError) as e:
            raise SystemExit(f"--attacks: {e}")

    if args.schedule:
        if args.serve or args.site is not None or args.folds is not None:
            raise SystemExit(
                "--schedule is the fleet-scheduler mode; --serve/--site/"
                "--folds are single-fit options"
            )
        from ..checks.sanitize import SanitizerViolation
        from .scheduler import FleetScheduler

        sched = FleetScheduler(
            args.data_path,
            pod_slices=args.pod_slices,
            poll_s=args.serve_poll,
            verbose=verbose,
        )
        exporter = None
        if args.statusz_port is not None:
            from ..telemetry.collector import PodCollector
            from ..telemetry.exporter import StatusExporter

            # pod-scope plane (r23): the scheduler's own bus plus any
            # worker exporters advertising themselves via heartbeats
            # under the schedule root, merged behind ONE /statusz
            collector = PodCollector(
                args.data_path,
                local_bus=sched.bus,
                local_labels={"process": "scheduler"},
                status_extra=sched.status,
            )
            exporter = StatusExporter(
                collector, port=args.statusz_port,
                health=sched.health_probes(), statusz=collector.status,
                slo=(
                    {"histogram": "serve_epoch_ms",
                     "p99_target_ms": args.slo_p99_ms}
                    if args.slo_p99_ms is not None else None
                ),
            )
            port = exporter.start()
            if verbose:
                print(json.dumps({
                    "statusz": f"http://127.0.0.1:{port}",
                    "endpoints": ["/metrics", "/healthz", "/statusz",
                                  "/tracez"],
                }))
        try:
            summary = sched.run(
                max_wall_s=args.sched_wall_s, max_ticks=args.sched_ticks,
            )
        except SanitizerViolation as v:
            print(json.dumps({"sanitizer_violation": str(v)}),
                  file=sys.stderr)
            return 70
        finally:
            if exporter is not None:
                exporter.stop()
        from ..telemetry.sink import _finite

        print(json.dumps(_finite(summary), default=str))
        return 0

    if args.serve:
        if args.site is not None or args.folds is not None:
            raise SystemExit(
                "--serve is the daemon mode; --site/--folds are batch-mode "
                "options"
            )
        from ..checks.sanitize import SanitizerViolation
        from .fed_runner import FedDaemon, discover_site_dirs

        capacity = args.serve_capacity or len(discover_site_dirs(args.data_path))
        daemon = FedDaemon(
            cfg,
            capacity=capacity,
            spool_dir=args.serve_spool,
            out_dir=args.out_dir,
            data_path=args.data_path,
            quorum=args.serve_quorum,
            poll_s=args.serve_poll,
            fault_plan=fault_plan,
            attack_plan=attack_plan,
            inventory_rows=args.serve_rows,
            resume=args.resume,
            verbose=verbose,
        )
        # live observability plane (r16): /metrics /healthz /statusz
        # /tracez over the process bus, and crash hooks so an unhandled
        # exception dumps the flight ring (SIGTERM/SIGINT dump rides the
        # daemon's cooperative PreemptionGuard path — signals=() here,
        # the guard owns those handlers during serve())
        daemon.flight.install(signals=())
        exporter = None
        if args.statusz_port is not None:
            from ..telemetry.exporter import StatusExporter

            exporter = StatusExporter(
                daemon.bus, port=args.statusz_port,
                tracer=daemon.trainer.tracer, flight=daemon.flight,
                health=daemon.health_probes(), statusz=daemon.status,
                slo=(
                    {"histogram": "serve_epoch_ms",
                     "p99_target_ms": args.slo_p99_ms}
                    if args.slo_p99_ms is not None else None
                ),
            )
            port = exporter.start()
            if verbose:
                print(json.dumps({
                    "statusz": f"http://127.0.0.1:{port}",
                    "endpoints": ["/metrics", "/healthz", "/statusz",
                                  "/tracez"],
                }))
        try:
            # DINUNET_SANITIZE / --sanitize: the one-epoch-compile guard
            # wraps the WHOLE service — any churn-induced retrace trips it
            from ..checks.sanitize import sanitized_fit

            with sanitized_fit(daemon.trainer, label="serve"):
                summary = daemon.serve(max_epochs=args.serve_epochs)
        except SanitizerViolation as v:
            daemon.flight.dump("sanitizer-violation")
            print(json.dumps({"sanitizer_violation": str(v)}), file=sys.stderr)
            return 70
        finally:
            # the excepthook stays installed on the failure path — an
            # exception unwinding past here still dumps the flight ring
            # at interpreter exit
            if exporter is not None:
                exporter.stop()
        daemon.flight.uninstall()
        from ..telemetry.sink import _finite

        print(json.dumps(_finite(summary), default=str))
        return 0

    if args.site is not None:
        if args.folds is not None or args.resume:
            raise SystemExit(
                "--folds/--resume are federated-mode options; "
                "not supported together with --site"
            )
        if fault_plan is not None:
            raise SystemExit(
                "--faults targets federated rounds; not supported with --site"
            )
        if attack_plan is not None:
            raise SystemExit(
                "--attacks targets federated rounds; not supported with "
                "--site"
            )
        from .fed_runner import SiteRunner

        from ..checks.sanitize import SanitizerViolation

        runner = SiteRunner(
            task_id=cfg.task_id, data_path=args.data_path,
            mode=cfg.mode, site_index=args.site, out_dir=args.out_dir,
            # drop the keys passed explicitly above — they already carry any
            # override (cfg.mode includes --mode / --set mode=...)
            **{k: v for k, v in overrides.items()
               if k not in ("task_id", "mode", "site_index", "out_dir")},
        )
        try:
            results = runner.run(verbose=verbose)
        except SanitizerViolation as v:
            print(json.dumps({"sanitizer_violation": str(v)}), file=sys.stderr)
            return 70  # EX_SOFTWARE: an internal invariant broke
    else:
        from ..checks.sanitize import SanitizerViolation
        from ..robustness.preemption import Preempted
        from .fed_runner import FedRunner

        runner = FedRunner(cfg, data_path=args.data_path, out_dir=args.out_dir,
                           fault_plan=fault_plan, attack_plan=attack_plan)
        try:
            results = runner.run(
                folds=args.folds, verbose=verbose, resume=args.resume
            )
        except SanitizerViolation as v:
            print(json.dumps({"sanitizer_violation": str(v)}), file=sys.stderr)
            return 70  # EX_SOFTWARE: an internal invariant broke
        except Preempted as p:
            # cooperative shutdown (SIGTERM/SIGINT or FaultPlan kill): state
            # was checkpointed before the raise — rerun with --resume to
            # continue bit-exact from the saved epoch boundary
            print(json.dumps({
                "preempted": True, "reason": p.reason, "epoch": p.epoch,
                "resume_with": "--resume",
            }), file=sys.stderr)
            return p.exit_code

    for k, res in enumerate(results):
        loss, metric = res["test_metrics"][0]
        print(json.dumps({
            "fold": (args.folds or list(range(len(results))))[k],
            "test_loss": loss,
            f"test_{cfg.monitor_metric}": metric,
            "best_val_epoch": res["best_val_epoch"],
        }))
    return 0


if __name__ == "__main__":
    sys.exit(main())
