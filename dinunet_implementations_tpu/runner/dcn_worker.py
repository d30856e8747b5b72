"""Multi-host worker entry point — one process per host (or per TPU slice).

Graduated from the r8 test fixture (``tests/dcn_worker.py``) into the real
multi-slice launch path (r18): each invocation joins a ``jax.distributed``
runtime as ONE process of an N-process cluster and trains the shared
federated program over the resulting global mesh. With ``--slices N`` the
mesh is the three-tier ``(slice, site, model)`` topology
(parallel/distributed.py ``multihost_sliced_site_mesh`` via
``TrainConfig.num_slices``) — processes map to slices, so the ONLY
per-round DCN traffic is the inter-slice hop of the hierarchical
aggregation, carrying one (optionally ``--dcn-wire-quant``-quantized)
per-slice partial.

Typical per-slice launch (one process per TPU slice / host)::

    python -m dinunet_implementations_tpu.runner.dcn_worker \
        --coordinator host0:1234 --num-processes 4 --process-id $RANK \
        --slices 4 --data-path /data/tree --out-dir /shared/out

Supervised mode (r19 — runner/supervisor.py): ``--supervise`` makes this
invocation the SUPERVISOR of the fleet instead of a worker. It launches
one worker per ``--process-id`` slot, monitors process exits AND heartbeat
staleness (each slice's lead rank pulses
``<out>/heartbeats/slice_<i>.json`` from a timer thread — staleness
catches hard freezes and dead-mount write blocks; a fleet wedged in a
collective is recovered through the dead peer's exit + drain), records
every slice death in the shared liveness spool
(``<out>/slice_liveness/``), dumps its flight recorder with the slice id +
last heartbeat age, drains the survivors (SIGTERM → checkpoint + clean
exit; SIGKILL past the grace window), computes the CROSS-SLICE CHECKPOINT
CONSENSUS — the newest round where all surviving slices' rotating sidecar
checkpoints (``<out>/slices/slice_<i>/``, written every epoch with a
params-sha256 meta; torn files fall back to ``.prev`` per the PR 2
contract) agree by digest — installs that generation as the fleet resume
point, and relaunches everything with ``--resume``. A preempted slice
costs the run one checkpoint window, never the run itself. The
deterministic chaos arm: a ``--faults`` plan with ``kill_slice_at`` makes
the named slice's worker SIGKILL ITSELF when its round counter crosses
the kill (first launch generation only — restarted incarnations sail
through), so the whole death→consensus→rejoin cycle replays identically
in CI.

Every process computes identical replicated results; only process 0 writes
logs/checkpoints (trainer/loop.py ``_coordinator``). ``--report PATH``
writes a JSON record of the run — mesh shape, per-epoch losses, a params
checksum (bit-compared across processes by the multihost smoke test), the
epoch compile count, and the process-0-only write counters.

Exit codes (every failure path calls ``distributed_shutdown()`` first, so
the runtime is re-entrant and a wedged peer surfaces as a nonzero exit
rather than a hang):

- ``0`` — run completed.
- ``66`` (:data:`UNSUPPORTED_RC`) — capability probe: this jaxlib's CPU
  backend cannot execute multiprocess collectives at all; CI smokes SKIP
  on it instead of failing red. A supervisor propagates it verbatim.
- ``128 + signum`` — cooperative preemption: SIGTERM/SIGINT landed during
  the fit, the rotating checkpoint was saved at the epoch boundary, the
  flight recorder dumped, and the process exited with the shell's
  signal-death convention (e.g. 143 for SIGTERM). ``75`` is the
  deterministic FaultPlan ``kill_at_round`` arm of the same path
  (robustness/preemption.py ``Preempted.exit_code``).
- ``-9`` / ``137`` — the ``kill_slice_at`` chaos arm's self-SIGKILL (an
  abrupt, uncheckpointed death by design: the supervisor must recover it
  from the OTHER slices' checkpoints).
- ``69`` (:data:`~..runner.supervisor.SUPERVISOR_GAVE_UP_RC`) — supervisor
  only: a slice kept dying past ``--max-restarts``.
"""

from __future__ import annotations

import argparse
import hashlib
import json
import os
import signal
import sys

#: exit code for "this backend cannot run multiprocess collectives" — the
#: tier-1/CI smokes skip on it (tests/test_distributed.py)
UNSUPPORTED_RC = 66


def _parse(argv):
    p = argparse.ArgumentParser(
        prog="dcn_worker",
        description="multi-host/multi-slice federated training worker",
    )
    p.add_argument("--coordinator", default=None, metavar="HOST:PORT",
                   help="jax.distributed coordinator address (process 0 "
                        "hosts it); omit with --num-processes 1 for the "
                        "single-process reference run")
    p.add_argument("--num-processes", type=int, default=1)
    p.add_argument("--process-id", type=int, default=0)
    p.add_argument("--data-path", required=True,
                   help="dataset tree (reference simulator layout); every "
                        "process loads the same tree and feeds its own "
                        "addressable mesh slices")
    p.add_argument("--out-dir", default=None,
                   help="shared output dir (process 0 writes; heartbeats, "
                        "the liveness spool and per-slice checkpoint "
                        "sidecars live here too)")
    p.add_argument("--report", default=None, metavar="PATH",
                   help="write the run-report JSON here (supervised mode: "
                        "one _p<rank> report per worker)")
    p.add_argument("--slices", type=int, default=1,
                   help="num_slices for the three-tier (slice, site, model) "
                        "mesh; must divide --num-processes (1 = the legacy "
                        "hybrid (site, model) mesh)")
    p.add_argument("--dcn-wire-quant", default="",
                   choices=["", "none", "bf16", "int8", "fp8"],
                   help="inter-slice wire codec (TrainConfig.dcn_wire_quant; "
                        "'' follows --set wire_quant)")
    p.add_argument("--devices-per-process", type=int, default=4,
                   help="virtual CPU devices per process (emulation; "
                        "ignored on real accelerator backends)")
    p.add_argument("--epochs", type=int, default=4)
    p.add_argument("--task", default="FS-Classification")
    p.add_argument("--batch-size", type=int, default=8)
    p.add_argument("--faults", default=None, metavar="JSON|@FILE",
                   help="deterministic FaultPlan (robustness/faults.py) — "
                        "site AND slice-tier windows; kill_slice_at is "
                        "realized as a real self-SIGKILL of the named "
                        "slice's worker (first generation only)")
    p.add_argument("--resume", action="store_true",
                   help="continue from the last rotating checkpoint "
                        "(FedRunner resume; the supervisor always passes "
                        "this on relaunch)")
    p.add_argument("--supervise", action="store_true",
                   help="run as the fleet SUPERVISOR: launch one worker "
                        "per process slot, monitor heartbeats/exits, "
                        "restart dead slices via checkpoint-consensus "
                        "rejoin (module docstring)")
    p.add_argument("--heartbeat-s", type=float, default=2.0,
                   help="worker heartbeat interval (seconds)")
    p.add_argument("--heartbeat-timeout-s", type=float, default=30.0,
                   help="supervisor: heartbeat staleness past this is a "
                        "wedged worker (with_retry deadline semantics "
                        "before the verdict)")
    p.add_argument("--max-restarts", type=int, default=2,
                   help="supervisor: give up (rc 69) after this many "
                        "fleet restarts")
    p.add_argument("--slice-ckpt", action="store_true",
                   help="rotate a per-slice checkpoint sidecar every epoch "
                        "(consensus input; the supervisor passes this to "
                        "its workers)")
    p.add_argument("--restart-generation", type=int, default=1,
                   help=argparse.SUPPRESS)  # supervisor-internal
    p.add_argument("--statusz-port", type=int, default=None, metavar="PORT",
                   help="supervisor: serve the FEDERATED pod-level "
                        "/metrics + /statusz here (the PodCollector "
                        "scrapes every worker's heartbeat-advertised "
                        "statusz port and exact-merges the buses; r23). "
                        "Workers always auto-pick their own port and "
                        "advertise it via the heartbeat")
    p.add_argument("--slo-p99-ms", type=float, default=2000.0,
                   metavar="MS",
                   help="supervisor: p99 target for the pod /statusz SLO "
                        "burn over the fleet-merged epoch_ms histogram")
    p.add_argument("--pod-trace", default=None, metavar="ID",
                   help="pod-wide trace id stamped on every dcn-epoch "
                        "span (the supervisor mints one and passes it to "
                        "all workers, so telemetry.assemble can follow "
                        "one run across processes)")
    p.add_argument("--set", dest="overrides", action="append", default=[],
                   metavar="KEY=VALUE",
                   help="raw TrainConfig overrides (JSON-parsed values)")
    return p.parse_args(argv)


def _config_overrides(pairs):
    out = {}
    for kv in pairs:
        k, _, v = kv.partition("=")
        try:
            out[k] = json.loads(v)
        except json.JSONDecodeError:
            out[k] = v
    return out


def _slice_of(process_id: int, num_processes: int, slices: int) -> int:
    """The mesh slice this process belongs to — processes are slice
    granules, contiguous (parallel/distributed.py
    multihost_sliced_site_mesh)."""
    if slices <= 1:
        return 0
    return process_id // max(num_processes // slices, 1)


def _params_checksum(state) -> str:
    """Order-stable digest of the replicated params — every process of a
    correct run reports the SAME hex (params are replicated by the
    aggregation collectives; the multihost smoke bit-compares this across
    processes after one round, and the cross-slice checkpoint consensus
    keys on it). ``addressable_data(0)`` reads the local replica, so no
    cross-process fetch is needed."""
    import jax
    import numpy as np

    h = hashlib.sha256()
    for leaf in jax.tree.leaves(state.params):
        a = leaf.addressable_data(0) if hasattr(leaf, "addressable_data") else leaf
        h.update(np.ascontiguousarray(np.asarray(a)).tobytes())
    return h.hexdigest()


# ---------------------------------------------------------------------------
# supervisor entry
# ---------------------------------------------------------------------------


def _free_port() -> int:
    import socket

    s = socket.socket()
    s.bind(("127.0.0.1", 0))
    port = s.getsockname()[1]
    s.close()
    return port


def _report_path(base: str | None, rank: int) -> str | None:
    if not base:
        return None
    root, ext = os.path.splitext(base)
    return f"{root}_p{rank}{ext or '.json'}"


def _supervise(args) -> int:
    """The ``--supervise`` entry: drive a :class:`~.supervisor
    .SliceSupervisor` over per-slice ``dcn_worker`` processes (module
    docstring). Runs withOUT initializing jax.distributed in this process —
    the supervisor is a pure host-side state machine."""
    import subprocess

    from ..telemetry.bus import global_bus
    from ..telemetry.flight import FlightRecorder
    from ..telemetry.tracer import new_trace_id
    from .supervisor import (
        SliceSupervisor,
        consensus_round,
        slice_ckpt_dir,
    )

    out_dir = args.out_dir or "."
    os.makedirs(out_dir, exist_ok=True)
    flight = FlightRecorder(out_dir, bus=global_bus())
    flight.install()  # crash dumps; SIGTERM chained (no guard owns it here)
    # one pod-wide trace id for the whole supervised run: every worker
    # (every generation — a restarted fleet continues the SAME story)
    # stamps it on its dcn-epoch spans, so telemetry.assemble can follow
    # the run across process boundaries
    pod_trace = args.pod_trace or new_trace_id()
    launch = {"generation": 0, "port": None}

    def spawn(rank: int, generation: int):
        if generation != launch["generation"]:
            launch["generation"] = generation
            launch["port"] = _free_port()
        worker_argv = [
            sys.executable, "-m",
            "dinunet_implementations_tpu.runner.dcn_worker",
            "--coordinator", f"127.0.0.1:{launch['port']}",
            "--num-processes", str(args.num_processes),
            "--process-id", str(rank),
            "--data-path", args.data_path,
            "--slices", str(args.slices),
            "--epochs", str(args.epochs),
            "--task", args.task,
            "--batch-size", str(args.batch_size),
            "--devices-per-process", str(args.devices_per_process),
            "--heartbeat-s", str(args.heartbeat_s),
            "--restart-generation", str(generation),
            "--pod-trace", pod_trace,
            "--slice-ckpt",
            "--out-dir", out_dir,
        ]
        if args.dcn_wire_quant:
            worker_argv += ["--dcn-wire-quant", args.dcn_wire_quant]
        if args.faults:
            worker_argv += ["--faults", args.faults]
        if args.resume or generation > 1:
            worker_argv += ["--resume"]
        rep = _report_path(args.report, rank)
        if rep:
            worker_argv += ["--report", rep]
        for kv in args.overrides:
            worker_argv += ["--set", kv]
        # the workers own their backend config (devices-per-process etc.);
        # an inherited XLA device-count flag would double-apply
        env = {k: v for k, v in os.environ.items()
               if k not in ("XLA_FLAGS", "JAX_PLATFORMS")}
        with open(os.path.join(
            out_dir, f"worker_p{rank}_gen{generation}.log"), "w",
        ) as log:
            # the child dups the fd at spawn; closing ours leaks nothing
            return subprocess.Popen(
                worker_argv, stdout=log, stderr=subprocess.STDOUT, env=env,
            )

    def slice_of(rank: int) -> int:
        return _slice_of(rank, args.num_processes, args.slices)

    def install_consensus(generation: int, dead_slice: int) -> None:
        """Pick the newest round all SURVIVING slices' sidecars agree on
        and install it as the fleet resume point, unless the shared fold
        checkpoint already sits at that epoch (keeping its richer fit
        meta — loss history, early-stop bookkeeping — when it does). The
        decision is PERSISTED under <out>/consensus/ (r23): a flight note
        alone may never reach disk if the supervisor dies before its next
        dump, and the postmortem timeline must name the round chosen."""
        import time as _time

        from ..telemetry.postmortem import CONSENSUS_DIR
        from ..trainer.checkpoint import CorruptCheckpointError, load_meta
        from ..trainer.logs import fold_dir
        from .supervisor import _atomic_json

        decision_path = os.path.join(
            out_dir, CONSENSUS_DIR, f"decision_gen{generation}.json"
        )
        os.makedirs(os.path.dirname(decision_path), exist_ok=True)
        dirs = {
            sl: slice_ckpt_dir(out_dir, sl)
            for sl in range(max(args.slices, 1)) if sl != dead_slice
        }
        agreed = consensus_round(dirs or {
            sl: slice_ckpt_dir(out_dir, sl)
            for sl in range(max(args.slices, 1))
        })
        if agreed is None:
            flight.note("consensus-none", generation=generation)
            _atomic_json(decision_path, {
                "time_unix": _time.time(), "generation": generation,
                "dead_slice": dead_slice, "round": None,
            })
            return  # fleet resumes from the shared fold checkpoint as-is
        rnd, sha, path = agreed
        epoch = load_meta(path).get("epoch")
        resume = os.path.join(
            fold_dir(out_dir, "remote", args.task, 0),
            "checkpoint_latest.msgpack",
        )
        try:
            fold_epoch = load_meta(resume).get("epoch")
        except (OSError, CorruptCheckpointError):
            fold_epoch = None
        if fold_epoch != epoch:
            # torn, missing, or AHEAD of the agreement (the coordinator
            # checkpointed an epoch a now-dead slice never sealed): roll
            # the fleet to the agreed generation
            import shutil

            os.makedirs(os.path.dirname(resume), exist_ok=True)
            shutil.copyfile(path, resume)
        flight.note("consensus-install", round=rnd, epoch=epoch,
                    sha=sha[:12], replaced=fold_epoch != epoch)
        _atomic_json(decision_path, {
            "time_unix": _time.time(), "generation": generation,
            "dead_slice": dead_slice, "round": rnd, "epoch": epoch,
            "sha": sha, "replaced": fold_epoch != epoch,
        })

    sup = SliceSupervisor(
        spawn,
        num_processes=args.num_processes,
        out_dir=out_dir,
        slice_of_process=slice_of,
        heartbeat_timeout_s=args.heartbeat_timeout_s,
        max_restarts=args.max_restarts,
        flight=flight,
        bus=global_bus(),
        on_consensus=install_consensus,
        passthrough_rcs=(UNSUPPORTED_RC,),
    )
    exporter = None
    if args.statusz_port is not None:
        # the pod observability plane (r23): one /statusz + /metrics for
        # the whole fleet — the PodCollector discovers every worker from
        # its heartbeat-advertised port and exact-merges the buses, and
        # the UNCHANGED StatusExporter serves the merged view (the
        # collector duck-types the bus read API)
        from ..telemetry.collector import PodCollector
        from ..telemetry.exporter import StatusExporter

        collector = PodCollector(
            out_dir, local_bus=global_bus(),
            local_labels={"process": "supervisor"},
            status_extra=lambda: {
                "mode": "supervisor",
                "generation": sup.generation,
                "restarts": sup.restarts,
                "pod_trace": pod_trace,
            },
        )
        exporter = StatusExporter(
            collector, port=args.statusz_port, flight=flight,
            statusz=collector.status,
            slo={"histogram": "epoch_ms",
                 "p99_target_ms": args.slo_p99_ms},
        )
        port = exporter.start()
        print(f"[supervise] pod statusz http://127.0.0.1:{port}/statusz "
              f"(federated /metrics, SLO over merged epoch_ms)",
              flush=True)
    rc = sup.run()
    flight.note("supervisor-exit", rc=rc, restarts=sup.restarts)
    if exporter is not None:
        exporter.stop()
    # the supervisor's ring (launches, deaths, consensus, restarts) must
    # reach disk even on a CLEAN exit — it is postmortem evidence, and the
    # per-death dumps only cover the unhappy path
    flight.dump(f"supervisor-exit:rc={rc}")
    try:
        # best-effort pod trace assembly: workers wrote per-process
        # trace_p<rank>_gen<g>.jsonl files; merge them into one Perfetto
        # timeline now so the artifact exists without a second command
        from ..telemetry.assemble import (
            POD_TRACE_DIR,
            POD_TRACE_FILE,
            assemble,
        )

        if os.path.isdir(os.path.join(out_dir, POD_TRACE_DIR)):
            assemble(out_dir, os.path.join(
                out_dir, POD_TRACE_DIR, POD_TRACE_FILE
            ))
    except (OSError, ValueError, TypeError, KeyError) as e:
        # unreadable/torn trace files or a full disk — the assembly is a
        # convenience artifact and must not mask the run's rc
        flight.note("pod-trace-assembly-failed", error=repr(e))
    return rc


# ---------------------------------------------------------------------------
# worker entry
# ---------------------------------------------------------------------------


def main(argv=None) -> int:
    args = _parse(argv if argv is not None else sys.argv[1:])
    if args.supervise:
        return _supervise(args)

    import jax

    # CPU emulation: one child per slice over virtual CPU devices, until each
    # child can be given chips of its own (ROADMAP R4). The device-count knob
    # only shapes the CPU backend, so it is set whatever the platform.
    if not os.environ.get("JAX_PLATFORMS"):
        jax.config.update("jax_platforms", "cpu")
    jax.config.update("jax_num_cpu_devices", args.devices_per_process)

    from dinunet_implementations_tpu.parallel import (
        distributed_init,
        distributed_shutdown,
    )
    from dinunet_implementations_tpu.robustness.faults import (
        parse_fault_plan,
    )
    from dinunet_implementations_tpu.robustness.preemption import Preempted
    from dinunet_implementations_tpu.runner.supervisor import (
        Heartbeat,
        heartbeat_path,
        slice_ckpt_dir,
    )
    from dinunet_implementations_tpu.telemetry.flight import FlightRecorder

    try:
        fault_plan = parse_fault_plan(args.faults)
    except (ValueError, OSError) as e:
        print(f"--faults: {e}", file=sys.stderr)
        return 2

    slice_id = _slice_of(args.process_id, args.num_processes, args.slices)
    # one sidecar/heartbeat writer per slice: with several processes per
    # slice (num_processes > slices), slice-mates rotating the same files
    # would race checkpoint.py's exists-then-replace (and shadow each
    # other's pulses); params are replicated, so the slice's FIRST rank
    # writing is lossless
    procs_per_slice = max(args.num_processes // max(args.slices, 1), 1)
    slice_lead = args.process_id % procs_per_slice == 0
    heartbeat = None
    flight = None
    if args.out_dir:
        flight = FlightRecorder(args.out_dir)
        # crash dumps + SIGTERM-outside-the-fit dumps; DURING the fit the
        # PreemptionGuard owns SIGTERM and the Preempted handler below
        # dumps cooperatively (telemetry/flight.py contract)
        flight.install()
        if slice_lead:
            heartbeat = Heartbeat(
                heartbeat_path(args.out_dir, slice_id), slice_id,
                interval_s=args.heartbeat_s,
            ).start()

    multi = distributed_init(
        coordinator_address=args.coordinator,
        num_processes=args.num_processes,
        process_id=args.process_id,
    ) if args.num_processes > 1 else distributed_init()

    import dinunet_implementations_tpu.trainer.loop as loop_mod
    from dinunet_implementations_tpu import TrainConfig
    from dinunet_implementations_tpu.parallel.distributed import (
        spans_processes,
    )
    from dinunet_implementations_tpu.runner import FedRunner

    writes = {"logs": 0, "ckpt": 0}
    _orig_logs = loop_mod.write_logs_json
    _orig_ckpt = loop_mod.save_checkpoint
    _save_checkpoint = loop_mod.save_checkpoint

    def _count_logs(*a, **k):
        writes["logs"] += 1
        return _orig_logs(*a, **k)

    def _count_ckpt(*a, **k):
        writes["ckpt"] += 1
        return _orig_ckpt(*a, **k)

    loop_mod.write_logs_json = _count_logs
    loop_mod.save_checkpoint = _count_ckpt

    # keep the final epoch state visible for the params checksum (the fit
    # result dict carries metrics, not weights) — and the trainer for the
    # CompileGuard-style epoch compile count. In supervised/--slice-ckpt
    # mode the same hook also (a) pulses the heartbeat with round progress,
    # (b) rotates this slice's consensus sidecar, and (c) fires the
    # kill_slice_at self-SIGKILL chaos arm (first generation only).
    final = {"state": None, "trainer": None, "epoch": 0, "round": 0}

    # the pod observability plane (r23): every slice lead serves its OWN
    # /statusz (auto-picked port) and advertises it in the heartbeat, so
    # the supervisor's PodCollector can discover + scrape + merge the
    # fleet's buses with zero configuration. started_unix rides the
    # statusz payload too — the collector cross-checks it against the
    # heartbeat to reject recycled pids.
    exporter = None
    if heartbeat is not None:
        from dinunet_implementations_tpu.telemetry.bus import global_bus
        from dinunet_implementations_tpu.telemetry.exporter import (
            StatusExporter,
        )

        exporter = StatusExporter(
            global_bus(), flight=flight,
            statusz=lambda: {
                "mode": "dcn_worker",
                "process_id": args.process_id,
                "slice": slice_id,
                "generation": args.restart_generation,
                "started_unix": heartbeat.started_unix,
                "epoch": final["epoch"],
                "round": final["round"],
            },
        )
        heartbeat.beat(
            statusz_port=exporter.start(), process=args.process_id,
        )

    def _write_pod_trace() -> None:
        """Flush this process's spans to <out>/pod_trace/ so the
        cross-process assembler (telemetry/assemble.py) can merge them —
        the per-fit sink is coordinator-only, and the pod view needs
        EVERY process's timeline."""
        tr = final["trainer"]
        if (args.out_dir and args.pod_trace and tr is not None
                and tr.tracer.enabled):
            from dinunet_implementations_tpu.telemetry.assemble import (
                POD_TRACE_DIR,
            )

            tr.tracer.write_jsonl(os.path.join(
                args.out_dir, POD_TRACE_DIR,
                f"trace_p{args.process_id}"
                f"_gen{args.restart_generation}.jsonl",
            ))

    _orig_run_epoch = loop_mod.FederatedTrainer.run_epoch
    kill_round = (
        fault_plan.kill_round_for_slice(slice_id)
        if fault_plan is not None and args.restart_generation <= 1 else None
    )
    my_ckpt_dir = (
        slice_ckpt_dir(args.out_dir, slice_id)
        if args.out_dir and args.slice_ckpt and slice_lead else None
    )

    def _record_run_epoch(self, state, *a, **k):
        # first call reads the INPUT state's round (a resumed fit starts
        # past 0; the kill arm must key on genuinely-crossed rounds)
        round_before = (
            final["round"] if final["epoch"] else int(state.round)
        )
        if args.pod_trace:
            # the pod-wide trace id on every epoch span: the assembled
            # Perfetto timeline follows it across process boundaries
            with self.tracer.span(
                "dcn-epoch", trace=args.pod_trace, slice=slice_id,
                process=args.process_id,
                generation=args.restart_generation,
            ):
                out = _orig_run_epoch(self, state, *a, **k)
        else:
            out = _orig_run_epoch(self, state, *a, **k)
        final["state"], final["trainer"] = out[0], self
        # the GLOBAL fit epoch (run_epoch's third positional arg) — a
        # restarted generation resumes at epoch k+1, and the sidecar meta
        # must say so or consensus would compare local counts against the
        # fold checkpoint's global epochs and roll the fleet back wrong
        fit_epoch = a[1] if len(a) > 1 else k.get("epoch", 0)
        final["epoch"] = int(fit_epoch)
        final["round"] = int(out[0].round)
        if heartbeat is not None:
            heartbeat.beat(epoch=final["epoch"], round=final["round"])
        if kill_round is not None and round_before <= kill_round < final["round"]:
            # the chaos arm: die like a preempted slice ACTUALLY dies —
            # abruptly, BEFORE this epoch's sidecar seals, so the
            # supervisor must recover from the other slices' checkpoints
            if flight is not None:
                flight.note("kill-slice", slice=slice_id,
                            round=final["round"])
                flight.dump(f"kill-slice:{slice_id}@round{kill_round}")
            os.kill(os.getpid(), signal.SIGKILL)
        if my_ckpt_dir is not None:
            _save_checkpoint(
                os.path.join(my_ckpt_dir, "checkpoint_latest.msgpack"),
                out[0],
                meta={
                    "round": final["round"], "epoch": final["epoch"],
                    "slice": slice_id,
                    "params_sha256": _params_checksum(out[0]),
                },
                rotate=True,
            )
        return out

    loop_mod.FederatedTrainer.run_epoch = _record_run_epoch

    cfg = TrainConfig(
        task_id=args.task, epochs=args.epochs, validation_epochs=2,
        patience=10, batch_size=args.batch_size,
        split_ratio=(0.7, 0.15, 0.15), seed=0,
        num_slices=args.slices, dcn_wire_quant=args.dcn_wire_quant,
    ).with_overrides(_config_overrides(args.overrides))
    runner = FedRunner(
        cfg, data_path=args.data_path, out_dir=args.out_dir,
        fault_plan=fault_plan,
    )
    try:
        res = runner.run(verbose=False, resume=args.resume)[0]
    except Preempted as p:
        # cooperative preemption (SIGTERM during the fit / kill_at_round):
        # the rotating checkpoint landed at the epoch boundary before this
        # raise — dump the flight ring, tear the runtime down, exit with
        # the documented 128+signum (75 for the deterministic arm)
        if flight is not None:
            flight.note("preempted", signum=p.signum, epoch=p.epoch,
                        slice=slice_id)
            flight.dump(
                f"signal:{p.signum}" if p.signum else "kill_at_round"
            )
        _write_pod_trace()  # a drained survivor's spans are pod evidence
        if heartbeat is not None:
            heartbeat.stop()
        if exporter is not None:
            exporter.stop()
        distributed_shutdown()
        return p.exit_code
    except Exception as e:  # noqa: BLE001 — capability probe, see below
        if heartbeat is not None:
            heartbeat.stop()
        if "Multiprocess computations aren't implemented" in str(e):
            # this jaxlib's CPU backend cannot execute cross-process
            # collectives at all (e.g. 0.4.x): report "unsupported",
            # distinct from a real failure, so callers can skip
            print(f"UNSUPPORTED: {e}", flush=True)
            distributed_shutdown()
            return UNSUPPORTED_RC
        # any other failure still tears the runtime down first: a raise
        # with the distributed client live would leave peers wedged in
        # their next collective with nothing to surface it
        distributed_shutdown()
        raise

    if args.report:
        from dinunet_implementations_tpu.checks.sanitize import jit_cache_size

        trainer = final["trainer"]
        report = {
            "process_index": jax.process_index(),
            "process_count": jax.process_count(),
            "global_devices": len(jax.devices()),
            "local_devices": len(jax.local_devices()),
            "multi": bool(multi),
            "mesh_spans_processes": spans_processes(runner.mesh),
            "mesh_shape": dict(runner.mesh.shape),
            "mesh_axes": list(runner.mesh.axis_names),
            "num_slices": args.slices,
            "slice_id": slice_id,
            "restart_generation": args.restart_generation,
            "epoch_losses": [float(x) for x in res["epoch_losses"]],
            "test_metrics": res["test_metrics"],
            "n_log_writes": writes["logs"],
            "n_ckpt_writes": writes["ckpt"],
            # bit-compared across processes by the multihost smoke: the
            # replicated params after the final round
            "params_sha256": (
                _params_checksum(final["state"])
                if final["state"] is not None else None
            ),
            # the one-epoch-compile-per-process contract (CompileGuard's
            # counter): churnless multi-host training must compile the
            # epoch exactly once in EVERY process
            "epoch_compiles": (
                jit_cache_size(trainer.epoch_fn)
                if trainer is not None else None
            ),
        }
        with open(args.report, "w") as fh:
            json.dump(report, fh)

    _write_pod_trace()
    if heartbeat is not None:
        heartbeat.stop()
    if exporter is not None:
        exporter.stop()
    # clean teardown: leave the runtime re-entrant (the coordinated barrier
    # in shutdown also surfaces a wedged peer as a nonzero exit, instead of
    # letting a caller's timeout mask it)
    distributed_shutdown()
    return 0


if __name__ == "__main__":
    sys.exit(main())
