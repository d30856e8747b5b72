"""Multi-host (DCN) layer — mesh topology AND live multi-process execution.

Two layers of coverage:
- single-process contracts: ``distributed_init`` no-ops, mesh degeneration,
  collectives on the host mesh, put/fetch plumbing;
- a LIVE 2-process jax.distributed CPU run (VERDICT r3 #1):
  ``test_two_process_dcn_runtime_live`` launches two coordinated worker
  processes (tests/dcn_worker.py, 4 virtual devices each) that train
  FedRunner end-to-end over a real spans-processes mesh — executing the
  ``make_array_from_process_local_data`` feed, ``process_allgather`` fetch,
  and process-0-only write branches that no single-process test can reach.
"""

import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax import shard_map
from jax.sharding import PartitionSpec as P

from dinunet_implementations_tpu.parallel import (
    MODEL_AXIS,
    SITE_AXIS,
    distributed_init,
    multihost_site_mesh,
)


def test_single_process_init_is_noop():
    assert distributed_init() is False
    assert distributed_init(num_processes=1) is False


def test_mesh_shape_and_axis_names():
    mesh = multihost_site_mesh(sites_per_process=4, model_axis_size=2)
    assert dict(mesh.shape) == {SITE_AXIS: 4, MODEL_AXIS: 2}
    assert mesh.axis_names == (SITE_AXIS, MODEL_AXIS)


def test_mesh_defaults_fill_the_process():
    mesh = multihost_site_mesh()
    assert dict(mesh.shape) == {SITE_AXIS: len(jax.devices()), MODEL_AXIS: 1}


def test_mesh_uses_leading_subset_when_devices_surplus():
    # 3 sites x model=2 on 8 devices: 6 used, 2 idle (same contract as
    # make_site_mesh's devices[:need] on one host)
    mesh = multihost_site_mesh(sites_per_process=3, model_axis_size=2)
    assert dict(mesh.shape) == {SITE_AXIS: 3, MODEL_AXIS: 2}
    assert list(mesh.devices.flat) == jax.devices()[:6]


def test_mesh_rejects_oversubscription():
    with pytest.raises(ValueError, match="devices per process"):
        multihost_site_mesh(sites_per_process=5, model_axis_size=2)


def test_collectives_run_on_the_mesh():
    mesh = multihost_site_mesh(sites_per_process=4, model_axis_size=2)
    x = jnp.arange(8.0).reshape(4, 2)

    out = jax.jit(
        shard_map(
            lambda v: jax.lax.psum(v, (SITE_AXIS, MODEL_AXIS)),
            mesh=mesh,
            in_specs=P(SITE_AXIS, MODEL_AXIS),
            out_specs=P(SITE_AXIS, MODEL_AXIS),
        )
    )(x)
    np.testing.assert_allclose(np.asarray(out), np.full((4, 2), x.sum()))


def test_put_site_batch_single_process_commits_site_sharding():
    from dinunet_implementations_tpu.parallel.distributed import put_site_batch

    mesh = multihost_site_mesh(sites_per_process=8)
    a = np.arange(8 * 3 * 2, dtype=np.float32).reshape(8, 3, 2)
    arr = put_site_batch(mesh, a)
    assert arr.sharding.spec == P(SITE_AXIS)
    np.testing.assert_array_equal(np.asarray(arr), a)
    cast = put_site_batch(mesh, a, dtype="bfloat16")
    assert str(cast.dtype) == "bfloat16"


def test_coordinator_join_deadline_fails_fast(monkeypatch):
    """Satellite regression (r19): the DCN coordinator-join path keeps its
    with_retry(deadline_s=) contract — a coordinator that never comes up
    fails the worker within the wall-clock budget instead of retrying
    forever (the hung-coordinator fail-fast PR 8 gave
    jax.distributed.initialize)."""
    import time

    from dinunet_implementations_tpu.parallel import distributed as dist

    calls = {"n": 0}

    def refused(**kw):
        calls["n"] += 1
        raise ConnectionRefusedError("coordinator not up")

    monkeypatch.setattr(dist.jax.distributed, "initialize", refused)
    monkeypatch.setattr(dist.jax.distributed, "shutdown", lambda: None)
    monkeypatch.setattr(dist, "_jax_distributed_client", lambda: None)
    monkeypatch.setattr(dist, "_initialized", False)
    t0 = time.monotonic()
    with pytest.raises(ConnectionRefusedError):
        dist.distributed_init(
            coordinator_address="127.0.0.1:1", num_processes=2,
            process_id=1, join_deadline_s=0.6, join_timeout_s=None,
        )
    elapsed = time.monotonic() - t0
    # at least one retry happened, and the deadline capped the total —
    # never the unbounded 3-attempt exponential backoff
    assert calls["n"] >= 2
    assert elapsed < 5.0
    assert dist._initialized is False


def test_coordinator_join_attempt_timeout_is_fatal(monkeypatch):
    """A join attempt that HANGS (wedged coordinator accepting the TCP
    connect and never completing the handshake) is abandoned after
    join_timeout_s and FAILS the operation — a timed-out attempt's zombie
    thread may still be mutating jax's global distributed state, so
    retrying would race it (distributed_init retry_on_timeout=False)."""
    import time

    from dinunet_implementations_tpu.parallel import distributed as dist
    from dinunet_implementations_tpu.robustness.retry import RetryTimeout
    from dinunet_implementations_tpu.telemetry.bus import global_bus

    def hung(**kw):
        time.sleep(30)

    monkeypatch.setattr(dist.jax.distributed, "initialize", hung)
    monkeypatch.setattr(dist, "_jax_distributed_client", lambda: None)
    monkeypatch.setattr(dist, "_initialized", False)
    t0 = time.monotonic()
    with pytest.raises(RetryTimeout):
        dist.distributed_init(
            coordinator_address="127.0.0.1:1", num_processes=2,
            process_id=1, join_deadline_s=30.0, join_timeout_s=0.3,
        )
    assert time.monotonic() - t0 < 5.0
    assert dist._initialized is False
    # the dcn_timeout observability: the failure landed on the live bus
    counters = global_bus().snapshot().get("counters", {})
    assert any("dcn_join_timeouts_total" in k for k in counters)


def test_fetch_site_outputs_single_process_is_numpy_identity():
    from dinunet_implementations_tpu.parallel.distributed import (
        fetch_site_outputs,
    )

    mesh = multihost_site_mesh(sites_per_process=8)
    tree = (jnp.arange(8.0), {"x": jnp.ones((8, 2))})
    out = fetch_site_outputs(tree, mesh)
    assert isinstance(out[0], np.ndarray)
    np.testing.assert_array_equal(out[0], np.arange(8.0))
    np.testing.assert_array_equal(out[1]["x"], np.ones((8, 2)))


# ---------------------------------------------------------------------------
# Live multi-process DCN execution (VERDICT r3 #1): two coordinated
# jax.distributed CPU processes (4 virtual devices each) drive FedRunner
# end-to-end through the spans_processes branches — put_site_batch's
# make_array_from_process_local_data, fetch_site_outputs' process_allgather,
# and the process-0-only output writes. The reference's execution model IS
# multi-process (one container per site, entry.py:5); this is its live
# TPU-native equivalent, scaled to what one host can test.
# ---------------------------------------------------------------------------


def _free_port():
    import socket

    s = socket.socket()
    s.bind(("127.0.0.1", 0))
    port = s.getsockname()[1]
    s.close()
    return port


def _run_dcn_workers(data_path, out_dir, reports, nproc, timeout=420,
                     extra=()):
    """Launch the coordinated workers with stdout redirected to files —
    the workers are barrier-coupled through jax.distributed, so a full
    OS pipe on one would deadlock them all; files also survive a timeout
    for the failure diagnostics. ``extra`` appends module flags (the
    worker graduated to runner/dcn_worker.py in r18 — e.g.
    ``["--slices", "2"]`` for the multi-slice smoke)."""
    import subprocess
    import sys
    import time

    worker = os.path.join(os.path.dirname(__file__), "dcn_worker.py")
    port = _free_port()
    env = {k: v for k, v in os.environ.items()
           if k not in ("XLA_FLAGS", "JAX_PLATFORMS")}
    log_paths = [f"{rep}.log" for rep in reports]
    procs = []
    for r in range(nproc):
        with open(log_paths[r], "w") as log:
            procs.append(subprocess.Popen(
                [sys.executable, worker, str(port), str(nproc), str(r),
                 str(data_path), str(out_dir), str(reports[r]),
                 *[str(a) for a in extra]],
                stdout=log, stderr=subprocess.STDOUT, env=env,
            ))
    deadline = time.monotonic() + timeout
    try:
        for p in procs:
            p.wait(timeout=max(deadline - time.monotonic(), 1))
    except subprocess.TimeoutExpired:
        pass
    finally:
        for p in procs:
            if p.poll() is None:
                p.kill()
                p.wait()
    if any(p.returncode == 66 for p in procs):
        # worker-side capability probe (dcn_worker.py): this jaxlib's CPU
        # backend cannot execute cross-process collectives at all
        pytest.skip("multiprocess CPU collectives unsupported by this jaxlib")
    for r, p in enumerate(procs):
        out = open(log_paths[r]).read()
        assert p.returncode == 0, f"worker {r} rc={p.returncode}:\n{out[-4000:]}"
    return [json.load(open(rep)) for rep in reports]


@pytest.mark.slow
def test_two_process_dcn_runtime_live(tmp_path):
    """The multi-host runtime executes for real: identical losses on every
    process AND vs the single-process run, with exactly one process writing
    the shared output directory."""
    from dinunet_implementations_tpu.data.demo import make_demo_tree

    data = tmp_path / "demo"
    make_demo_tree(str(data))  # 4 sites → 2 per process

    # --- 2-process coordinated run (shared out dir, like a shared FS)
    out2 = tmp_path / "out_2proc"
    reps = [tmp_path / f"rep{r}.json" for r in range(2)]
    r0, r1 = _run_dcn_workers(data, out2, reps, nproc=2)

    for r in (r0, r1):
        assert r["multi"] is True
        assert r["process_count"] == 2
        assert r["global_devices"] == 8 and r["local_devices"] == 4
        assert r["mesh_spans_processes"] is True
        assert r["mesh_shape"] == {SITE_AXIS: 4, MODEL_AXIS: 1}
    assert r0["process_index"] == 0 and r1["process_index"] == 1

    # every process computes identical replicated results...
    np.testing.assert_array_equal(r0["epoch_losses"], r1["epoch_losses"])
    assert r0["test_metrics"] == r1["test_metrics"]
    # ...and only process 0 touches the shared output directory
    assert r0["n_log_writes"] > 0 and r0["n_ckpt_writes"] > 0
    assert r1["n_log_writes"] == 0 and r1["n_ckpt_writes"] == 0
    logs = sorted(p.relative_to(out2).as_posix()
                  for p in out2.rglob("logs.json"))
    assert any(l.startswith("remote/") for l in logs), logs

    # --- single-process reference run: the DCN topology must not change math
    out1 = tmp_path / "out_1proc"
    (r_solo,) = _run_dcn_workers(data, out1, [tmp_path / "rep_solo.json"],
                                 nproc=1)
    assert r_solo["multi"] is False
    assert r_solo["mesh_spans_processes"] is False
    # cross-process results are bit-identical (asserted above); vs the
    # single-process topology XLA lowers the site-psum differently (gloo
    # cross-process collective vs intra-process reduction), so the losses
    # agree to 1 ulp rather than bitwise
    np.testing.assert_allclose(
        r0["epoch_losses"], r_solo["epoch_losses"], rtol=3e-7, atol=0,
    )
    # test_metrics are rounded to 5 decimals — the 1-ulp divergence can
    # still flip a rounding boundary, so compare at that granularity
    np.testing.assert_allclose(
        r0["test_metrics"], r_solo["test_metrics"], atol=1.1e-5,
    )


@pytest.mark.slow
def test_two_process_multislice_smoke(tmp_path):
    """r18 multi-slice over real processes: 2 coordinated workers form a
    (slice=2, site, model) mesh — one process per slice, the inter-slice
    aggregation hop is the only per-round DCN traffic — and after training
    the replicated params agree BIT-FOR-BIT across processes (sha256 of
    every leaf) with the epoch compiled exactly once per process (the
    CompileGuard one-program contract, reported as the jit cache size)."""
    from dinunet_implementations_tpu.data.demo import make_demo_tree

    data = tmp_path / "demo"
    make_demo_tree(str(data))  # 4 sites → 2 per slice

    out = tmp_path / "out_slices"
    reps = [tmp_path / f"slrep{r}.json" for r in range(2)]
    r0, r1 = _run_dcn_workers(
        data, out, reps, nproc=2,
        extra=["--slices", "2", "--epochs", "2"],
    )
    for r in (r0, r1):
        assert r["multi"] is True and r["mesh_spans_processes"] is True
        assert r["mesh_axes"] == ["slice", "site", "model"]
        assert r["mesh_shape"]["slice"] == 2
        assert r["num_slices"] == 2
        # one epoch compile per process — multi-slice must not retrace
        assert r["epoch_compiles"] == 1, r["epoch_compiles"]
    # cross-process param agreement after the rounds: the replicated
    # params digest is identical on every process
    assert r0["params_sha256"] is not None
    assert r0["params_sha256"] == r1["params_sha256"]
    np.testing.assert_array_equal(r0["epoch_losses"], r1["epoch_losses"])
    # process-0-only output contract survives the sliced topology
    assert r0["n_log_writes"] > 0 and r1["n_log_writes"] == 0


@pytest.mark.slow
def test_supervised_chaos_kill_one_worker_completes(tmp_path):
    """r19 chaos smoke (the tier-1 mirror of the CI multislice job): a
    2-process supervised multi-slice run whose FaultPlan SIGKILLs slice
    1's worker mid-run. The supervisor must record the death (liveness
    spool + flight dump carrying the slice id and heartbeat age), restart
    the fleet from the cross-slice checkpoint consensus, and complete —
    with final params bit-identical to a no-fault reference run (resume
    is bit-exact, so the surviving-slice trajectory reconverges on the
    uninterrupted one). Skips on jaxlibs without multiprocess CPU
    collectives (rc 66)."""
    import glob
    import subprocess
    import sys

    from dinunet_implementations_tpu.data.demo import make_demo_tree
    from dinunet_implementations_tpu.runner.supervisor import (
        read_slice_liveness,
    )

    data = tmp_path / "demo"
    make_demo_tree(str(data))  # 4 sites → 2 per slice
    env = {k: v for k, v in os.environ.items()
           if k not in ("XLA_FLAGS", "JAX_PLATFORMS")}

    def supervised(out, rep, faults=None):
        argv = [
            sys.executable, "-m",
            "dinunet_implementations_tpu.runner.dcn_worker",
            "--supervise", "--num-processes", "2", "--slices", "2",
            "--epochs", "4", "--data-path", str(data),
            "--out-dir", str(out), "--report", str(rep),
            "--heartbeat-timeout-s", "120",
        ]
        if faults:
            argv += ["--faults", faults]
        return subprocess.run(
            argv, env=env, capture_output=True, text=True, timeout=900,
        )

    chaos = supervised(
        tmp_path / "chaos", tmp_path / "chaos_rep.json",
        faults='{"kill_slice_at":[[1,2]]}',
    )
    if chaos.returncode == 66:
        pytest.skip("multiprocess CPU collectives unsupported (rc 66)")
    assert chaos.returncode == 0, chaos.stdout[-4000:] + chaos.stderr[-4000:]
    events = read_slice_liveness(str(tmp_path / "chaos" / "slice_liveness"))
    kinds = [(e["event"], e["slice"]) for e in events]
    assert ("dead", 1) in kinds and ("alive", 1) in kinds, kinds
    dumps = glob.glob(str(tmp_path / "chaos" / "flight_*.json"))
    reasons = [json.load(open(p))["reason"] for p in dumps]
    assert any(r.startswith("slice-death:slice=1") for r in reasons), reasons

    ref = supervised(tmp_path / "ref", tmp_path / "ref_rep.json")
    assert ref.returncode == 0, ref.stdout[-4000:] + ref.stderr[-4000:]
    r_chaos = json.load(open(tmp_path / "chaos_rep_p0.json"))
    r_ref = json.load(open(tmp_path / "ref_rep_p0.json"))
    assert r_chaos["restart_generation"] == 2  # the rejoined incarnation
    assert r_chaos["params_sha256"] == r_ref["params_sha256"] is not None


@pytest.mark.slow
def test_trainer_on_mesh_with_committed_batches():
    """The put/fetch plumbing drives a real federated fit on a host mesh and
    matches the vmap (mesh=None) path's losses."""
    from dinunet_implementations_tpu.core.config import TrainConfig
    from dinunet_implementations_tpu.data.api import SiteArrays
    from dinunet_implementations_tpu.models import MSANNet
    from dinunet_implementations_tpu.trainer import FederatedTrainer

    rng = np.random.default_rng(0)
    sites = []
    for s in range(4):
        y = (rng.random(16) > 0.5).astype(np.int64)
        x = rng.normal(size=(16, 6)).astype(np.float32) + y[:, None]
        sites.append(SiteArrays(x, y, np.arange(16)))
    cfg = TrainConfig(task_id="FS-Classification", batch_size=8, epochs=3,
                      validation_epochs=1, patience=10)
    model = MSANNet(in_size=6, hidden_sizes=(8,), out_size=2)
    mesh = multihost_site_mesh(sites_per_process=4)
    res_mesh = FederatedTrainer(cfg, model, mesh=mesh).fit(
        sites, sites, sites, verbose=False)
    res_vmap = FederatedTrainer(cfg, model, mesh=None).fit(
        sites, sites, sites, verbose=False)
    np.testing.assert_allclose(res_mesh["epoch_losses"],
                               res_vmap["epoch_losses"], rtol=1e-5)
