"""Serving fleet (r21): replicated engines with sharded session affinity,
zero-recompile params hot-swap, SLO-driven admission, and the train-to-serve
CD plane.

The load-bearing claims, as tests:

- a streaming session NEVER splits across replicas — every chunk of a
  session lands on its home replica (crc32 shard), and the per-replica
  session tables partition the session space (eviction and generation
  discipline hold per shard);
- a crashed replica's sessions re-home through the FRESH gate: the
  supervisor restarts the slot at a bumped membership generation, and a
  re-homed session's replay is BIT-EXACT with a fresh single-engine run —
  stale carries cannot resurrect across restarts or route moves;
- served probabilities from the fleet are BITWISE the single-engine
  reference at every bucket, before AND after params hot-swaps, and the
  CompileGuard zero-compile proof extends across ≥2 swaps;
- the publish gauntlet (serving/publish.py): stale-digest gate, shadow-lane
  rejection of non-finite candidates, SLO-error-budget rollback that
  restores the retained weights — all as pure buffer donation;
- admission (r21 microbatcher): priority lanes over FIFO, deadline
  shedding, max_queue shedding at submit — and the p99-targeted max-delay
  autotuner whose dual-conservative histogram bounds give it a dead band
  (no oscillation on bucket error).

The host-side logic (shard function, admission, autotuner, histogram
windows, watcher, version gate) runs in the fast tier; every test that
warms real engines (multi-replica AOT warmups + donated swap grafts) is
``slow`` — the fast gate's wall-clock budget has no headroom for ~10
fleet warmups, and the CI fleet smoke drives the same claims end to end
through the CLI on every PR anyway.
"""

import json
import os
import threading
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from dinunet_implementations_tpu.core.config import NNComputation, TrainConfig
from dinunet_implementations_tpu.runner.registry import get_task
from dinunet_implementations_tpu.serving import (
    AutotunerDaemon,
    CheckpointWatcher,
    DelayAutotuner,
    InferenceEngine,
    Microbatcher,
    PublishController,
    ReplicaSet,
    RequestError,
    RequestFuture,
    home_slot,
)
from dinunet_implementations_tpu.serving.engine import ServingError
from dinunet_implementations_tpu.telemetry.bus import MetricsBus
from dinunet_implementations_tpu.telemetry.hist import (
    HistogramShapeError,
    LogHistogram,
)
from dinunet_implementations_tpu.trainer.steps import FederatedTask


# ---------------------------------------------------------------------------
# fixtures (tiny CPU corners; conftest forces 8 virtual devices)
# ---------------------------------------------------------------------------


def _ica_cfg():
    return TrainConfig(
        task_id=NNComputation.TASK_ICA, epochs=1, batch_size=4, seed=5,
    ).with_overrides({"ica_args": {
        "num_components": 3, "window_size": 4, "temporal_size": 32,
        "window_stride": 4, "input_size": 8, "hidden_size": 6,
        "bidirectional": False,
    }})


def _fs_cfg():
    return TrainConfig(
        task_id=NNComputation.TASK_FREE_SURFER, epochs=1, batch_size=4,
        seed=3,
    ).with_overrides({"fs_args": {"input_size": 6, "hidden_sizes": [8]}})


def _init(cfg, sample):
    task = FederatedTask(get_task(cfg.task_id).build_model(cfg))
    params, stats = task.init_variables(jax.random.PRNGKey(0), sample)
    return task, params, stats


@pytest.fixture(scope="module")
def ica_env():
    cfg = _ica_cfg()
    task, params, stats = _init(cfg, jnp.ones((2, 8, 3, 4)))
    return cfg, task, params, stats


@pytest.fixture(scope="module")
def fs_env():
    cfg = _fs_cfg()
    task, params, stats = _init(cfg, jnp.ones((4, 6)))
    return cfg, task, params, stats


def _make_fleet(env, replicas=2, **kw):
    cfg, _, params, stats = env
    kw.setdefault("row_buckets", (1, 2, 4))
    kw.setdefault("stream_buckets", (1, 2))
    kw.setdefault("stream_chunk", 4)
    kw.setdefault("stream_slots", 4)
    kw.setdefault("max_delay_ms", 1.0)
    kw.setdefault("supervise_interval_s", 0.05)
    kw.setdefault("bus", MetricsBus())
    fleet = ReplicaSet(cfg, replicas=replicas, params=params,
                       batch_stats=stats, **kw)
    fleet.warmup()
    return fleet


def _seq(seed=1, windows=12):
    return np.random.default_rng(seed).normal(
        size=(windows, 3, 4)
    ).astype(np.float32)


def _wait_restart(fleet, slot, want, timeout=30.0):
    deadline = time.monotonic() + timeout
    while time.monotonic() < deadline:
        if fleet.restarts >= want and fleet._replica_alive(slot):
            return
        time.sleep(0.02)
    raise AssertionError(f"replica {slot} did not restart in {timeout}s")


# ---------------------------------------------------------------------------
# sharded session affinity
# ---------------------------------------------------------------------------


def test_home_slot_is_stable_and_covers_shards():
    sids = [f"session-{i}" for i in range(64)]
    slots = [home_slot(s, 4) for s in sids]
    assert slots == [home_slot(s, 4) for s in sids]  # deterministic
    assert set(slots) == {0, 1, 2, 3}  # every shard gets sessions
    assert all(0 <= s < 4 for s in slots)


@pytest.mark.slow
def test_sessions_never_split_across_replicas(ica_env):
    """Every chunk of a session routes to its home replica; afterwards each
    session id is resident in EXACTLY one replica's session table."""
    fleet = _make_fleet(ica_env, replicas=2, stream_slots=8)
    try:
        sids = [f"aff-{i}" for i in range(6)]
        for sid in sids:
            seq = _seq(seed=hash(sid) % 1000)
            for lo in range(0, 12, 4):
                fleet.stream(sid, seq[lo:lo + 4]).result()
            assert fleet.replica_of(sid) == home_slot(sid, 2)
        for sid in sids:
            residents = [
                i for i, eng in enumerate(fleet._engines)
                if eng.sessions.slot_of(sid) is not None
            ]
            assert residents == [home_slot(sid, 2)], sid
    finally:
        fleet.close()


@pytest.mark.slow
def test_eviction_and_generation_discipline_per_shard(ica_env):
    """LRU eviction and generation bumps happen inside ONE shard's table —
    traffic on one replica cannot evict the other replica's sessions."""
    fleet = _make_fleet(ica_env, replicas=2, stream_slots=2)
    try:
        # pin one session on each shard, then overflow shard 0 only
        by_home = {0: [], 1: []}
        i = 0
        while len(by_home[0]) < 4 or len(by_home[1]) < 1:
            sid = f"evict-{i}"
            i += 1
            h = home_slot(sid, 2)
            if len(by_home[h]) < (4 if h == 0 else 1):
                by_home[h].append(sid)
        keeper = by_home[1][0]
        fleet.stream(keeper, _seq()[:4]).result()
        for sid in by_home[0]:  # 4 sessions through 2 slots → evictions
            fleet.stream(sid, _seq()[:4]).result()
        e0, e1 = fleet._engines
        assert e0.sessions.evictions >= 2
        assert e1.sessions.evictions == 0
        assert e1.sessions.slot_of(keeper) is not None  # untouched shard
        # an evicted session comes back FRESH at a bumped generation
        victim = by_home[0][0]
        assert e0.sessions.slot_of(victim) is None
        slot, gen, fresh = e0.sessions.resolve(victim)
        assert fresh and gen == 2
    finally:
        fleet.close()


# ---------------------------------------------------------------------------
# crash → supervised restart → fresh-gate re-home
# ---------------------------------------------------------------------------


@pytest.mark.slow
def test_rehomed_session_replays_bit_exact_from_fresh_gate(ica_env):
    """Kill a replica mid-conversation: the supervisor restarts the slot at
    a bumped membership generation, the router drops every route into it,
    and a client replaying its session from the start lands BITWISE on the
    original answers — the fresh gate zeroed the carry, nothing stale
    carried over."""
    fleet = _make_fleet(ica_env, replicas=2)
    try:
        sid = next(
            f"victim-{i}" for i in range(100)
            if home_slot(f"victim-{i}", 2) == 0
        )
        seq = _seq(seed=9)
        ref = [
            np.asarray(fleet.stream(sid, seq[lo:lo + 4]).result()["probs"])
            for lo in range(0, 12, 4)
        ]
        gen_before = fleet.table.generation_of("replica-0")
        fleet.kill_replica(0)
        _wait_restart(fleet, 0, want=1)
        assert fleet.table.generation_of("replica-0") == gen_before + 1
        assert fleet.replica_of(sid) is None  # route dropped with the slot
        got = [
            np.asarray(fleet.stream(sid, seq[lo:lo + 4]).result()["probs"])
            for lo in range(0, 12, 4)
        ]
        for a, b in zip(got, ref):
            np.testing.assert_array_equal(a, b)
        assert fleet.restarts == 1
        fleet.assert_no_compiles()
    finally:
        fleet.close()


@pytest.mark.slow
def test_restarted_replica_serves_current_weights(ica_env):
    """A replica restarted AFTER a hot-swap must serve the published
    params, not the boot checkpoint — the fleet re-seeds restarts from its
    host-side live-weights copy."""
    cfg, task, params, stats = ica_env
    fleet = _make_fleet(ica_env, replicas=2)
    try:
        new_params = jax.tree.map(lambda x: np.asarray(x) + 0.01, params)
        fleet.swap_params(new_params, stats)
        fleet.kill_replica(0)
        _wait_restart(fleet, 0, want=1)
        sid = next(
            f"w-{i}" for i in range(100) if home_slot(f"w-{i}", 2) == 0
        )
        seq = _seq(seed=11)
        got = np.asarray(fleet.stream(sid, seq[:4]).result()["probs"])
        with InferenceEngine(
            cfg, params=new_params, batch_stats=stats, row_buckets=(1,),
            stream_buckets=(1,), stream_chunk=4, stream_slots=2,
            max_delay_ms=1.0,
        ) as ref_eng:
            ref_eng.warmup()
            ref = np.asarray(ref_eng.stream("r", seq[:4]).result()["probs"])
        np.testing.assert_array_equal(got, ref)
    finally:
        fleet.close()


# ---------------------------------------------------------------------------
# bit-exactness vs the single-engine reference, across swaps
# ---------------------------------------------------------------------------


@pytest.mark.slow
def test_fleet_bit_exact_vs_single_engine_every_bucket(ica_env):
    cfg, task, params, stats = ica_env
    rng = np.random.default_rng(3)
    fleet = _make_fleet(ica_env, replicas=2)
    try:
        with InferenceEngine(
            cfg, params=params, batch_stats=stats, row_buckets=(1, 2, 4),
            streaming=False, max_delay_ms=1.0,
        ) as ref_eng:
            ref_eng.warmup()
            for rows in (1, 2, 4):
                x = rng.normal(size=(rows, 8, 3, 4)).astype(np.float32)
                got = np.asarray(fleet.submit(x).result())
                ref = np.asarray(ref_eng.submit(x).result())
                np.testing.assert_array_equal(got, ref)
    finally:
        fleet.close()


@pytest.mark.slow
def test_two_hot_swaps_zero_compile_and_bit_exact(ica_env):
    """The acceptance claim: CompileGuard stays at max_compiles=0 ACROSS
    two publishes, and after each swap the fleet's answers are bitwise the
    single-engine reference built directly on the swapped params."""
    cfg, task, params, stats = ica_env
    rng = np.random.default_rng(4)
    probes = {
        rows: rng.normal(size=(rows, 8, 3, 4)).astype(np.float32)
        for rows in (1, 2, 4)
    }

    def reference(p):
        with InferenceEngine(
            cfg, params=p, batch_stats=stats, row_buckets=(1, 2, 4),
            streaming=False, max_delay_ms=1.0,
        ) as eng:
            eng.warmup()
            return {
                rows: np.asarray(eng.submit(x).result())
                for rows, x in probes.items()
            }

    p1 = jax.tree.map(lambda x: np.asarray(x) + 0.01, params)
    p2 = jax.tree.map(lambda x: np.asarray(x) - 0.02, params)
    fleet = _make_fleet(ica_env, replicas=2, streaming=False)
    try:
        for cand in (p1, p2):
            got_pause = fleet.swap_params(cand, stats)
            assert got_pause["pause_ms"] >= 0
            assert len(got_pause["per_replica"]) == 2
            ref = reference(cand)
            for rows, x in probes.items():
                np.testing.assert_array_equal(
                    np.asarray(fleet.submit(x).result()), ref[rows]
                )
        fleet.assert_no_compiles()  # the guard spans both publishes
        summary = fleet.close()
        assert summary["swaps"] == 4  # 2 publishes × 2 replicas
        assert summary["compiles_after_warmup"] == 0
    except BaseException:
        fleet.close()
        raise


@pytest.mark.slow
def test_swap_refuses_shape_mismatch(ica_env):
    cfg, task, params, stats = ica_env
    fleet = _make_fleet(ica_env, replicas=2, streaming=False)
    try:
        bad = jax.tree.map(
            lambda x: np.zeros(np.asarray(x).shape + (1,), np.float32),
            params,
        )
        with pytest.raises(ServingError, match="hot-swap refused"):
            fleet.swap_params(bad, stats)
        # the live weights never moved
        x = np.zeros((1, 8, 3, 4), np.float32)
        got = np.asarray(fleet.submit(x).result())
        assert np.all(np.isfinite(got))
    finally:
        fleet.close()


# ---------------------------------------------------------------------------
# publish plane: gauntlet + rollback
# ---------------------------------------------------------------------------


class _ListSink:
    def __init__(self):
        self.rows = []

    def append(self, row):
        self.rows.append(row)

    def close(self):
        pass


@pytest.mark.slow
def test_publish_gauntlet_and_slo_rollback(fs_env):
    """Stale-digest gate, shadow rejection of a non-finite candidate,
    healthy probation release, and an induced SLO-burn rollback restoring
    the retained weights — every step emitting its schema row."""
    cfg, task, params, stats = fs_env
    bus = MetricsBus()
    sink = _ListSink()
    rng = np.random.default_rng(0)
    with InferenceEngine(
        cfg, params=params, batch_stats=stats, row_buckets=(2, 4),
        streaming=False, max_delay_ms=1.0, bus=bus,
    ) as eng:
        eng.warmup()
        for _ in range(8):
            eng.submit(rng.normal(size=(2, 6)).astype(np.float32)).result()
        pc = PublishController(
            eng, bus=bus, sink=sink, p99_target_ms=50.0,
            rollback_burn=1.0, min_window_samples=5,
        )
        cand = jax.tree.map(lambda x: np.asarray(x) + 0.01, params)
        assert pc.publish(cand, stats, digest="d1")["outcome"] == "swapped"
        assert pc.publish(
            cand, stats, digest="d1"
        )["outcome"] == "rejected-stale"
        bad = jax.tree.map(
            lambda x: np.full_like(np.asarray(x), np.nan), params
        )
        row = pc.publish(bad, stats, digest="d2")
        assert row["outcome"] == "rejected-shadow"
        assert row["shadow"]["finite"] is False
        assert pc.live_digest == "d1"  # live params never moved

        # probation: too-thin window → no verdict; then a healthy release
        assert pc.check_rollback() is None
        for _ in range(6):
            eng.submit(rng.normal(size=(2, 6)).astype(np.float32)).result()
        verdict = pc.check_rollback()
        assert verdict["rolled_back"] is False
        assert pc.check_rollback() is None  # probation is one verdict

        # induced burn: swap again, poison the latency series, roll back
        assert pc.publish(
            jax.tree.map(lambda x: np.asarray(x) + 0.02, params),
            stats, digest="d3",
        )["outcome"] == "swapped"
        for _ in range(30):
            bus.observe("serving_request_latency_ms", 500.0, lane="infer")
        verdict = pc.check_rollback()
        assert verdict["rolled_back"] is True
        assert verdict["burn"] > 1.0
        assert pc.live_digest == "d1"  # the retained weights are live again
        eng.assert_no_compiles()  # every swap + rollback was a donation

    # schema: every emitted row carries its kind's required keys
    from dinunet_implementations_tpu.telemetry.sink import ROW_REQUIRED

    kinds = [r["kind"] for r in sink.rows]
    assert kinds.count("publish") == 4 and kinds.count("rollback") == 2
    for row in sink.rows:
        assert ROW_REQUIRED[row["kind"]] <= set(row), row


def test_checkpoint_watcher_fingerprint_and_digest(tmp_path):
    path = str(tmp_path / "publish.json")
    w = CheckpointWatcher(path)
    assert w.poll() is None  # missing file

    def announce(digest, epoch):
        tmp = path + ".tmp"
        with open(tmp, "w") as f:
            json.dump({"path": "ck.msgpack", "digest": digest,
                       "epoch": epoch}, f)
        os.replace(tmp, path)

    announce("aaa", 1)
    got = w.poll()
    assert got is not None and got["digest"] == "aaa"
    assert w.poll() is None  # unchanged fingerprint
    announce("aaa", 2)  # rewritten, same digest → still stale
    assert w.poll() is None
    announce("bbb", 3)
    assert w.poll()["digest"] == "bbb"
    with open(path + ".tmp2", "w") as f:
        f.write("{not json")
    os.replace(path + ".tmp2", path)
    assert w.poll() is None  # unparseable: skip, don't raise


def test_params_digest_keyed_by_values_and_shapes(fs_env):
    from dinunet_implementations_tpu.trainer.checkpoint import params_digest

    cfg, task, params, stats = fs_env
    d1 = params_digest(params, stats)
    assert d1 == params_digest(params, stats)  # deterministic
    moved = jax.tree.map(lambda x: np.asarray(x) + 1e-6, params)
    assert params_digest(moved, stats) != d1


# ---------------------------------------------------------------------------
# admission: priority, deadline, max_queue
# ---------------------------------------------------------------------------


class _Req:
    def __init__(self, n, priority=0, deadline_ms=None):
        self.rows = np.zeros((n, 2), np.float32)
        self.priority = priority
        self.deadline_ms = deadline_ms
        self.future = RequestFuture()


def _gated_dispatch(order, gate):
    """Dispatch that records batch identity and blocks on ``gate`` for the
    FIRST batch only — holds the lane so later submissions pile up
    pending."""
    first = threading.Event()

    def dispatch(batch, bucket):
        if not first.is_set():
            first.set()
            gate.wait(10)
        order.append([r.tag for r in batch])
        for r in batch:
            r.future.set_result(None)

    return dispatch


def test_priority_overtakes_fifo_within_pending():
    order, gate = [], threading.Event()
    mb = Microbatcher(
        _gated_dispatch(order, gate), buckets=(2,), max_delay_ms=5.0
    )
    reqs = {}
    for tag, prio in (("blocker", 0), ("lo", 0), ("mid", 1), ("hi", 5)):
        r = _Req(2, priority=prio)
        r.tag = tag
        reqs[tag] = r
    mb.submit(reqs["blocker"])
    while not mb.stats["dispatches"] and mb.depth():
        time.sleep(0.002)  # blocker is IN dispatch, lane held
    for tag in ("lo", "mid", "hi"):  # FIFO arrival, priority order out
        mb.submit(reqs[tag])
    gate.set()
    for r in reqs.values():
        r.future.result(timeout=10)
    mb.close()
    assert order == [["blocker"], ["hi"], ["mid"], ["lo"]]


def test_default_priority_preserves_fifo():
    order, gate = [], threading.Event()
    mb = Microbatcher(
        _gated_dispatch(order, gate), buckets=(2,), max_delay_ms=5.0
    )
    reqs = []
    for i in range(4):
        r = _Req(2)
        r.tag = i
        reqs.append(r)
        mb.submit(r)
    gate.set()
    for r in reqs:
        r.future.result(timeout=10)
    mb.close()
    assert order == [[0], [1], [2], [3]]


def test_deadline_shedding_fails_fast():
    order, gate = [], threading.Event()
    mb = Microbatcher(
        _gated_dispatch(order, gate), buckets=(2,), max_delay_ms=1.0
    )
    blocker = _Req(2)
    blocker.tag = "blocker"
    mb.submit(blocker)
    doomed = _Req(2, deadline_ms=5.0)
    doomed.tag = "doomed"
    survivor = _Req(2, deadline_ms=60_000.0)
    survivor.tag = "survivor"
    mb.submit(doomed)
    mb.submit(survivor)
    time.sleep(0.05)  # doomed's 5 ms deadline lapses while the lane holds
    gate.set()
    with pytest.raises(RequestError, match="deadline"):
        doomed.future.result(timeout=10)
    survivor.future.result(timeout=10)
    mb.close()
    assert mb.stats["shed"] == 1
    assert ["survivor"] in order and ["doomed"] not in order


def test_max_queue_sheds_at_admission():
    bus = MetricsBus()
    order, gate = [], threading.Event()
    mb = Microbatcher(
        _gated_dispatch(order, gate), buckets=(2,), max_delay_ms=1.0,
        max_queue=1, bus=bus,
    )
    blocker = _Req(2)
    blocker.tag = "blocker"
    mb.submit(blocker)
    while not mb.stats["dispatches"] and mb.depth():
        time.sleep(0.002)
    queued = _Req(2)
    queued.tag = "queued"
    mb.submit(queued)  # depth 1 = bound
    with pytest.raises(RequestError, match="queue full"):
        mb.submit(_Req(2))
    gate.set()
    queued.future.result(timeout=10)
    mb.close()
    assert mb.stats["shed"] == 1
    sheds = {
        k: v for k, v in bus.snapshot()["counters"].items()
        if k.startswith("serving_shed_total") and 'why="queue_full"' in k
    }
    assert list(sheds.values()) == [1]


# ---------------------------------------------------------------------------
# the p99-targeted max-delay autotuner
# ---------------------------------------------------------------------------


class _Lane:
    def __init__(self, delay_ms=2.0):
        self.max_delay_s = delay_ms / 1e3
        self.name = "infer"
        self.labels = {}


def _hist(values):
    h = LogHistogram()
    for v in values:
        h.record(v)
    return h


def test_autotuner_shrinks_only_on_certain_violations():
    lane = _Lane(delay_ms=2.0)
    t = DelayAutotuner(lane, p99_target_ms=10.0, budget=0.01,
                       min_samples=10)
    # 10% of samples certainly above 10 ms target → shrink
    assert t.step(_hist([1.0] * 90 + [100.0] * 10)) == "shrink"
    assert lane.max_delay_s == pytest.approx(1e-3)
    # samples NEAR the target (same bucket) are not certain violations:
    # the dead band holds instead of flapping
    assert t.step(_hist([10.0] * 100)) == "hold"


def test_autotuner_grows_only_with_proven_slack():
    lane = _Lane(delay_ms=2.0)
    t = DelayAutotuner(lane, p99_target_ms=100.0, budget=0.01,
                       headroom=0.5, min_samples=10)
    # upper-edge p99 well under target × headroom → provable slack
    assert t.step(_hist([1.0] * 100)) == "grow"
    assert lane.max_delay_s == pytest.approx(2.5e-3)
    # p99 between headroom and target: neither certainty → hold
    assert t.step(_hist([80.0] * 100)) == "hold"


def test_autotuner_holds_on_thin_windows_and_clamps():
    lane = _Lane(delay_ms=0.05)
    t = DelayAutotuner(lane, p99_target_ms=10.0, min_samples=50,
                       min_delay_ms=0.05)
    assert t.step(_hist([100.0] * 10)) == "hold"  # too few samples
    assert t.step(None) == "hold"
    # parked at the min clamp: a shrink that cannot move reports hold
    assert t.step(_hist([100.0] * 60)) == "hold"
    assert lane.max_delay_s == pytest.approx(5e-5)
    with pytest.raises(ValueError):
        DelayAutotuner(_Lane(), p99_target_ms=1.0, headroom=1.5)
    with pytest.raises(ValueError):
        DelayAutotuner(_Lane(), p99_target_ms=1.0, shrink=1.5)


def test_autotuner_daemon_steps_on_window_deltas():
    bus = MetricsBus()
    lane = _Lane(delay_ms=2.0)
    tuner = DelayAutotuner(lane, p99_target_ms=10.0, budget=0.01,
                           min_samples=10, bus=bus)
    daemon = AutotunerDaemon(bus, [tuner], interval_s=60.0)
    for _ in range(20):
        bus.observe("serving_request_latency_ms", 1.0, lane="infer")
    daemon.tick()  # first tick: baseline only, no window yet
    assert tuner.decisions == {"shrink": 0, "grow": 0, "hold": 1}
    for _ in range(20):
        bus.observe("serving_request_latency_ms", 100.0, lane="infer")
    daemon.tick()  # window = the 20 slow samples only → shrink
    assert tuner.decisions["shrink"] == 1
    assert lane.max_delay_s == pytest.approx(1e-3)
    daemon.stop()


@pytest.mark.slow
def test_engine_wires_priority_and_deadline(fs_env):
    cfg, task, params, stats = fs_env
    with InferenceEngine(
        cfg, params=params, batch_stats=stats, row_buckets=(2,),
        streaming=False, max_delay_ms=1.0, max_queue=64,
    ) as eng:
        eng.warmup()
        x = np.zeros((2, 6), np.float32)
        got = eng.submit(x, priority=3, deadline_ms=60_000.0).result()
        assert np.all(np.isfinite(np.asarray(got)))
        assert eng.status()["shed"] == 0


# ---------------------------------------------------------------------------
# histogram windows
# ---------------------------------------------------------------------------


def test_hist_delta_is_exact_window():
    a = _hist([1.0, 5.0, 50.0])
    snap = a.copy()
    for v in (2.0, 200.0):
        a.record(v)
    d = a.delta(snap)
    assert d.count == 2
    assert d.sum == pytest.approx(202.0)
    merged = snap.copy().merge(d)
    assert merged.counts == a.counts and merged.count == a.count


def test_hist_delta_rejects_backwards_series():
    a = _hist([1.0, 2.0, 3.0])
    b = _hist([1.0])
    with pytest.raises(HistogramShapeError, match="backwards"):
        b.delta(a)  # b is not a later snapshot of a's series


# ---------------------------------------------------------------------------
# fleet rollup + status surfaces
# ---------------------------------------------------------------------------


@pytest.mark.slow
def test_fleet_summary_and_status_shapes(ica_env):
    from dinunet_implementations_tpu.telemetry.sink import ROW_REQUIRED

    sink = _ListSink()
    fleet = _make_fleet(ica_env, replicas=2, sink=sink)
    rng = np.random.default_rng(7)
    for _ in range(4):
        fleet.submit(rng.normal(size=(2, 8, 3, 4)).astype(np.float32)).result()
    st = fleet.status()
    assert st["replicas"] == 2 and st["replicas_live"] == 2
    assert set(st["per_replica"]) == {"replica-0", "replica-1"}
    assert st["membership"]["slots"] == ["replica-0", "replica-1"]
    probes = fleet.health_probes()
    assert all(p() for p in probes.values())
    fleet.close()
    # per-replica rows + ONE fleet rollup row, all schema-complete
    rollups = [r for r in sink.rows if r.get("replica") == "fleet"]
    assert len(rollups) == 1
    per_replica = [
        r for r in sink.rows
        if r.get("kind") == "serve_summary" and r.get("replica") != "fleet"
    ]
    assert {r["replica"] for r in per_replica} == {"0", "1"}
    for row in rollups + per_replica:
        assert ROW_REQUIRED["serve_summary"] <= set(row), row
    assert rollups[0]["requests"] == 4
    assert rollups[0]["compiles_after_warmup"] == 0


def test_fleet_rejects_bad_arguments(ica_env):
    cfg = ica_env[0]
    with pytest.raises(ServingError, match=">= 1 replica"):
        ReplicaSet(cfg, replicas=0, params={})
    with pytest.raises(ServingError, match="checkpoint path or explicit"):
        ReplicaSet(cfg, replicas=1)
    fleet = ReplicaSet(cfg, replicas=1, params=ica_env[2],
                       batch_stats=ica_env[3])
    with pytest.raises(ServingError, match="warmup"):
        fleet.submit(np.zeros((1, 8, 3, 4), np.float32))
