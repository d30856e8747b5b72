"""Serving path (r15): session-slot cache, continuous microbatcher,
AOT-compiled InferenceEngine, and the bit-exactness bridge.

The load-bearing claims, as tests:

- served probabilities reproduce the trainer's eval path BIT-FOR-BIT on the
  same checkpoint and batches (FS/MSANNet incl. mask-weighted batch-stat
  padding, ICA-LSTM) — the shared ``eval_forward`` (trainer/steps.py);
- streaming in chunks is BITWISE identical to full-sequence replay (the
  scan-accumulated carry of models/icalstm.py ICALstmStream), and matches
  the batched full-sequence forward;
- the request path never compiles after warmup (CompileGuard at
  max_compiles=0 across a 100-request mixed-bucket run) and session state
  is O(1): the carry table's shape never depends on session history;
- the serving S-rule cells are clean and their negative fixtures trip
  (S001 sneaked psum, S003 broken table aliasing, S005 drifted program).
"""

import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from dinunet_implementations_tpu.checks import semantic as sem
from dinunet_implementations_tpu.checks.sanitize import SanitizerViolation
from dinunet_implementations_tpu.core.config import NNComputation, TrainConfig
from dinunet_implementations_tpu.data.api import SiteArrays
from dinunet_implementations_tpu.data.batching import plan_eval
from dinunet_implementations_tpu.runner.registry import get_task
from dinunet_implementations_tpu.serving import (
    InferenceEngine,
    Microbatcher,
    RequestError,
    RequestFuture,
    SessionError,
    SessionTable,
)
from dinunet_implementations_tpu.serving.engine import ServingError
from dinunet_implementations_tpu.trainer.loop import FederatedTrainer
from dinunet_implementations_tpu.trainer.steps import FederatedTask, eval_forward


# ---------------------------------------------------------------------------
# session table
# ---------------------------------------------------------------------------


def test_session_table_dense_first_and_generations():
    t = SessionTable(3)
    assert t.resolve("a") == (0, 1, True)
    assert t.resolve("b") == (1, 1, True)
    assert t.resolve("a") == (0, 1, False)  # returning stream keeps its slot
    t.close("a")
    assert t.resolve("c") == (0, 1, True)  # lowest free slot
    assert t.resolve("a") == (2, 2, True)  # rejoin bumps the generation
    assert t.trash_slot == 3


def test_session_table_lru_eviction():
    t = SessionTable(2)
    t.resolve("a")
    t.resolve("b")
    t.resolve("a")  # touch a → b is LRU
    slot, gen, fresh = t.resolve("c")
    assert (slot, fresh) == (1, True)  # b's slot reused
    assert t.slot_of("b") is None
    assert t.evictions == 1
    # the evicted session comes back fresh at a bumped generation
    slot, gen, fresh = t.resolve("b")
    assert fresh and gen == 2


def test_session_table_errors():
    with pytest.raises(SessionError):
        SessionTable(0)
    t = SessionTable(1)
    with pytest.raises(SessionError):
        t.resolve("")
    with pytest.raises(SessionError):
        t.close("ghost")


# ---------------------------------------------------------------------------
# microbatcher
# ---------------------------------------------------------------------------


class _FakeReq:
    def __init__(self, n, key=None):
        self.rows = np.zeros((n, 2), np.float32)
        self.key = key
        self.future = RequestFuture()


def _collect_batches(batcher_kwargs, reqs):
    batches = []

    def dispatch(batch, bucket):
        batches.append((tuple(len(r.rows) for r in batch), bucket))
        for r in batch:
            r.future.set_result(len(r.rows))

    mb = Microbatcher(dispatch, **batcher_kwargs)
    for r in reqs:
        mb.submit(r)
    for r in reqs:
        r.future.result(timeout=10)
    mb.close()
    return batches


def test_microbatcher_coalesces_to_bucket():
    reqs = [_FakeReq(2) for _ in range(4)]
    batches = _collect_batches(
        dict(buckets=(8,), max_delay_ms=200.0), reqs
    )
    # all four (8 rows) coalesce into ONE full-bucket dispatch
    assert batches == [((2, 2, 2, 2), 8)]


def test_microbatcher_max_delay_fires_partial_bucket():
    reqs = [_FakeReq(3)]
    batches = _collect_batches(
        dict(buckets=(4, 16), max_delay_ms=5.0), reqs
    )
    # nothing else arrives: the delay budget fires the smallest fitting
    # bucket with one pad row
    assert batches == [((3,), 4)]


def test_microbatcher_oversize_rejected():
    mb = Microbatcher(lambda b, k: None, buckets=(4,), max_delay_ms=1.0)
    with pytest.raises(RequestError):
        mb.submit(_FakeReq(5))
    mb.close()


def test_microbatcher_conflict_key_serializes():
    """Two requests with the same key (chunks of one session) must land in
    DIFFERENT dispatches, in order."""
    reqs = [_FakeReq(1, key="s"), _FakeReq(1, key="s"), _FakeReq(1, key="t")]
    batches = _collect_batches(
        dict(buckets=(4,), max_delay_ms=20.0, rows_of=lambda r: 1,
             conflict_key=lambda r: r.key),
        reqs,
    )
    assert len(batches) == 2  # (s, t) then the deferred second s-chunk


def test_microbatcher_dispatch_error_reaches_futures():
    def boom(batch, bucket):
        raise ValueError("kaput")

    mb = Microbatcher(boom, buckets=(4,), max_delay_ms=1.0)
    r = _FakeReq(1)
    mb.submit(r)
    with pytest.raises(ValueError, match="kaput"):
        r.future.result(timeout=10)
    # the lane survives a dispatch error and keeps serving
    r2 = _FakeReq(1)
    mb.submit(r2)
    with pytest.raises(ValueError, match="kaput"):
        r2.future.result(timeout=10)
    mb.close()


# ---------------------------------------------------------------------------
# engine fixtures (tiny CPU corners)
# ---------------------------------------------------------------------------


def _fs_cfg():
    return TrainConfig(
        task_id=NNComputation.TASK_FREE_SURFER, epochs=1, batch_size=4,
        seed=3,
    ).with_overrides({"fs_args": {"input_size": 6, "hidden_sizes": [8]}})


def _ica_cfg():
    return TrainConfig(
        task_id=NNComputation.TASK_ICA, epochs=1, batch_size=4, seed=5,
    ).with_overrides({"ica_args": {
        "num_components": 5, "window_size": 4, "temporal_size": 48,
        "window_stride": 4, "input_size": 12, "hidden_size": 10,
        "bidirectional": False,
    }})


def _init_task(cfg, sample):
    task = FederatedTask(get_task(cfg.task_id).build_model(cfg))
    params, stats = task.init_variables(jax.random.PRNGKey(0), sample)
    return task, params, stats


def _sites(rng, n_sites, n, feat):
    return [
        SiteArrays(
            rng.normal(size=(n,) + feat).astype(np.float32),
            rng.integers(0, 2, n).astype(np.int32),
            np.arange(n, dtype=np.int32),
        )
        for _ in range(n_sites)
    ]


# ---------------------------------------------------------------------------
# bit-exactness bridge: served checkpoint == trainer eval path
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("make_cfg,feat", [
    (_fs_cfg, (6,)),
    (_ica_cfg, (12, 5, 4)),
], ids=["freesurfer-mlp", "ica-lstm"])
def test_served_checkpoint_reproduces_trainer_eval(tmp_path, make_cfg, feat):
    """Train a fold, then serve its checkpoint against the trainer's own
    eval batches (same rows, same masks — for the batch-stat MSANNet the
    eval plan's pad rows ride as weight-0 request rows, keeping them out of
    the BatchNorm statistics exactly like eval).

    Three layers of the bridge:

    - served probs are BITWISE the shared ``eval_forward`` program's output
      (the engine's AOT executable is that exact program);
    - served probs vs the trainer's vmap+scan-wrapped eval: two DIFFERENT
      compiled programs, whose fusions may reassociate a float32 reduction
      (observed at most 1 ulp on CPU: 6e-08 at a probability of 0.54 on
      the ICA-LSTM), so the probs are held to 4 ulp while the
    - recorded eval SCORES (rank/argmax metrics from those probs) must
      reproduce bit-for-bit on both tasks."""
    cfg = make_cfg()
    rng = np.random.default_rng(0)
    train = _sites(rng, 2, 12, feat)
    val = _sites(rng, 2, 6, feat)
    test = _sites(rng, 2, 7, feat)  # 7 → a masked pad row per site at B=4
    trainer = FederatedTrainer(cfg, get_task(cfg.task_id).build_model(cfg),
                               mesh=None, out_dir=str(tmp_path))
    res = trainer.fit(train, val, test, fold=0, verbose=False)
    state = res["state"]
    fb = plan_eval(test, cfg.batch_size)
    probs_ref = np.asarray(trainer.eval_fn(
        state, jnp.asarray(fb.inputs), jnp.asarray(fb.labels),
        jnp.asarray(fb.weights),
    )[0])

    ckpt = os.path.join(
        str(tmp_path), "remote", "simulatorRun", cfg.task_id, "fold_0",
        "checkpoint_best.msgpack",
    )
    eng = InferenceEngine(
        cfg, checkpoint=ckpt, row_buckets=(cfg.batch_size,),
        max_delay_ms=1.0,
    )
    eng.warmup()
    shared = jax.jit(
        lambda p, s, x, w: eval_forward(eng.task, p, s, x, None, w)
    )
    served = np.zeros_like(probs_ref)
    try:
        for s in range(fb.num_sites):
            for t in range(fb.steps):
                got = eng.submit(
                    fb.inputs[s, t], weights=fb.weights[s, t]
                ).result()
                served[s, t] = got
                # the engine's executable IS the shared eval_forward program
                np.testing.assert_array_equal(got, np.asarray(shared(
                    eng._params, eng._stats, jnp.asarray(fb.inputs[s, t]),
                    jnp.asarray(fb.weights[s, t]),
                )))
                np.testing.assert_array_max_ulp(
                    got, probs_ref[s, t], maxulp=4
                )
    finally:
        eng.close()
    # the recorded eval scores reproduce bit-for-bit from the served probs
    m = trainer._new_metrics(served.shape[-1])
    trainer._add_probs(m, served, fb.labels, fb.weights)
    for name, recorded in res["test_scores"].items():
        assert m.value(name) == recorded, name


def test_load_inference_state_strips_train_state(tmp_path):
    """The inference restore is template-free and carries ONLY
    params/batch_stats/meta — no optimizer, engine, health or buffer
    shapes can block serving a checkpoint."""
    from dinunet_implementations_tpu.engines import make_engine
    from dinunet_implementations_tpu.trainer.checkpoint import (
        load_inference_state,
        save_checkpoint,
    )
    from dinunet_implementations_tpu.trainer.steps import (
        init_train_state,
        make_optimizer,
    )

    cfg = _fs_cfg()
    task, params, stats = _init_task(cfg, jnp.ones((4, 6)))
    state = init_train_state(
        task, make_engine("dSGD"), make_optimizer("adam", 1e-3),
        jax.random.PRNGKey(0), jnp.ones((4, 6)), num_sites=3,
    )
    path = str(tmp_path / "ck.msgpack")
    save_checkpoint(path, state, meta={"best_val_epoch": 7})
    p, s, meta = load_inference_state(path)
    assert meta["best_val_epoch"] == 7
    ref = jax.tree.leaves(state.params)
    got = jax.tree.leaves(p)
    assert len(ref) == len(got)
    for a, b in zip(ref, got):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


# ---------------------------------------------------------------------------
# streaming: O(1) session cache
# ---------------------------------------------------------------------------


@pytest.fixture(scope="module")
def ica_engine():
    cfg = _ica_cfg()
    task, params, stats = _init_task(cfg, jnp.ones((2, 12, 5, 4)))
    eng = InferenceEngine(
        cfg, params=params, batch_stats=stats, row_buckets=(1, 2, 4),
        stream_buckets=(1, 2), stream_chunk=4, stream_slots=4,
        max_delay_ms=1.0,
    )
    eng.warmup()
    yield eng, task, params, stats
    eng.close()


def _seq(seed=1, windows=12):
    return np.random.default_rng(seed).normal(
        size=(windows, 5, 4)
    ).astype(np.float32)


def test_streaming_chunked_equals_full_replay(ica_engine):
    """THE streaming claim: a returning stream shipping only its new
    timesteps, chunk by chunk, lands BITWISE on the same answer as replaying
    the whole sequence through the streaming path in one submission —
    the pooled carry accumulates inside the recurrence scan, a strict left
    fold, so chunk boundaries are associativity-free."""
    eng, *_ = ica_engine
    seq = _seq()
    replay = eng.stream("replay-full", seq).result()
    for lo in range(0, len(seq), 4):
        last = eng.stream("replay-chunked", seq[lo:lo + 4]).result()
    np.testing.assert_array_equal(last["probs"], replay["probs"])
    # odd chunk sizes (2+3+7) — chunk padding rides step_valid, still exact
    for lo, hi in ((0, 2), (2, 5), (5, 12)):
        last = eng.stream("replay-ragged", seq[lo:hi]).result()
    np.testing.assert_array_equal(last["probs"], replay["probs"])


def test_streaming_matches_batched_forward(ica_engine):
    """Streaming the full sequence matches the batched full-sequence eval
    forward (the trainer-shared path) — same classifier answer whether the
    sequence arrives at once or as a stream."""
    eng, task, params, stats = ica_engine
    seq = _seq(seed=7)
    got = eng.stream("vs-batched", seq).result()["probs"]
    ref = np.asarray(eval_forward(
        task, params, stats, jnp.asarray(seq[None]), None,
        jnp.ones((1,), jnp.float32),
    ))[0]
    np.testing.assert_allclose(got, ref, atol=1e-6)


def test_streaming_session_isolation_and_restart(ica_engine):
    """Concurrent sessions cannot perturb each other; closing (or evicting)
    a session restarts it fresh — generation bumped, carry zeroed."""
    eng, *_ = ica_engine
    a, b = _seq(seed=2), _seq(seed=3)
    solo = eng.stream("iso-solo", a[:4]).result()["probs"]
    # interleave another session between a's chunks
    r1 = eng.stream("iso-a", a[:4]).result()
    eng.stream("iso-b", b[:4]).result()
    np.testing.assert_array_equal(r1["probs"], solo)
    # restart semantics: close then re-stream == a brand-new session
    eng.close_session("iso-a")
    r2 = eng.stream("iso-a", a[:4]).result()
    assert r2["restarted"] and r2["generation"] == 2
    np.testing.assert_array_equal(r2["probs"], solo)


def test_streaming_state_is_o1(ica_engine):
    """The structural O(1) claim: after arbitrarily long sessions, the
    device-resident session state is still the fixed [slots+1, H] table —
    nothing grows with history (the latency flatness is bench.py --serve's
    half of the claim)."""
    eng, *_ = ica_engine
    shapes_before = {k: v.shape for k, v in eng._table.items()}
    for _ in range(6):  # 6 × 12 windows ≫ any compiled chunk shape
        eng.stream("long-session", _seq(seed=9)).result()
    assert {k: v.shape for k, v in eng._table.items()} == shapes_before


def test_stream_empty_windows_is_loud(ica_engine):
    eng, *_ = ica_engine
    with pytest.raises(ServingError, match="at least one window"):
        eng.stream("empty", np.zeros((0, 5, 4), np.float32))


def test_stream_slots_must_cover_largest_bucket():
    """A dispatch of B sessions needs B distinct slots — fewer would let one
    batch LRU-evict its own members into duplicate scatter indices."""
    cfg = _ica_cfg()
    task, params, stats = _init_task(cfg, jnp.ones((2, 12, 5, 4)))
    with pytest.raises(ServingError, match="below the largest"):
        InferenceEngine(cfg, params=params, batch_stats=stats,
                        stream_buckets=(1, 4), stream_slots=2)


def test_chained_future_surfaces_first_chunk_error():
    """A multi-chunk stream()'s future must raise an EARLY chunk's dispatch
    error even when later chunks resolved — a silently truncated session
    history must never read as success."""
    from dinunet_implementations_tpu.serving.microbatch import ChainedFuture

    first, last = RequestFuture(), RequestFuture()
    first.set_exception(ValueError("chunk 1 died"))
    last.set_result({"probs": np.zeros(2)})
    chained = ChainedFuture([first, last])
    assert chained.done()
    with pytest.raises(ValueError, match="chunk 1 died"):
        chained.result()


def test_streaming_refused_for_bidirectional():
    cfg = _ica_cfg().with_overrides({"ica_args": {"bidirectional": True}})
    task, params, stats = _init_task(cfg, jnp.ones((2, 12, 5, 4)))
    eng = InferenceEngine(cfg, params=params, batch_stats=stats,
                          row_buckets=(2,), max_delay_ms=1.0)
    eng.warmup()
    try:
        assert not eng.streaming
        with pytest.raises(ServingError, match="bidirectional"):
            eng.stream("s", _seq()[:4])
    finally:
        eng.close()


# ---------------------------------------------------------------------------
# compile-free request path
# ---------------------------------------------------------------------------


def test_mixed_100_request_run_compiles_nothing(ica_engine):
    """The acceptance gate: 100 mixed batched+streaming requests across
    every bucket — zero compiles after warmup (CompileGuard max_compiles=0),
    every request answered, no bucket misses."""
    eng, task, params, stats = ica_engine
    rng = np.random.default_rng(11)
    futures = []
    for i in range(100):
        if i % 3 == 2:
            futures.append(eng.stream(
                f"mix-{i % 5}",
                rng.normal(size=(1 + i % 6, 5, 4)).astype(np.float32),
            ))
        else:
            n = (1, 2, 3, 4)[i % 4]
            futures.append(eng.submit(
                rng.normal(size=(n, 12, 5, 4)).astype(np.float32)
            ))
    for f in futures:
        f.result()
    eng.assert_no_compiles()
    assert sum(eng.compiles_after_warmup().values()) == 0
    assert eng.stats["requests"] >= 100


def test_oversize_request_is_loud_not_a_recompile(ica_engine):
    eng, *_ = ica_engine
    with pytest.raises(RequestError):
        eng.submit(np.zeros((5, 12, 5, 4), np.float32))  # max bucket is 4
    eng.assert_no_compiles()


def test_compile_guard_trips_on_request_path_tracing(ica_engine):
    """If anything invoked the jitted entries post-warmup (a silent
    fallback), the guard must fail loudly."""
    eng, task, params, stats = ica_engine
    eng._infer_jit(
        eng._params, eng._stats, jnp.zeros((3, 12, 5, 4)), jnp.ones((3,))
    )  # simulate a fallback trace at an uncompiled shape
    with pytest.raises(SanitizerViolation):
        eng.assert_no_compiles()
    # restore the guard for the other module-scoped tests
    from dinunet_implementations_tpu.checks.sanitize import CompileGuard

    eng._guard = CompileGuard(
        {"infer_fn": eng._infer_jit, "stream_fn": eng._stream_jit},
        max_compiles=0, label="serving",
    )


# ---------------------------------------------------------------------------
# serving telemetry rows
# ---------------------------------------------------------------------------


def test_serving_telemetry_rows_validate(tmp_path):
    from dinunet_implementations_tpu.telemetry.sink import (
        FitTelemetry,
        load_metrics,
        validate_metrics_rows,
    )

    cfg = _fs_cfg()
    task, params, stats = _init_task(cfg, jnp.ones((4, 6)))
    sink = FitTelemetry.open(str(tmp_path / "serving"), cfg)
    eng = InferenceEngine(cfg, params=params, batch_stats=stats,
                          row_buckets=(2, 4), max_delay_ms=1.0, sink=sink)
    eng.warmup()
    for _ in range(5):
        eng.submit(np.zeros((2, 6), np.float32)).result()
    summary = eng.close()
    rows = load_metrics(str(tmp_path / "serving" / "metrics.jsonl"))
    assert validate_metrics_rows(rows) == []
    kinds = {r["kind"] for r in rows}
    assert {"dispatch", "serve_summary"} <= kinds
    assert summary["latency_ms_p50"] is not None
    assert summary["compiles_after_warmup"] == 0
    assert summary["requests"] == 5


# ---------------------------------------------------------------------------
# serving semantic cells (S001 / S003 / S005) + negative fixtures
# ---------------------------------------------------------------------------


def test_serving_cells_clean():
    assert sem.run_serving_checks() == []


def test_s001_serving_negative_a_sneaked_psum():
    """A serving forward that synchronizes across a mesh axis must trip the
    zero-collectives rule."""
    from dinunet_implementations_tpu.parallel.mesh import SITE_AXIS

    def bad_forward(x):
        return jax.vmap(
            lambda r: jax.lax.psum(r, SITE_AXIS), axis_name=SITE_AXIS
        )(x)

    jaxpr = jax.make_jaxpr(bad_forward)(jnp.ones((2, 3)))
    fs = sem.check_no_collectives(
        sem.audit_jaxpr(jaxpr).collectives, "trace://serving/fixture"
    )
    assert [f.rule for f in fs] == ["S001"]
    assert "psum" in fs[0].snippet


def test_s003_serving_negative_broken_table_aliasing():
    """A streaming step whose carry update cannot alias the donated table
    (here: a table leaf with no same-shape output) is the silent
    double-residency bug the serving S003 cell guards."""
    def bad_stream(table, ix, x):
        h = table["h"][ix] + x
        return h.sum()  # the donated table has NO aliasable output

    f = jax.jit(bad_stream, donate_argnums=(0,))
    args = ({"h": jnp.ones((4, 3))}, jnp.zeros((2,), jnp.int32),
            jnp.ones((2, 3)))
    comp = f.lower(*args).compile()
    fs = sem.check_donation(comp, args, (0,), "trace://serving/fixture")
    assert [f.rule for f in fs] == ["S003"]


def test_s005_serving_negative_drifted_program():
    """If the batched serving lane drifts from the eval forward (any extra
    op), the identity cell must fire."""
    cfg = _fs_cfg()
    task, params, stats = _init_task(cfg, jnp.ones((4, 6)))
    args = (params, stats, jnp.zeros((4, 6)), jnp.ones((4,)))
    ref = jax.jit(
        lambda p, s, x, w: eval_forward(task, p, s, x, None, w)
    ).lower(*args).as_text()
    drifted = jax.jit(
        lambda p, s, x, w: eval_forward(task, p, s, x, None, w) * 1.0000001
    ).lower(*args).as_text()
    fs = sem.check_lowering_identity(
        [("serve-infer-is-eval-forward", ref, drifted, True)],
        path_prefix="lowering://serving/",
    )
    assert [f.rule for f in fs] == ["S005"]