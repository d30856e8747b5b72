"""Telemetry tests (telemetry/): span tracer nesting/closing across the
prefetch thread and on Preempted, the telemetry="off" program-identity
regression, on-device round metrics vs bit-exact host recomputation for dSGD
and rankDAD, manifest/metrics.jsonl schema round-trip, and the report CLI.
"""

import json
import os
import threading

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from dinunet_implementations_tpu import TrainConfig
from dinunet_implementations_tpu.checks import CompileGuard
from dinunet_implementations_tpu.data.api import SiteArrays
from dinunet_implementations_tpu.engines import make_engine
from dinunet_implementations_tpu.models import MSANNet
from dinunet_implementations_tpu.parallel.mesh import SITE_AXIS
from dinunet_implementations_tpu.robustness import FaultPlan, Preempted
from dinunet_implementations_tpu.telemetry import SpanTracer, duration, scopes
from dinunet_implementations_tpu.telemetry.metrics import (
    TELEMETRY_KEYS,
    default_round_telemetry,
    payload_bytes_of,
    telemetry_summary,
    tree_sq_sum,
)
from dinunet_implementations_tpu.telemetry.sink import (
    MANIFEST_FILE,
    METRICS_FILE,
    TRACE_CHROME_FILE,
    TRACE_JSONL_FILE,
    load_metrics,
    validate_manifest,
    validate_metrics_rows,
)
from dinunet_implementations_tpu.trainer import (
    FederatedTask,
    FederatedTrainer,
    init_train_state,
    load_checkpoint,
    make_optimizer,
    make_train_epoch_fn,
    save_checkpoint,
)
from dinunet_implementations_tpu.trainer.logs import telemetry_log_fields


# ---------------------------------------------------------------------------
# span tracer
# ---------------------------------------------------------------------------


def test_spans_nest_and_close_across_threads():
    """One tracer serves the main loop AND a worker thread (the prefetch
    planner): spans nest per thread, depths/threads are recorded, and the
    cross-thread events land in one buffer."""
    tracer = SpanTracer()

    def worker():
        for _ in range(2):
            with tracer.span("plan-build"):
                pass

    with tracer.span("fit"):
        t = threading.Thread(target=worker, name="worker")
        with tracer.span("epoch"):
            t.start()
            t.join()
    evs = tracer.events()
    by_name = {e["name"]: e for e in evs}
    assert by_name["fit"]["depth"] == 0
    assert by_name["epoch"]["depth"] == 1  # nested under fit on main thread
    builds = [e for e in evs if e["name"] == "plan-build"]
    assert len(builds) == 2
    assert all(e["depth"] == 0 for e in builds)  # worker has its own stack
    assert builds[0]["tid"] != by_name["fit"]["tid"]
    assert all(e["ok"] for e in evs)
    # inner spans close (are recorded) before their parent
    order = [e["name"] for e in evs]
    assert order.index("epoch") < order.index("fit")


def test_span_closes_on_preempted():
    """Preempted (a BaseException) unwinding through a span still closes it,
    flagged not-ok — the trainer's fit span survives preemption."""
    tracer = SpanTracer()
    with pytest.raises(Preempted):
        with tracer.span("fit"):
            raise Preempted("signal 15 during epoch 2", signum=15, epoch=2)
    (ev,) = tracer.events()
    assert ev["name"] == "fit" and ev["ph"] == "X" and not ev["ok"]


def test_chrome_trace_is_perfetto_loadable_shape(tmp_path):
    tracer = SpanTracer()
    with tracer.span("fit", fold=0):
        tracer.event("checkpoint", epoch=1)
        tracer.counter("queue-depth", 1)
    path = tracer.write_chrome_trace(str(tmp_path / "trace.chrome.json"))
    with open(path) as fh:
        trace = json.load(fh)
    evs = trace["traceEvents"]
    phases = {e["ph"] for e in evs}
    assert {"M", "X", "i", "C"} <= phases
    meta = [e for e in evs if e["ph"] == "M"]
    assert meta[0]["name"] == "thread_name"
    x = next(e for e in evs if e["ph"] == "X")
    assert {"name", "ts", "dur", "pid", "tid"} <= set(x)
    assert x["args"]["fold"] == 0  # span attrs ride the args dict


def test_disabled_tracer_is_noop_and_duration_helper():
    tracer = SpanTracer(enabled=False)
    with tracer.span("fit"):
        tracer.event("x")
    assert tracer.events() == []
    # the ONE reference-keyed duration helper (moved from trainer/logs.py):
    # starts come from the tracer's monotonic clock (perf_counter)
    cache: dict = {}
    import time

    t0 = time.perf_counter()
    d1 = duration(cache, t0, "time_spent_on_computation")
    duration(cache, t0, "time_spent_on_computation")
    assert len(cache["time_spent_on_computation"]) == 2
    assert cache["time_spent_on_computation"][0] == d1 >= 0


def test_duration_survives_stepped_wall_clock(monkeypatch):
    """Regression (r16): ``duration`` read ``time.time()`` while every span
    (and every caller's start) used the monotonic ``perf_counter`` clock —
    an NTP/DST wall-clock step mid-fit corrupted the checkpointed duration
    cache with wildly wrong (even negative) entries. Stepping the wall
    clock by a day in either direction must not perturb the recorded
    durations."""
    import time

    cache: dict = {}
    t0 = time.perf_counter()
    monkeypatch.setattr(time, "time", lambda: 1e9)  # wall clock steps back
    d1 = duration(cache, t0, "time_spent_on_computation")
    monkeypatch.setattr(time, "time", lambda: 4e9)  # ...and jumps forward
    d2 = duration(cache, t0, "time_spent_on_computation")
    assert 0 <= d1 <= d2 < 60  # monotonic, sane magnitudes
    assert cache["time_spent_on_computation"] == [d1, d2]


# ---------------------------------------------------------------------------
# on-device round metrics
# ---------------------------------------------------------------------------


def _epoch_setup(engine_name, S=2, steps=1, B=8, D=6, engine_kw=None,
                 telemetry=True):
    task = FederatedTask(MSANNet(in_size=D, hidden_sizes=(8,), out_size=2))
    engine = make_engine(engine_name, **(engine_kw or {}))
    opt = make_optimizer("adam", 1e-2)
    rng = np.random.default_rng(11)
    x = jnp.asarray(rng.normal(size=(S, steps, B, D)).astype(np.float32))
    y = jnp.asarray((rng.random((S, steps, B)) > 0.5).astype(np.int32))
    w = jnp.ones((S, steps, B), jnp.float32)
    state0 = init_train_state(task, engine, opt, jax.random.PRNGKey(0),
                              x[0, 0], num_sites=S, telemetry=telemetry)
    return task, engine, opt, state0, x, y, w


def _host_recompute_round(task, engine, opt, state, x, y, w):
    """From-scratch mirror of ONE round (local_iterations=1, every site
    live): the same rng derivation, micro-scan accumulation ops, engine
    aggregate, rounds-scan structure and tree_sq_sum reduction order as
    trainer/steps.py. The scan/vmap structure is replicated deliberately —
    XLA's fusion choices depend on it, and a flat re-expression of the same
    math lands 1 ULP away. Returns per-site (grad_sq, residual_sq) and the
    global update_sq."""
    from dinunet_implementations_tpu.trainer.steps import cross_entropy

    S, B = x.shape[0], x.shape[2]

    def loss_fn(params, stats, rng, xb, yb, wb):
        logits, new_stats = task.apply(
            params, stats, xb, train=True, rng=rng, mask=wb, mutable=True
        )
        return cross_entropy(logits, yb, wb), new_stats

    grad_fn = jax.value_and_grad(loss_fn, has_aux=True)
    rng_epoch = jax.random.fold_in(state.rng, state.round)
    _, sub = jax.random.split(rng_epoch)

    def site(es, xb, yb, wb):
        # xb: [L=1, B, ...] — the per-round micro-batch block
        site_ix = jax.lax.axis_index(SITE_AXIS)

        def micro(acc, mb):
            g_sum, n_sum, stats = acc
            xm, ym, wm, i = mb
            key = jax.random.fold_in(jax.random.fold_in(sub, site_ix), i)
            (loss, new_stats), grads = grad_fn(
                state.params, stats, key, xm, ym, wm
            )
            n = wm.sum()
            g_sum = jax.tree.map(lambda a, g: a + g * n, g_sum, grads)
            return (g_sum, n_sum + n, new_stats), loss * n

        g0 = jax.tree.map(jnp.zeros_like, state.params)
        (g_sum, n_sum, _), _ = jax.lax.scan(
            micro, (g0, jnp.zeros(()), state.batch_stats),
            (xb, yb, wb, jnp.arange(1)),
        )
        site_grad = jax.tree.map(
            lambda g: g / jnp.maximum(n_sum, 1.0), g_sum
        )
        # guard is active at the default quarantine_rounds, so the epoch
        # passes live=contribute (1.0 for a healthy site) into aggregate
        agg, _ = engine.aggregate(
            site_grad, es, n_sum, SITE_AXIS, live=jnp.asarray(1.0)
        )
        gsq = tree_sq_sum(site_grad)
        rsq = tree_sq_sum(jax.tree.map(lambda g, a: g - a, site_grad, agg))
        return gsq, rsq, agg

    def mirror(es, x, y, w):
        x_r = x.reshape((S, 1, 1) + x.shape[2:])
        y_r, w_r = y.reshape(S, 1, 1, B), w.reshape(S, 1, 1, B)

        def one_round(carry, xs):
            gsq, rsq, agg = jax.vmap(site, axis_name=SITE_AXIS)(es, *xs)
            agg0 = jax.tree.map(lambda a: a[0], agg)
            updates, _ = opt.update(agg0, state.opt_state, state.params)
            return carry, (gsq, rsq, tree_sq_sum(updates))

        _, (gsq, rsq, usq) = jax.lax.scan(
            one_round, 0,
            tuple(jnp.moveaxis(a, 1, 0) for a in (x_r, y_r, w_r)),
        )
        return gsq[0], rsq[0], usq[0]

    return jax.jit(mirror)(state.engine_state, x, y, w)


@pytest.mark.parametrize("engine_name,engine_kw", [
    ("dSGD", {}),
    ("rankDAD", dict(dad_reduction_rank=4, dad_num_pow_iters=3,
                     dad_tol=0.0)),
])
def test_on_device_metrics_match_host_recompute(engine_name, engine_kw):
    """The acceptance gate: the accumulators the rounds scan maintains equal
    a from-scratch host recomputation of the same quantities to a few ulp
    (two programs), under the CompileGuard (one program per fit)."""
    task, engine, opt, state0, x, y, w = _epoch_setup(
        engine_name, engine_kw=engine_kw
    )
    fn = make_train_epoch_fn(task, engine, opt, mesh=None, telemetry=True)
    guard = CompileGuard({"epoch_fn": fn})
    st, _ = fn(state0, x, y, w)
    t = {k: np.asarray(v) for k, v in st.telemetry.items()}
    gsq, rsq, usq = _host_recompute_round(task, engine, opt, state0, x, y, w)
    # The mirror is a DIFFERENT compiled program: XLA fuses and orders its
    # float32 sums otherwise, so a norm lands an ulp or two away (5.96e-08 on
    # grad_sq_last since the PR-21 toolchain). Across the two programs the
    # accumulators are held to 4 ulp; what one program computes twice stays
    # bit-exact (after one round sum, max and last are the same number).
    for name, want in (("grad_sq_last", gsq), ("residual_sq_sum", rsq),
                       ("update_sq_last", usq)):
        np.testing.assert_array_max_ulp(
            t[name], np.broadcast_to(np.asarray(want), t[name].shape),
            maxulp=4,
        )
    np.testing.assert_array_equal(t["grad_sq_sum"], t["grad_sq_last"])
    np.testing.assert_array_equal(t["grad_sq_max"], t["grad_sq_last"])
    assert (t["payload_bytes"] == payload_bytes_of(engine, state0.params)).all()
    assert (t["rounds"] == 1).all()
    # a second chained epoch accumulates (and still compiles nothing new)
    st2, _ = fn(st, x, y, w)
    t2 = {k: np.asarray(v) for k, v in st2.telemetry.items()}
    assert (t2["rounds"] == 2).all()
    np.testing.assert_array_equal(
        t2["grad_sq_sum"], t["grad_sq_sum"] + t2["grad_sq_last"]
    )
    guard.check(context=f"telemetry epoch, engine={engine_name}")


def test_telemetry_off_program_identical_and_outputs_bitwise():
    """telemetry="off" (the default) must compile the exact pre-telemetry
    program: identical lowering to a build that never mentions telemetry,
    state.telemetry stays None, and the on-arm trains bitwise-identically
    (the metrics observe, never perturb). Program identity goes through the
    shared normalized differ (checks/lowering.py) — the parametrized
    off==baseline harness in tests/test_lowering_identity.py and the S005
    semantic gate run the same comparison."""
    from dinunet_implementations_tpu.checks.lowering import diff_report

    task, engine, opt, _, x, y, w = _epoch_setup("dSGD", steps=3,
                                                 telemetry=False)
    state0 = init_train_state(task, engine, opt, jax.random.PRNGKey(0),
                              x[0, 0], num_sites=2, telemetry=False)
    fn_off = make_train_epoch_fn(task, engine, opt, mesh=None,
                                 telemetry=False)
    fn_default = make_train_epoch_fn(task, engine, opt, mesh=None)
    report = diff_report(
        fn_off.lower(state0, x, y, w).as_text(),
        fn_default.lower(state0, x, y, w).as_text(),
        "telemetry=False", "default-build",
    )
    assert report is None, report
    st_off, losses_off = fn_off(state0, x, y, w)
    assert st_off.telemetry is None
    state_t = init_train_state(task, engine, opt, jax.random.PRNGKey(0),
                               x[0, 0], num_sites=2, telemetry=True)
    fn_on = make_train_epoch_fn(task, engine, opt, mesh=None, telemetry=True)
    st_on, losses_on = fn_on(state_t, x, y, w)
    np.testing.assert_array_equal(
        np.asarray(losses_off), np.asarray(losses_on)
    )
    jax.tree.map(
        lambda a, b: np.testing.assert_array_equal(
            np.asarray(a), np.asarray(b)
        ),
        st_off.params, st_on.params,
    )
    # an off-program fed a telemetry-carrying state drops the accumulators
    # (trace-time normalization), keeping the legacy program
    st_mixed, _ = fn_off(state_t, x, y, w)
    assert st_mixed.telemetry is None


def test_nonfinite_round_poisons_last_not_sums():
    """A NaN round shows in grad_sq_last (the blow-up signal) but is
    excluded from the sum/max accumulators, which must stay usable."""
    task, engine, opt, state0, x, y, w = _epoch_setup("dSGD", steps=2)
    x = x.at[1, 1].set(jnp.nan)  # site 1's second round is poisoned
    fn = make_train_epoch_fn(task, engine, opt, mesh=None, telemetry=True)
    st, _ = fn(state0, x, y, w)
    t = {k: np.asarray(v) for k, v in st.telemetry.items()}
    assert np.isnan(t["grad_sq_last"][1])
    assert np.isfinite(t["grad_sq_last"][0])
    assert np.isfinite(t["grad_sq_sum"]).all()
    assert np.isfinite(t["grad_sq_max"]).all()


def test_telemetry_checkpoint_roundtrip(tmp_path):
    """TrainState.telemetry rides the checkpoint (R006 enforces the schema
    statically; this is the dynamic round-trip)."""
    task, engine, opt, state0, x, y, w = _epoch_setup("dSGD", steps=2)
    fn = make_train_epoch_fn(task, engine, opt, mesh=None, telemetry=True)
    st, _ = fn(state0, x, y, w)
    p = str(tmp_path / "ckpt.msgpack")
    save_checkpoint(p, st)
    fresh = init_train_state(task, engine, opt, jax.random.PRNGKey(0),
                             x[0, 0], num_sites=2, telemetry=True)
    restored = load_checkpoint(p, fresh)
    for k in TELEMETRY_KEYS:
        np.testing.assert_array_equal(
            np.asarray(st.telemetry[k]), np.asarray(restored.telemetry[k])
        )
    # a telemetry-off resume tolerates the stored accumulators (dropped)
    fresh_off = init_train_state(task, engine, opt, jax.random.PRNGKey(0),
                                 x[0, 0], num_sites=2, telemetry=False)
    assert load_checkpoint(p, fresh_off).telemetry is None


# ---------------------------------------------------------------------------
# the fit-level artifact pipeline
# ---------------------------------------------------------------------------


def _toy_sites(ns, n=24, d=6, seed=0):
    out = []
    rng = np.random.default_rng(seed)
    for _ in range(ns):
        X = rng.normal(size=(n, d)).astype(np.float32)
        y = (X.sum(-1) > 0).astype(np.int32)
        out.append(SiteArrays(X, y, np.arange(n, dtype=np.int32)))
    return out


def _fit(cfg, out_dir, fault_plan=None):
    model = MSANNet(in_size=6, hidden_sizes=(8,), out_size=2)
    tr = FederatedTrainer(cfg, model, mesh=None, out_dir=out_dir,
                          fault_plan=fault_plan)
    res = tr.fit(_toy_sites(2), _toy_sites(2, n=16, seed=9),
                 _toy_sites(2, n=16, seed=5), verbose=False)
    return tr, res


def test_fit_emits_schema_valid_artifacts(tmp_path):
    """A telemetry="on" fit leaves manifest.json + metrics.jsonl + both
    trace forms, all schema-valid, with exactly one epoch compile and the
    prefetch thread's plan-build spans in the trace."""
    cfg = TrainConfig(epochs=3, batch_size=8, patience=50, telemetry="on")
    tr, res = _fit(cfg, str(tmp_path))
    d = tmp_path / "telemetry" / "fold_0"
    with open(d / MANIFEST_FILE) as fh:
        manifest = json.load(fh)
    assert validate_manifest(manifest) == []
    assert manifest["agg_engine"] == "dSGD"
    assert manifest["num_sites"] == 2
    assert manifest["jax_version"] == jax.__version__
    rows = load_metrics(str(d / METRICS_FILE))
    assert validate_metrics_rows(rows) == []
    epochs = [r for r in rows if r["kind"] == "epoch"]
    assert [r["epoch"] for r in epochs] == [1, 2, 3]
    assert all(len(r["site_grad_sq_last"]) == 2 for r in epochs)
    assert all(r["transfer_bytes"] > 0 for r in epochs)
    (summary,) = [r for r in rows if r["kind"] == "summary"]
    assert summary["epoch_compiles"] == 1  # CompileGuard invariant, recorded
    assert summary["epochs_run"] == 3
    assert "prefetch_stall_s" in summary
    # trace: both forms parse; plan-build ran on the prefetch thread
    spans = [json.loads(ln) for ln in open(d / TRACE_JSONL_FILE)]
    names = {e["name"] for e in spans if e["ph"] == "X"}
    assert {"fit", "epoch", "eval", "plan-build", "test"} <= names
    main_tid = next(e["tid"] for e in spans if e["name"] == "fit")
    build_threads = {
        e["thread"] for e in spans if e["name"] == "plan-build"
    }
    assert build_threads == {"dinunet-epoch-prefetch"}
    assert all(e["tid"] != main_tid for e in spans
               if e["name"] == "plan-build")
    with open(d / TRACE_CHROME_FILE) as fh:
        chrome = json.load(fh)
    assert isinstance(chrome["traceEvents"], list) and chrome["traceEvents"]
    # the results dict carries the rollup
    assert len(res["site_telemetry"]["site_grad_norm_last"]) == 2


def test_logs_json_telemetry_fields_roundtrip(tmp_path):
    """Satellite contract: write_logs_json surfaces the per-site grad-norm
    rollup next to health_log_fields — remote lists, per-site scalars —
    and the values round-trip through the JSON."""
    cfg = TrainConfig(epochs=2, batch_size=8, patience=50, telemetry="on")
    _, res = _fit(cfg, str(tmp_path))
    remote = json.load(open(
        tmp_path / "remote/simulatorRun/FS-Classification/fold_0/logs.json"))
    rollup = res["site_telemetry"]
    assert remote["site_grad_norm_last"] == rollup["site_grad_norm_last"]
    assert remote["site_grad_norm_max"] == rollup["site_grad_norm_max"]
    assert remote["site_residual_norm_mean"] == rollup["site_residual_norm_mean"]
    assert remote["update_norm_last"] == rollup["update_norm_last"]
    # health fields still present next to them (the "next to" contract)
    assert "site_skipped_rounds" in remote
    local1 = json.load(open(
        tmp_path / "local1/simulatorRun/FS-Classification/fold_0/logs.json"))
    assert local1["grad_norm_last"] == rollup["site_grad_norm_last"][1]
    assert local1["grad_norm_mean"] == rollup["site_grad_norm_mean"][1]
    # helper symmetry on the same rollup dict
    assert telemetry_log_fields(rollup)["site_grad_norm_last"] == \
        rollup["site_grad_norm_last"]
    assert telemetry_log_fields(None) == {}


def test_telemetry_off_fit_writes_nothing(tmp_path):
    cfg = TrainConfig(epochs=2, batch_size=8, patience=50)  # default off
    tr, res = _fit(cfg, str(tmp_path))
    assert not (tmp_path / "telemetry").exists()
    assert "site_telemetry" not in res
    remote = json.load(open(
        tmp_path / "remote/simulatorRun/FS-Classification/fold_0/logs.json"))
    assert "site_grad_norm_last" not in remote


def test_preempted_fit_still_finalizes_artifacts(tmp_path):
    """A FaultPlan kill mid-fit raises Preempted through the trainer — the
    sink's finally still writes the trace files, the preempted event is in
    metrics.jsonl, and the fit span is closed (ok=false)."""
    cfg = TrainConfig(epochs=4, batch_size=8, patience=50, telemetry="on")
    with pytest.raises(Preempted):
        # 24 samples / batch 8 → 3 rounds/epoch; kill inside epoch 2
        _fit(cfg, str(tmp_path), fault_plan=FaultPlan(kill_at_round=4))
    d = tmp_path / "telemetry" / "fold_0"
    rows = load_metrics(str(d / METRICS_FILE))
    assert validate_metrics_rows(rows) == []
    assert any(
        r["kind"] == "event" and r["name"] == "preempted" for r in rows
    )
    (summary,) = [r for r in rows if r["kind"] == "summary"]
    assert summary["epochs_run"] == 2
    spans = [json.loads(ln) for ln in open(d / TRACE_JSONL_FILE)]
    fit_span = next(e for e in spans if e["name"] == "fit")
    assert fit_span["ok"] is False


@pytest.mark.slow
def test_xprof_window_captures_epoch_range(tmp_path):
    """--xprof-dir: the jax.profiler capture brackets exactly the
    configured epoch window of a real fit and finalizes its trace file.

    Slow tier: a full 3-epoch fit under the profiler (~40s on the CPU
    container) — well past the >~10s line the ``slow`` marker draws.
    """
    from dinunet_implementations_tpu.telemetry.xprof import trace_files

    cfg = TrainConfig(epochs=3, batch_size=8, patience=50,
                      xprof_dir=str(tmp_path / "xprof"),
                      xprof_window=(2, 2))
    _fit(cfg, str(tmp_path / "out"))
    assert trace_files(str(tmp_path / "xprof" / "fold_0"))


def test_xprof_window_fires_when_resume_starts_inside_it(tmp_path):
    """A resumed fit whose start epoch lands INSIDE the window (preempted
    mid-window) must still capture the remaining windowed epochs."""
    from dinunet_implementations_tpu.telemetry.xprof import (
        XprofWindow,
        trace_files,
    )

    w = XprofWindow(str(tmp_path), (2, 3))
    f = jax.jit(lambda x: x + 1)
    w.epoch_begin(3)  # resume skipped epochs 1-2
    f(jnp.ones(4)).block_until_ready()
    w.epoch_end(3)
    w.close()
    assert trace_files(str(tmp_path))


def test_metrics_jsonl_is_strict_json(tmp_path):
    """NaN rides the metrics rows by design (the blow-up signal), but the
    emitted JSONL must be strict RFC 8259 — non-finite reals become null,
    never a bare NaN/Infinity token that breaks JSON.parse/jq."""
    from dinunet_implementations_tpu.telemetry.sink import FitTelemetry

    sink = FitTelemetry(str(tmp_path), SpanTracer(enabled=False))
    sink.append({"kind": "event", "name": "blowup", "v": float("nan"),
                 "l": [1.0, np.float32("inf"), 2]})
    raw = open(tmp_path / METRICS_FILE).read()
    assert "NaN" not in raw and "Infinity" not in raw
    (row,) = load_metrics(str(tmp_path / METRICS_FILE))
    assert row["v"] is None and row["l"] == [1.0, None, 2]


def test_invalid_telemetry_value_rejected():
    with pytest.raises(ValueError, match="telemetry"):
        FederatedTrainer(
            TrainConfig(telemetry="yes"),
            MSANNet(in_size=6, hidden_sizes=(8,), out_size=2), mesh=None,
        )


def test_profile_and_xprof_dirs_mutually_exclusive(tmp_path):
    with pytest.raises(ValueError, match="mutually exclusive"):
        FederatedTrainer(
            TrainConfig(profile_dir=str(tmp_path / "a"),
                        xprof_dir=str(tmp_path / "b")),
            MSANNet(in_size=6, hidden_sizes=(8,), out_size=2), mesh=None,
        )


# ---------------------------------------------------------------------------
# schema validators + report CLI
# ---------------------------------------------------------------------------


def test_schema_validators_reject_drift():
    good = {"kind": "event", "name": "checkpoint"}
    assert validate_metrics_rows([good]) == []
    assert validate_metrics_rows([{"kind": "nonsense"}])
    assert validate_metrics_rows([{"kind": "epoch", "fold": 0}])  # missing
    assert validate_manifest({"schema_version": 1})  # missing keys
    assert validate_manifest([1, 2])  # not an object
    # version bump without a validator update must fail loudly
    assert any(
        "schema_version" in p
        for p in validate_manifest({"schema_version": 99})
    )


def test_schema_validators_unknown_kind_and_serving_rows():
    """An unknown ``kind`` is a finding, not a silent pass (a typo'd kind
    would otherwise vanish from the report), and the serving row kinds'
    required-key sets are enforced key by key (negative fixtures: each
    missing key must be NAMED in a problem string)."""
    problems = validate_metrics_rows([{"kind": "dsipatch"}])  # typo
    assert problems and "unknown kind" in problems[0]
    good_dispatch = {
        "kind": "dispatch", "lane": "infer", "bucket": 4, "rows": 3,
        "pad_rows": 1, "queue_depth": 0,
    }
    assert validate_metrics_rows([good_dispatch]) == []
    for key in ("lane", "bucket", "rows", "pad_rows", "queue_depth"):
        bad = {k: v for k, v in good_dispatch.items() if k != key}
        problems = validate_metrics_rows([bad])
        assert problems and key in problems[0], (key, problems)
    good_summary = {
        "kind": "serve_summary", "task_id": "FS-Classification",
        "requests": 1, "samples": 1, "dispatches": 1,
        "latency_ms_p50": 1.0, "latency_ms_p95": 1.0, "latency_ms_p99": 1.0,
        "requests_per_s": 1.0, "samples_per_s": 1.0, "pad_waste_pct": 0.0,
        "bucket_hit_rate": 1.0, "warmup_seconds": 0.1,
        "compiles_after_warmup": 0,
    }
    assert validate_metrics_rows([good_summary]) == []
    for key in ("latency_ms_p50", "latency_ms_p95", "latency_ms_p99",
                "requests", "dispatches", "compiles_after_warmup"):
        bad = {k: v for k, v in good_summary.items() if k != key}
        problems = validate_metrics_rows([bad])
        assert problems and key in problems[0], (key, problems)


def test_report_cli_smoke(tmp_path, capsys):
    cfg = TrainConfig(epochs=2, batch_size=8, patience=50, telemetry="on")
    _fit(cfg, str(tmp_path))
    from dinunet_implementations_tpu.telemetry import report

    # --validate gates clean artifacts
    assert report.main([str(tmp_path / "telemetry"), "--validate"]) == 0
    capsys.readouterr()
    # rendering finds the fold dir from the run root and prints the tables
    assert report.main([str(tmp_path / "telemetry")]) == 0
    out = capsys.readouterr().out
    assert "phase time" in out and "per-site rollup" in out
    assert "epoch_compiles=1" in out
    # validation failure path: corrupt the manifest
    mpath = tmp_path / "telemetry" / "fold_0" / MANIFEST_FILE
    mpath.write_text(json.dumps({"schema_version": 99}))
    assert report.main([str(tmp_path / "telemetry"), "--validate"]) == 1
    with pytest.raises(FileNotFoundError):
        report.fit_dirs(str(tmp_path))  # no manifest anywhere


def test_telemetry_summary_rollup_shapes():
    t = default_round_telemetry(3)
    t = {k: np.asarray(v) for k, v in t.items()}
    t["grad_sq_last"] = np.asarray([4.0, 9.0, np.nan], np.float32)
    t["rounds"] = np.asarray([2, 2, 2], np.int32)
    s = telemetry_summary(t)
    assert s["site_grad_norm_last"][:2] == [2.0, 3.0]
    assert np.isnan(s["site_grad_norm_last"][2])
    assert s["rounds"] == 2
    assert telemetry_summary(None) is None


# ---------------------------------------------------------------------------
# the tracer's second sink: the profiler's host plane (ISSUE 36)
# ---------------------------------------------------------------------------


def _profiled(tmp_path, body):
    """Run ``body()`` inside a CPU ``jax.profiler`` session (python tracer
    off, as the benchmark's traced stretch) and return the host-plane events
    named ``dinunet/...`` as ``(name, thread line, stats)``."""
    import glob

    from jax.profiler import ProfileData

    options = jax.profiler.ProfileOptions()
    options.python_tracer_level = 0
    jax.profiler.start_trace(str(tmp_path), profiler_options=options)
    try:
        body()
    finally:
        jax.profiler.stop_trace()
    events = []
    for path in glob.glob(str(tmp_path / "**" / "*.xplane.pb"), recursive=True):
        for plane in ProfileData.from_file(path).planes:
            for i, line in enumerate(plane.lines):
                events += [
                    (e.name, f"{plane.name}#{i}", dict(e.stats))
                    for e in line.events
                    if e.name.startswith(scopes.HOST_PREFIX)
                ]
    return events


def _loop_epochs(trainer, sites, epochs):
    """``epochs`` epochs the way ``_fit_impl`` runs them: plans from the
    prefetch thread under the trainer's tracer, then ``run_epoch``."""
    from dinunet_implementations_tpu.trainer.prefetch import EpochPlanPrefetcher

    state = trainer.init_state(jnp.ones((8, 6)), num_sites=len(sites))
    prefetch = EpochPlanPrefetcher(
        lambda e: trainer._build_epoch_payload(sites, e, 8, (e - 1) * 3),
        1, epochs, tracer=trainer.tracer,
    )
    try:
        for epoch in range(1, epochs + 1):
            state, _ = trainer.run_epoch(
                state, sites, epoch, batch_size=8, plan=prefetch.get(epoch)
            )
    finally:
        prefetch.close()


@pytest.mark.parametrize("telemetry", ["off", "on"])
def test_fit_loop_spans_reach_the_profiler_whatever_telemetry_says(
        tmp_path, telemetry):
    """Every host name of scopes.py is in the profile once an epoch
    (``inventory-upload`` once a fit), with ``epoch=`` in its stats and
    ``plan-build`` on another thread line than the loop's — with
    ``cfg.telemetry`` off (a tracer that records no event) and on (the same
    names in the event buffer)."""
    cfg = TrainConfig(epochs=3, batch_size=8, telemetry=telemetry)
    trainer = FederatedTrainer(
        cfg, MSANNet(in_size=6, hidden_sizes=(8,), out_size=2), mesh=None)
    assert trainer.tracer.annotate
    assert trainer.tracer.enabled == (telemetry == "on")
    sites = _toy_sites(2)
    events = _profiled(tmp_path, lambda: _loop_epochs(trainer, sites, 3))
    by_name: dict = {}
    for name, line, stats in events:
        by_name.setdefault(name[len(scopes.HOST_PREFIX):], []).append(
            (line, stats))
    assert set(by_name) == set(scopes.HOST_SPANS)
    for name, hits in by_name.items():
        once = name == scopes.INVENTORY_UPLOAD
        assert sorted(s["epoch"] for _, s in hits) == ([1] if once
                                                       else [1, 2, 3]), name
    loop_line = {line for line, _ in by_name[scopes.EPOCH_DISPATCH]}
    assert len(loop_line) == 1
    for name in (scopes.PLAN_WAIT, scopes.EPOCH_INPUTS, scopes.LOSS_FETCH,
                 scopes.EPOCH_ACCOUNT, scopes.INVENTORY_UPLOAD):
        assert {line for line, _ in by_name[name]} == loop_line, name
    assert not {line for line, _ in by_name[scopes.PLAN_BUILD]} & loop_line
    recorded = {e["name"] for e in trainer.tracer.events() if e["ph"] == "X"}
    assert recorded == (set(scopes.HOST_SPANS) if telemetry == "on" else set())


def test_null_tracer_annotates_nothing_and_profiler_tracer_records_nothing(
        tmp_path):
    from dinunet_implementations_tpu.telemetry import (
        NULL_TRACER,
        PROFILER_TRACER,
    )

    assert not NULL_TRACER.enabled and not NULL_TRACER.annotate
    assert not PROFILER_TRACER.enabled and PROFILER_TRACER.annotate

    def body():
        with NULL_TRACER.span("from-null", epoch=1):
            pass
        with PROFILER_TRACER.span("from-profiler", epoch=7):
            PROFILER_TRACER.event("an-event")
            PROFILER_TRACER.counter("a-counter", 1)
        with SpanTracer().span("from-plain", epoch=1):
            pass

    events = _profiled(tmp_path, body)
    assert [(n, s) for n, _, s in events] == [
        (scopes.HOST_PREFIX + "from-profiler", {"epoch": 7})]
    assert PROFILER_TRACER.events() == [] and NULL_TRACER.events() == []


def test_tracer_imports_and_spans_without_jax():
    """tracer.py and scopes.py are stdlib-only: in a process where jax is not
    imported (the package's ``__init__`` would import it, so the two parents
    are bare namespaces here) an annotating tracer's spans still work, record
    when enabled, and pull nothing in."""
    import subprocess
    import sys

    pkg = os.path.dirname(os.path.dirname(os.path.abspath(
        sys.modules[SpanTracer.__module__].__file__)))
    code = f"""
import importlib, sys, types
for name, path in (("dinunet_implementations_tpu", {pkg!r}),
                   ("dinunet_implementations_tpu.telemetry",
                    {os.path.join(pkg, "telemetry")!r})):
    mod = types.ModuleType(name)
    mod.__path__ = [path]
    sys.modules[name] = mod
tracer = importlib.import_module("dinunet_implementations_tpu.telemetry.tracer")
with tracer.PROFILER_TRACER.span("plan-wait", epoch=1):
    pass
both = tracer.SpanTracer(annotate=True)
with both.span("epoch-dispatch", epoch=2):
    pass
assert [e["name"] for e in both.events()] == ["epoch-dispatch"]
assert both.events()[0]["epoch"] == 2
assert tracer.PROFILER_TRACER.events() == []
assert "jax" not in sys.modules, "tracer.py pulled jax in"
print("ok")
"""
    out = subprocess.run([sys.executable, "-c", code], capture_output=True,
                         text=True, timeout=60)
    assert out.returncode == 0 and out.stdout.strip() == "ok", out.stderr


def test_prefetcher_keeps_its_positional_signature_and_default_tracer():
    """The harness builds ``EpochPlanPrefetcher(build, first, last)``: three
    positional arguments and no tracer; the default one only annotates."""
    from dinunet_implementations_tpu.telemetry import PROFILER_TRACER
    from dinunet_implementations_tpu.trainer.prefetch import EpochPlanPrefetcher

    pf = EpochPlanPrefetcher(lambda e: e * 10, 1, 2)
    try:
        assert pf._tracer is PROFILER_TRACER
        assert [pf.get(1), pf.get(2)] == [10, 20]
        assert pf.stats()["gets"] == 2
    finally:
        pf.close()
    recording = SpanTracer()
    pf = EpochPlanPrefetcher(lambda e: e, 1, 1, tracer=recording)
    try:
        pf.get(1)
    finally:
        pf.close()
    (ev,) = recording.events()
    assert ev["name"] == scopes.PLAN_WAIT and ev["epoch"] == 1
