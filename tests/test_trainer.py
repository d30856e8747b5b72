"""Trainer tests: metrics, SPMD invariants, checkpointing, early stopping."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from dinunet_implementations_tpu.data.api import SiteArrays
from dinunet_implementations_tpu.engines import make_engine
from dinunet_implementations_tpu.models import MSANNet
from dinunet_implementations_tpu.parallel import host_mesh
from dinunet_implementations_tpu.trainer import (
    Averages,
    ClassificationMetrics,
    FederatedTask,
    FederatedTrainer,
    init_train_state,
    is_improvement,
    load_checkpoint,
    make_eval_fn,
    make_optimizer,
    make_train_epoch_fn,
    save_checkpoint,
)
from dinunet_implementations_tpu.core.config import TrainConfig


# ---------------------------------------------------------------------------
# metrics
# ---------------------------------------------------------------------------


def test_averages():
    a = Averages().add(2.0, 3).add(4.0, 1)
    assert a.avg == pytest.approx(2.5)
    b = Averages().add(10.0, 4)
    a.merge(b)
    assert a.avg == pytest.approx(6.25)


def test_classification_metrics_known_values():
    m = ClassificationMetrics()
    #         pred:  1    1    0    0      (threshold 0.5)
    m.add([0.9, 0.8, 0.3, 0.1], [1, 0, 1, 0])
    assert m.accuracy() == pytest.approx(0.5)
    assert m.precision() == pytest.approx(0.5)
    assert m.recall() == pytest.approx(0.5)
    assert m.f1() == pytest.approx(0.5)
    # AUC: pos scores {0.9, 0.3}, neg {0.8, 0.1}: pairs won 3/4
    assert m.auc() == pytest.approx(0.75)


def test_auc_with_ties_and_hard_preds():
    m = ClassificationMetrics()
    m.add([1, 1, 0, 0], [1, 0, 1, 0])  # hard predictions
    assert m.auc() == pytest.approx(0.5)  # one win, one loss, two ties


def test_metrics_weights_mask_padding():
    m = ClassificationMetrics()
    m.add([0.9, 0.9, 0.9], [1, 1, 1], weights=[1, 0, 0])
    s, y = m._cat()
    assert len(s) == 1


def test_multiclass_metrics_known_values():
    from dinunet_implementations_tpu.trainer.metrics import MulticlassMetrics

    m = MulticlassMetrics()
    # 4 samples, 3 classes; argmax preds = [0, 1, 2, 0]; labels = [0, 1, 2, 2]
    m.add(
        [[0.7, 0.2, 0.1], [0.1, 0.8, 0.1], [0.2, 0.2, 0.6], [0.5, 0.3, 0.2]],
        [0, 1, 2, 2],
    )
    assert m.accuracy() == pytest.approx(0.75)
    # per-class (P, R): c0 (1/2, 1), c1 (1, 1), c2 (1, 1/2) → macro P = R = 5/6
    assert m.precision() == pytest.approx(5 / 6)
    assert m.recall() == pytest.approx(5 / 6)
    assert 0.0 <= m.auc() <= 1.0
    # weights mask padding rows
    m2 = MulticlassMetrics()
    m2.add([[0.9, 0.1, 0.0]] * 3, [0, 0, 0], weights=[1, 0, 0])
    p, y = m2._cat()
    assert len(y) == 1


def test_evaluate_multiclass_path():
    """num_class > 2 must route through argmax-based metrics, not prob[:,1]."""
    cfg = TrainConfig(epochs=1, batch_size=8, num_class=3, monitor_metric="accuracy")
    model = MSANNet(in_size=6, hidden_sizes=(8,), out_size=3)
    tr = FederatedTrainer(cfg, model, host_mesh(2))
    sites = []
    rng = np.random.default_rng(5)
    for _ in range(2):
        X = rng.normal(size=(24, 6)).astype(np.float32)
        y = rng.integers(0, 3, size=24).astype(np.int32)
        sites.append(SiteArrays(X, y, np.arange(24, dtype=np.int32)))
    tr._num_sites = 2
    state = tr.init_state(jnp.ones((8, 6)), num_sites=2)
    avg, m = tr.evaluate(state, sites)
    from dinunet_implementations_tpu.trainer.metrics import MulticlassMetrics

    assert isinstance(m, MulticlassMetrics)
    assert 0.0 <= m.value("accuracy") <= 1.0


def test_is_improvement():
    assert is_improvement(0.8, None)
    assert is_improvement(0.8, 0.7, "maximize")
    assert not is_improvement(0.6, 0.7, "maximize")
    assert is_improvement(0.6, 0.7, "minimize")


# ---------------------------------------------------------------------------
# SPMD invariants
# ---------------------------------------------------------------------------


def _make_data(S, steps, B, d=6, seed=0):
    rng = np.random.default_rng(seed)
    X = rng.normal(size=(S, steps, B, d)).astype(np.float32)
    y = (X.sum(-1) > 0).astype(np.int32)
    w = np.ones((S, steps, B), np.float32)
    return jnp.asarray(X), jnp.asarray(y), jnp.asarray(w)


def _setup(mesh, lr=1e-2, local_iterations=1):
    task = FederatedTask(MSANNet(in_size=6, hidden_sizes=(16,), out_size=2))
    engine = make_engine("dSGD")
    opt = make_optimizer("adam", lr)
    state = init_train_state(task, engine, opt, jax.random.PRNGKey(0), jnp.ones((4, 6)))
    return task, engine, opt, state, make_train_epoch_fn(task, engine, opt, mesh, local_iterations)


def test_identical_sites_equal_single_site():
    """Four sites holding identical data must produce exactly the same params
    trajectory as one site (the dSGD aggregation is a no-op then)."""
    X, y, w = _make_data(1, 4, 8, seed=1)
    X4 = jnp.tile(X, (4, 1, 1, 1))
    y4, w4 = jnp.tile(y, (4, 1, 1)), jnp.tile(w, (4, 1, 1))

    mesh4 = host_mesh(4)
    _, _, _, s4, fn4 = _setup(mesh4)
    s4, _ = fn4(s4, X4, y4, w4)

    mesh1 = host_mesh(1)
    _, _, _, s1, fn1 = _setup(mesh1)
    s1, _ = fn1(s1, X, y, w)

    jax.tree.map(
        lambda a, b: np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=1e-6),
        s4.params,
        s1.params,
    )


def test_vmap_fold_matches_mesh():
    """The vmap-folded site axis must produce the same result as the
    shard_map mesh axis — same program, different realization."""
    X, y, w = _make_data(4, 3, 8, seed=2)
    mesh = host_mesh(4)
    _, _, _, sm, fnm = _setup(mesh)
    sm, lm = fnm(sm, X, y, w)
    _, _, _, sv, fnv = _setup(None)
    sv, lv = fnv(sv, X, y, w)
    np.testing.assert_allclose(np.asarray(lm), np.asarray(lv), atol=1e-6)
    jax.tree.map(
        lambda a, b: np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=1e-6),
        sm.params,
        sv.params,
    )


def test_grad_accumulation_weighting():
    """local_iterations=2 over batches [b1, b2] must equal one round with the
    pooled batch [b1;b2] (weighted accumulation invariant; BN-free model)."""
    import flax.linen as nn

    class Linear(nn.Module):
        @nn.compact
        def __call__(self, x, train=True, mask=None):
            return nn.Dense(2)(x)

    mesh = host_mesh(1)
    engine = make_engine("dSGD")
    opt = make_optimizer("sgd", 0.1)

    X, y, w = _make_data(1, 2, 8, seed=3)
    task = FederatedTask(Linear())
    s0 = init_train_state(task, engine, opt, jax.random.PRNGKey(1), jnp.ones((4, 6)))

    fn_acc = make_train_epoch_fn(task, engine, opt, mesh, local_iterations=2)
    s_acc, _ = fn_acc(s0, X, y, w)

    Xp = X.reshape(1, 1, 16, 6)
    yp, wp = y.reshape(1, 1, 16), w.reshape(1, 1, 16)
    fn_pool = make_train_epoch_fn(task, engine, opt, mesh, local_iterations=1)
    s_pool, _ = fn_pool(s0, Xp, yp, wp)

    jax.tree.map(
        lambda a, b: np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=1e-6),
        s_acc.params,
        s_pool.params,
    )


def test_eval_fn_masks_padding():
    mesh = host_mesh(2)
    task = FederatedTask(MSANNet(in_size=6, hidden_sizes=(8,), out_size=2))
    engine = make_engine("dSGD")
    opt = make_optimizer("adam", 1e-3)
    state = init_train_state(task, engine, opt, jax.random.PRNGKey(0), jnp.ones((4, 6)))
    eval_fn = make_eval_fn(task, mesh)
    X, y, w = _make_data(2, 2, 8, seed=4)
    w = w.at[1, 1, :].set(0.0)  # site 1's last batch is padding
    probs, loss_sum, wsum = eval_fn(state, X, y, w)
    assert np.asarray(wsum)[1] == 8.0
    assert np.isfinite(np.asarray(loss_sum)).all()


# ---------------------------------------------------------------------------
# checkpoint
# ---------------------------------------------------------------------------


def test_checkpoint_roundtrip(tmp_path):
    mesh = host_mesh(2)
    _, _, _, state, fn = _setup(mesh)
    X, y, w = _make_data(2, 2, 8)
    state, _ = fn(state, X, y, w)
    p = save_checkpoint(str(tmp_path / "ck.msgpack"), state, meta={"fold": 0})
    _, _, _, fresh, _ = _setup(mesh)
    restored = load_checkpoint(p, fresh)
    jax.tree.map(
        lambda a, b: np.testing.assert_array_equal(np.asarray(a), np.asarray(b)),
        state.params,
        restored.params,
    )
    assert int(restored.round) == int(state.round)


# ---------------------------------------------------------------------------
# FederatedTrainer loop behavior
# ---------------------------------------------------------------------------


def _toy_sites(ns, n=40, d=6, seed=0):
    out = []
    rng = np.random.default_rng(seed)
    for i in range(ns):
        X = rng.normal(size=(n, d)).astype(np.float32)
        y = (X.sum(-1) > 0).astype(np.int32)
        out.append(SiteArrays(X, y, np.arange(n, dtype=np.int32)))
    return out


def test_trainer_fit_learns_and_stops():
    cfg = TrainConfig(epochs=40, patience=12, batch_size=8, monitor_metric="auc",
                      fs_args=TrainConfig().fs_args)
    model = MSANNet(in_size=6, hidden_sizes=(16,), out_size=2)
    tr = FederatedTrainer(cfg, model, host_mesh(2))
    res = tr.fit(_toy_sites(2, n=80, seed=1), _toy_sites(2, n=40, seed=2),
                 _toy_sites(2, n=40, seed=3), verbose=False)
    assert res["test_scores"]["auc"] > 0.85
    assert res["best_val_epoch"] >= 1
    assert res["stopped_epoch"] <= 40


def test_checkpoint_engine_state_structure_change_resumes():
    """r6 regression (review finding): a checkpoint saved under a different
    engine-state structure (e.g. rankDAD before warm starts existed, or
    dad_warm_start flipped between save and resume) must still resume —
    params/optimizer exactly, engine state falling back to fresh init."""
    import os

    from dinunet_implementations_tpu.trainer import make_train_epoch_fn

    model = MSANNet(in_size=6, hidden_sizes=(8,), out_size=2)
    task = FederatedTask(model)
    opt = make_optimizer("adam", 1e-2)
    rng = np.random.default_rng(0)
    x = jnp.asarray(rng.normal(size=(2, 2, 4, 6)).astype(np.float32))
    cold = make_engine("rankDAD", dad_warm_start=False)
    st_cold = init_train_state(task, cold, opt, jax.random.PRNGKey(0), x[0, 0],
                               num_sites=2)
    path = "/tmp/_ckpt_structchange.msgpack"
    save_checkpoint(path, st_cold, meta={"epoch": 3})
    warm = make_engine("rankDAD", dad_warm_start=True)
    st_warm = init_train_state(task, warm, opt, jax.random.PRNGKey(1), x[0, 0],
                               num_sites=2)
    restored, meta = load_checkpoint(path, st_warm, with_meta=True)
    assert meta["epoch"] == 3
    # params resumed from the checkpoint, engine state fell back to fresh warm
    jax.tree.map(
        lambda a, b: np.testing.assert_array_equal(np.asarray(a), np.asarray(b)),
        restored.params, st_cold.params,
    )
    assert "omega" in restored.engine_state
    os.remove(path)


def test_batch_size_clamp_stays_local_to_the_fold():
    """ADVICE regression (r5): a fold whose smallest site forces the
    batch-size clamp must NOT mutate the trainer's shared config — the next
    fold (or any cfg reuse) gets the original batch size back."""
    cfg = TrainConfig(epochs=1, batch_size=16, validation_epochs=1)
    model = MSANNet(in_size=6, hidden_sizes=(8,), out_size=2)
    tr = FederatedTrainer(cfg, model, host_mesh(2))
    # smallest train split (6) < batch_size (16) → the clamp fires
    res = tr.fit(_toy_sites(2, n=6), _toy_sites(2, n=4), _toy_sites(2, n=4),
                 verbose=False)
    assert np.isfinite(res["epoch_losses"]).all()
    assert tr.cfg.batch_size == 16, "clamp leaked into the shared config"
    assert cfg.batch_size == 16


def test_rounds_scan_xs_reachable_from_config():
    """ADVICE regression (r5): TrainConfig.rounds_scan_xs must reach the
    compiled epoch (the peak-HBM escape hatch documented in
    trainer/steps.py) — both arms train and agree through the Trainer."""
    outs = {}
    for flag in (True, False):
        cfg = TrainConfig(epochs=2, batch_size=8, rounds_scan_xs=flag)
        model = MSANNet(in_size=6, hidden_sizes=(8,), out_size=2)
        tr = FederatedTrainer(cfg, model, host_mesh(2))
        res = tr.fit(_toy_sites(2), _toy_sites(2, n=16), _toy_sites(2, n=16),
                     verbose=False)
        outs[flag] = res
    np.testing.assert_allclose(
        outs[True]["epoch_losses"], outs[False]["epoch_losses"], rtol=1e-6
    )


def test_trainer_early_stop_on_patience():
    # lr=0 → metric never improves after first validation → stops at patience
    cfg = TrainConfig(epochs=50, patience=3, batch_size=8, learning_rate=0.0)
    model = MSANNet(in_size=6, hidden_sizes=(8,), out_size=2)
    tr = FederatedTrainer(cfg, model, host_mesh(2))
    res = tr.fit(_toy_sites(2), _toy_sites(2, n=16), _toy_sites(2, n=16), verbose=False)
    assert res["stopped_epoch"] <= 6


def test_final_validation_when_epochs_below_cadence():
    """ADVICE regression: epochs < validation_epochs must still validate once,
    so the trained (not init) state is selected and best_val_metric is set."""
    cfg = TrainConfig(epochs=2, validation_epochs=5, batch_size=8)
    model = MSANNet(in_size=6, hidden_sizes=(8,), out_size=2)
    tr = FederatedTrainer(cfg, model, host_mesh(2))
    res = tr.fit(_toy_sites(2), _toy_sites(2, n=16), _toy_sites(2, n=16), verbose=False)
    assert res["best_val_metric"] is not None
    assert res["best_val_epoch"] == 2


@pytest.mark.slow
def test_pretrain_uses_exact_gradients_with_compressed_engine():
    """ADVICE regression: warm start must run on dSGD even when the federated
    phase uses a compressed engine (and must not crash on engine-state shapes)."""
    from dinunet_implementations_tpu.core.config import PretrainArgs

    cfg = TrainConfig(
        epochs=2, batch_size=8, agg_engine="powerSGD", pretrain=True,
        pretrain_args=PretrainArgs(epochs=2, learning_rate=1e-3, batch_size=8),
    )
    model = MSANNet(in_size=6, hidden_sizes=(8,), out_size=2)
    tr = FederatedTrainer(cfg, model, host_mesh(2))
    res = tr.fit(_toy_sites(2, n=40), _toy_sites(2, n=16), _toy_sites(2, n=16),
                 verbose=False)
    assert np.isfinite(res["epoch_losses"]).all()


@pytest.mark.slow
def test_powersgd_residual_survives_epoch_boundary():
    """Review finding regression: powerSGD's per-site error-feedback residual
    must NOT be collapsed to site 0's copy between epoch_fn calls."""
    from dinunet_implementations_tpu.engines import make_engine

    for mesh in (host_mesh(2), None):
        task = FederatedTask(MSANNet(in_size=6, hidden_sizes=(8,), out_size=2))
        engine = make_engine("powerSGD", dad_reduction_rank=1)
        opt = make_optimizer("sgd", 0.01)
        state = init_train_state(
            task, engine, opt, jax.random.PRNGKey(0), jnp.ones((4, 6)), num_sites=2
        )
        X, y, w = _make_data(2, 2, 8, seed=9)  # heterogeneous site data
        fn = make_train_epoch_fn(task, engine, opt, mesh, 1)
        s1, _ = fn(state, X, y, w)
        e = s1.engine_state["e"]["linear_0"]["kernel"]
        assert e.shape[0] == 2  # per-site leading axis preserved
        e_np = np.asarray(e)
        assert not np.allclose(e_np[0], e_np[1]), "residuals must differ per site"
        # second epoch starts from per-site residuals (no collapse)
        s2, _ = fn(s1, X, y, w)
        e2 = np.asarray(s2.engine_state["e"]["linear_0"]["kernel"])
        assert not np.allclose(e2[0], e2[1])


def test_multiclass_auc_skips_absent_classes():
    """Review regression: a class missing from the eval set must not drag the
    macro AUC toward 0 — a perfect 3-class model with class 2 absent is ~1.0."""
    from dinunet_implementations_tpu.trainer.metrics import MulticlassMetrics

    m = MulticlassMetrics()
    m.add([[0.9, 0.05, 0.05], [0.1, 0.85, 0.05], [0.8, 0.1, 0.1],
           [0.05, 0.9, 0.05]], [0, 1, 0, 1])
    assert m.auc() == pytest.approx(1.0)
    assert m.accuracy() == pytest.approx(1.0)


# ---------------------------------------------------------------------------
# checkpoint wiring: mode="test", resume, warm start (VERDICT #5)
# ---------------------------------------------------------------------------


def test_mode_test_reproduces_stored_metrics(tmp_path):
    """mode='test' loads checkpoint_best and reproduces the training run's
    stored test_metrics without training."""
    cfg = TrainConfig(epochs=6, patience=10, batch_size=8)
    model = MSANNet(in_size=6, hidden_sizes=(8,), out_size=2)
    tr = FederatedTrainer(cfg, model, host_mesh(2), out_dir=str(tmp_path))
    train, val, test = _toy_sites(2, seed=1), _toy_sites(2, n=16, seed=2), _toy_sites(2, n=16, seed=3)
    res_train = tr.fit(train, val, test, verbose=False)

    cfg_test = cfg.replace(mode="test")
    tr2 = FederatedTrainer(cfg_test, model, host_mesh(2), out_dir=str(tmp_path))
    res_test = tr2.fit(train, val, test, verbose=False)
    assert res_test["test_metrics"] == res_train["test_metrics"]
    assert res_test["best_val_epoch"] == res_train["best_val_epoch"]


def test_mode_test_without_checkpoint_raises(tmp_path):
    cfg = TrainConfig(mode="test", epochs=2, batch_size=8)
    model = MSANNet(in_size=6, hidden_sizes=(8,), out_size=2)
    tr = FederatedTrainer(cfg, model, host_mesh(2), out_dir=str(tmp_path))
    with pytest.raises(FileNotFoundError, match="no trained checkpoint"):
        tr.fit(_toy_sites(2), _toy_sites(2, n=16), _toy_sites(2, n=16), verbose=False)


@pytest.mark.slow
def test_resume_matches_uninterrupted(tmp_path):
    """Kill a fit mid-fold, resume — same final metrics as an uninterrupted
    run (VERDICT #5 done-criterion)."""
    model = MSANNet(in_size=6, hidden_sizes=(8,), out_size=2)
    train, val, test = _toy_sites(2, seed=4), _toy_sites(2, n=16, seed=5), _toy_sites(2, n=16, seed=6)

    cfg_full = TrainConfig(epochs=8, patience=20, batch_size=8)
    tr_full = FederatedTrainer(cfg_full, model, host_mesh(2), out_dir=str(tmp_path / "full"))
    res_full = tr_full.fit(train, val, test, verbose=False)

    # "killed" after 4 epochs: same seed/config, shorter run
    cfg_half = cfg_full.replace(epochs=4)
    tr_half = FederatedTrainer(cfg_half, model, host_mesh(2), out_dir=str(tmp_path / "resumed"))
    tr_half.fit(train, val, test, verbose=False)
    # resume to the full 8 epochs
    tr_res = FederatedTrainer(cfg_full, model, host_mesh(2), out_dir=str(tmp_path / "resumed"))
    res_res = tr_res.fit(train, val, test, verbose=False, resume=True)

    assert res_res["test_metrics"] == res_full["test_metrics"]
    assert res_res["best_val_epoch"] == res_full["best_val_epoch"]
    assert len(res_res["epoch_losses"]) == len(res_full["epoch_losses"])
    np.testing.assert_allclose(res_res["epoch_losses"], res_full["epoch_losses"],
                               atol=1e-6)


@pytest.mark.slow
def test_pretrained_path_warm_start(tmp_path):
    """cfg.pretrained_path loads params from a saved checkpoint (the
    previously-dead load_params path)."""
    model = MSANNet(in_size=6, hidden_sizes=(8,), out_size=2)
    cfg = TrainConfig(epochs=3, batch_size=8)
    tr = FederatedTrainer(cfg, model, host_mesh(2), out_dir=str(tmp_path))
    res = tr.fit(_toy_sites(2, seed=7), _toy_sites(2, n=16, seed=8),
                 _toy_sites(2, n=16, seed=9), verbose=False)
    ckpt = str(tmp_path / "remote/simulatorRun/FS-Classification/fold_0/checkpoint_best.msgpack")

    # lr=0 → params stay at the warm start; they must equal the checkpoint's
    cfg2 = TrainConfig(epochs=1, batch_size=8, learning_rate=0.0,
                       pretrained_path=ckpt)
    tr2 = FederatedTrainer(cfg2, model, host_mesh(2))
    res2 = tr2.fit(_toy_sites(2, seed=7), _toy_sites(2, n=16, seed=8),
                   _toy_sites(2, n=16, seed=9), verbose=False)
    jax.tree.map(
        lambda a, b: np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=1e-7),
        res2["state"].params,
        res["state"].params,
    )


def test_per_site_logs_are_per_site(tmp_path):
    """VERDICT #8: each local{i}/logs.json carries that site's own test
    metrics, not a clone of the pooled numbers."""
    import json as _json

    cfg = TrainConfig(epochs=3, batch_size=8)
    model = MSANNet(in_size=6, hidden_sizes=(8,), out_size=2)
    tr = FederatedTrainer(cfg, model, host_mesh(2), out_dir=str(tmp_path))
    # deliberately different test data per site
    test = [_toy_sites(1, n=16, seed=20)[0], _toy_sites(1, n=16, seed=21)[0]]
    res = tr.fit(_toy_sites(2, seed=19), _toy_sites(2, n=16, seed=22), test,
                 verbose=False)
    logs = [
        _json.load(open(tmp_path / f"local{i}/simulatorRun/FS-Classification/fold_0/logs.json"))
        for i in range(2)
    ]
    assert logs[0]["site_index"] == 0 and logs[1]["site_index"] == 1
    assert logs[0]["test_metrics"] != logs[1]["test_metrics"]
    assert logs[0]["pooled_test_metrics"] == res["test_metrics"]
    # per-iteration durations: one entry per round, not per epoch
    steps_per_epoch = 40 // 8  # train n=40 per site, batch 8, drop_last
    assert len(logs[0]["local_iter_duration"]) == 3 * steps_per_epoch


def test_mode_test_reports_best_val_metric_and_site_count_independence(tmp_path):
    """Review regressions: mode='test' must report the stored best_val_metric
    (meta rides inside the msgpack), and must work with a different test-site
    count than training (eval-only restore has no engine-state shape tie)."""
    cfg = TrainConfig(epochs=4, batch_size=8)
    model = MSANNet(in_size=6, hidden_sizes=(8,), out_size=2)
    tr = FederatedTrainer(cfg, model, host_mesh(2), out_dir=str(tmp_path))
    res = tr.fit(_toy_sites(2, seed=30), _toy_sites(2, n=16, seed=31),
                 _toy_sites(2, n=16, seed=32), verbose=False)
    assert res["best_val_metric"] is not None

    # 3 test sites (training had 2) — eval-only restore must not care
    cfg_t = cfg.replace(mode="test")
    tr2 = FederatedTrainer(cfg_t, model, host_mesh(3), out_dir=str(tmp_path))
    res_t = tr2.fit(_toy_sites(3, seed=30), _toy_sites(3, n=16, seed=31),
                    _toy_sites(3, n=16, seed=33), verbose=False)
    assert res_t["best_val_metric"] == pytest.approx(res["best_val_metric"])
    assert res_t["best_val_epoch"] == res["best_val_epoch"]


def test_checkpoint_write_is_atomic_no_tmp_left(tmp_path):
    from dinunet_implementations_tpu.trainer.checkpoint import (
        load_checkpoint as _lc, save_checkpoint as _sc,
    )
    mesh = host_mesh(2)
    _, _, _, state, fn = _setup(mesh)
    p = _sc(str(tmp_path / "ck.msgpack"), state, meta={"epoch": 3})
    import os as _os
    assert not _os.path.exists(p + ".tmp")
    restored, meta = _lc(p, state, with_meta=True)
    assert meta["epoch"] == 3


def test_checkpoint_load_pre_meta_format(tmp_path):
    """ADVICE r2 regression: checkpoints written before meta_json existed
    (pre-0.2.0) must still load instead of failing the template match."""
    import flax.serialization

    mesh = host_mesh(2)
    _, _, _, state, fn = _setup(mesh)
    X, y, w = _make_data(2, 2, 8)
    state, _ = fn(state, X, y, w)
    old_payload = {
        "params": state.params,
        "batch_stats": state.batch_stats,
        "opt_state": state.opt_state,
        "engine_state": state.engine_state,
        "rng": state.rng,
        "round": state.round,
    }  # no meta_json key — the old on-disk format
    p = str(tmp_path / "old.msgpack")
    with open(p, "wb") as fh:
        fh.write(flax.serialization.to_bytes(old_payload))
    _, _, _, fresh, _ = _setup(mesh)
    restored, meta = load_checkpoint(p, fresh, with_meta=True)
    assert meta == {}
    jax.tree.map(
        lambda a, b: np.testing.assert_array_equal(np.asarray(a), np.asarray(b)),
        state.params,
        restored.params,
    )


def test_rounds_scan_xs_arms_bitwise_identical():
    """The epoch's two round-delivery forms — rounds-leading scan xs (the
    default) and the
    per-round dynamic-index A/B arm — must produce identical states and
    losses, so the benchmark arm can't silently rot."""
    S, steps, B, D = 3, 4, 8, 6
    task = FederatedTask(MSANNet(in_size=D, hidden_sizes=(8, 4)))
    engine = make_engine("dSGD")
    opt = make_optimizer("adam", 1e-3)
    rng = np.random.default_rng(0)
    x = jnp.asarray(rng.normal(size=(S, steps, B, D)).astype(np.float32))
    y = jnp.asarray((rng.random((S, steps, B)) > 0.5).astype(np.int32))
    w = jnp.ones((S, steps, B), jnp.float32)
    state0 = init_train_state(
        task, engine, opt, jax.random.PRNGKey(0), x[0, 0], num_sites=S
    )
    outs = {}
    for flag in (True, False):
        fn = make_train_epoch_fn(
            task, engine, opt, mesh=None, local_iterations=2,
            rounds_scan_xs=flag,
        )
        st, losses = fn(state0, x, y, w)
        outs[flag] = (st, losses)
    jax.tree.map(
        lambda a, b: np.testing.assert_array_equal(np.asarray(a), np.asarray(b)),
        outs[True][0].params, outs[False][0].params,
    )
    np.testing.assert_array_equal(
        np.asarray(outs[True][1]), np.asarray(outs[False][1])
    )
