"""End-to-end sequence/model-axis parallelism (VERDICT r2 #1).

Round 2 built and unit-tested the ring primitives (tests/test_sequence.py) but
left them unreachable from any config or trainer path. These tests cover the
wiring: ``TrainConfig.model_axis_size`` → a ``(site, model)`` mesh → the model
sharding its sequence axis internally → masked-loss + grad-psum assembly in
the train step (trainer/steps.py) — asserting the sharded run reproduces the
dense run, not just that it executes.
"""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from dinunet_implementations_tpu.core.config import TrainConfig
from dinunet_implementations_tpu.engines import make_engine
from dinunet_implementations_tpu.models import ICALstm, MultimodalNet
from dinunet_implementations_tpu.parallel.mesh import MODEL_AXIS, host_mesh
from dinunet_implementations_tpu.runner.registry import get_task
from dinunet_implementations_tpu.trainer import (
    FederatedTask,
    init_train_state,
    make_optimizer,
    make_train_epoch_fn,
)
from dinunet_implementations_tpu.trainer.steps import make_eval_fn


pytestmark = pytest.mark.slow  # shard_map integration tier: every test compiles a multi-device program


def _ica_model(seq_axis=None):
    return ICALstm(
        input_size=12, hidden_size=10, num_comps=3, window_size=4, num_cls=2,
        sequence_axis=seq_axis,
    )


def _epoch_data(S=2, steps=2, B=4, windows=8, comps=3, wlen=4, seed=0):
    rng = np.random.default_rng(seed)
    x = jnp.asarray(rng.normal(size=(S, steps, B, windows, comps, wlen)).astype(np.float32))
    y = jnp.asarray((rng.random((S, steps, B)) > 0.5).astype(np.int32))
    w = jnp.ones((S, steps, B), jnp.float32)
    return x, y, w


def _run_epochs(model, mesh, num_sites, data, epochs=3, optimizer="sgd"):
    task = FederatedTask(model)
    engine = make_engine("dSGD")
    opt = make_optimizer(optimizer, 1e-2)
    x, y, w = data
    state = init_train_state(
        task, engine, opt, jax.random.PRNGKey(0), x[0, 0], num_sites=num_sites
    )
    epoch_fn = make_train_epoch_fn(task, engine, opt, mesh, local_iterations=1)
    losses = []
    for _ in range(epochs):
        state, ls = epoch_fn(state, x, y, w)
        losses.extend(np.asarray(ls).tolist())
    return state, losses


def test_ica_train_matches_dense_over_model_axis():
    """Flagship e2e: 2 sites × model_axis 2 (4 devices) must reproduce the
    2-site dense run — same per-round losses AND same final params.

    SGD on purpose: it is linear in the gradient, so the assert is tight.
    (Verified during bring-up: grads match to ~1e-9; under Adam the early
    update is ≈ lr·sign(g), which amplifies that reduction-order noise into
    visible param drift while losses stay identical — covered by the Adam
    loss-trajectory test below.)"""
    data = _epoch_data()
    dense_state, dense_losses = _run_epochs(_ica_model(), host_mesh(2), 2, data)
    ring_state, ring_losses = _run_epochs(
        _ica_model(MODEL_AXIS), host_mesh(2, model_axis_size=2), 2, data
    )
    np.testing.assert_allclose(ring_losses, dense_losses, atol=1e-6)
    jax.tree.map(
        lambda a, b: np.testing.assert_allclose(
            np.asarray(a), np.asarray(b), atol=2e-6
        ),
        dense_state.params,
        ring_state.params,
    )
    jax.tree.map(
        lambda a, b: np.testing.assert_allclose(
            np.asarray(a), np.asarray(b), atol=2e-6
        ),
        dense_state.batch_stats,
        ring_state.batch_stats,
    )


def test_ica_adam_loss_trajectory_matches_dense():
    """Under Adam (the production optimizer) the per-round loss trajectory of
    the model-axis run tracks the dense run."""
    data = _epoch_data(seed=7)
    _, dense_losses = _run_epochs(
        _ica_model(), host_mesh(2), 2, data, optimizer="adam"
    )
    _, ring_losses = _run_epochs(
        _ica_model(MODEL_AXIS), host_mesh(2, model_axis_size=2), 2, data,
        optimizer="adam",
    )
    np.testing.assert_allclose(ring_losses, dense_losses, atol=1e-4)


def test_ica_eval_matches_dense_over_model_axis():
    data = _epoch_data()
    x, y, w = data
    dense_state, _ = _run_epochs(_ica_model(), host_mesh(2), 2, data, epochs=1)

    ring_model = _ica_model(MODEL_AXIS)
    ring_task = FederatedTask(ring_model)
    ring_task.init_variables(jax.random.PRNGKey(0), x[0, 0])
    dense_task = FederatedTask(_ica_model())
    dense_task.init_variables(jax.random.PRNGKey(0), x[0, 0])

    ev_dense = make_eval_fn(dense_task, host_mesh(2))
    ev_ring = make_eval_fn(ring_task, host_mesh(2, model_axis_size=2))
    # device-neutral copy: the trained state is committed to the 2-device
    # mesh; the ring eval jit places onto the 4-device mesh itself
    dense_state = jax.tree.map(np.asarray, dense_state)
    pd, ld, wd = ev_dense(dense_state, x, y, w)
    pr, lr, wr = ev_ring(dense_state, x, y, w)
    np.testing.assert_allclose(np.asarray(pr), np.asarray(pd), atol=2e-5)
    np.testing.assert_allclose(np.asarray(lr), np.asarray(ld), atol=1e-4)
    np.testing.assert_array_equal(np.asarray(wr), np.asarray(wd))


def test_multimodal_ring_forward_matches_local():
    """MultimodalNet attention="ring" + internal token sharding == the dense
    local-attention forward, on a real model-axis mesh."""
    rng = np.random.default_rng(1)
    # tokens = 2 + S windows; S=6 → T=8, divisible by the 4-way model axis
    S, C, W = 6, 3, 4
    model_local = MultimodalNet(
        fs_input_size=5, num_comps=C, window_size=W, embed_dim=16, num_heads=2,
        num_layers=2, num_cls=2,
    )
    model_ring = model_local.clone(attention="ring", axis_name=MODEL_AXIS)
    x = jnp.asarray(rng.normal(size=(3, 5 + S * C * W)).astype(np.float32))
    variables = model_local.init(
        {"params": jax.random.PRNGKey(0), "dropout": jax.random.PRNGKey(1)},
        x, train=False,
    )
    out_local = model_local.apply(variables, x, train=False)

    mesh = host_mesh(1, model_axis_size=4)
    from jax import shard_map
    from jax.sharding import PartitionSpec as P

    out_ring = shard_map(
        lambda v, xx: model_ring.apply(v, xx, train=False),
        mesh=mesh, in_specs=(P(), P()), out_specs=P(), check_vma=False,
    )(variables, x)
    np.testing.assert_allclose(np.asarray(out_ring), np.asarray(out_local), atol=2e-5)


def test_multimodal_ring_grads_match_local():
    """Masked-loss + psum-over-model-axis must assemble the exact full grad
    (the head/chunk double-count trap)."""
    rng = np.random.default_rng(2)
    S, C, W = 6, 2, 3
    model_local = MultimodalNet(
        fs_input_size=4, num_comps=C, window_size=W, embed_dim=8, num_heads=2,
        num_layers=1, num_cls=2,
    )
    model_ring = model_local.clone(attention="ring", axis_name=MODEL_AXIS)
    x = jnp.asarray(rng.normal(size=(2, 4 + S * C * W)).astype(np.float32))
    y = jnp.asarray([0, 1], jnp.int32)
    variables = model_local.init(
        {"params": jax.random.PRNGKey(0), "dropout": jax.random.PRNGKey(1)},
        x, train=False,
    )

    def loss_local(params):
        logits = model_local.apply({"params": params}, x, train=False)
        logp = jax.nn.log_softmax(logits)
        return -jnp.take_along_axis(logp, y[:, None], axis=-1).mean()

    g_local = jax.grad(loss_local)(variables["params"])

    mesh = host_mesh(1, model_axis_size=2)
    from jax import shard_map
    from jax.sharding import PartitionSpec as P

    def sharded_grad(params):
        def loss_ring(p):
            logits = model_ring.apply({"params": p}, x, train=False)
            logp = jax.nn.log_softmax(logits)
            loss = -jnp.take_along_axis(logp, y[:, None], axis=-1).mean()
            keep = (jax.lax.axis_index(MODEL_AXIS) == 0).astype(loss.dtype)
            return loss * keep

        g = jax.grad(loss_ring)(params)
        return jax.lax.psum(g, MODEL_AXIS)

    g_ring = shard_map(
        sharded_grad, mesh=mesh, in_specs=(P(),), out_specs=P(), check_vma=False
    )(variables["params"])
    jax.tree.map(
        lambda a, b: np.testing.assert_allclose(
            np.asarray(a), np.asarray(b), atol=1e-5
        ),
        g_local, g_ring,
    )


def test_ica_ring_bf16_pallas_tracks_dense():
    """Review-finding regression (r3): ring + compute_dtype=bf16 + the fused
    kernel — the relayed carry must stay f32 at chunk boundaries, so the
    sharded forward tracks the dense forward within bf16 tolerance."""
    from jax import shard_map
    from jax.sharding import PartitionSpec as P

    rng = np.random.default_rng(11)
    dense = ICALstm(
        input_size=12, hidden_size=10, num_comps=3, window_size=4, num_cls=2,
        compute_dtype="bfloat16", use_pallas=True,
    )
    ring = dense.clone(sequence_axis=MODEL_AXIS)
    x = jnp.asarray(rng.normal(size=(4, 8, 3, 4)).astype(np.float32))
    variables = dense.clone(use_pallas=False, compute_dtype=None).init(
        {"params": jax.random.PRNGKey(0), "dropout": jax.random.PRNGKey(1)},
        x, train=False,
    )
    out_dense = dense.apply(variables, x, train=False)
    mesh = host_mesh(1, model_axis_size=2)
    out_ring = shard_map(
        lambda v, xx: ring.apply(v, xx, train=False),
        mesh=mesh, in_specs=(P(), P()), out_specs=P(), check_vma=False,
    )(variables, x)
    np.testing.assert_allclose(
        np.asarray(out_ring), np.asarray(out_dense), atol=0.05
    )


def test_ring_dropout_decorrelated_across_chunks():
    """Train-mode dropout in the ring transformer must draw a DIFFERENT mask
    per token chunk: feed every device an identical chunk — correlated
    (tiled) dropout would make all per-device outputs identical."""
    from jax import shard_map
    from jax.sharding import PartitionSpec as P

    from dinunet_implementations_tpu.models.transformer import TransformerBlock

    rng = np.random.default_rng(5)
    block = TransformerBlock(
        embed_dim=8, num_heads=2, dropout_rate=0.5, attention="ring",
        axis_name=MODEL_AXIS,
    )
    x = jnp.asarray(rng.normal(size=(2, 4, 8)).astype(np.float32))
    variables = block.clone(attention="local", axis_name=None).init(
        {"params": jax.random.PRNGKey(0), "dropout": jax.random.PRNGKey(1)},
        x, train=False,
    )
    mesh = host_mesh(1, model_axis_size=4)

    def fn(v, xx):
        out = block.apply(
            v, xx, train=True, rngs={"dropout": jax.random.PRNGKey(2)}
        )
        return jax.lax.all_gather(out, MODEL_AXIS)

    outs = np.asarray(
        shard_map(fn, mesh=mesh, in_specs=(P(), P()), out_specs=P(), check_vma=False)(
            variables, x
        )
    )  # [4 devices, B, T_local, E] — same input chunk everywhere
    diffs = [np.abs(outs[i] - outs[0]).max() for i in range(1, 4)]
    assert all(d > 1e-6 for d in diffs), f"dropout masks tiled across chunks: {diffs}"


def test_fed_runner_builds_model_axis_mesh(tmp_path):
    """cfg.model_axis_size reaches the mesh and the model through FedRunner."""
    from dinunet_implementations_tpu.runner.fed_runner import FedRunner

    # synthetic 2-site ICA tree (shape mirrors tests/test_runner.py's helper)
    import pandas as pd

    rng = np.random.default_rng(3)
    n_sub, comps, T = 12, 3, 16
    for s in range(2):
        d = tmp_path / "input" / f"local{s}" / "simulatorRun"
        d.mkdir(parents=True)
        data = rng.normal(size=(n_sub, comps, T)).astype(np.float32)
        np.savez(d / "tc.npz", data=data)
        pd.DataFrame(
            {"index": list(range(n_sub)), "label": rng.integers(0, 2, n_sub)}
        ).to_csv(d / "labels.csv", index=False)

    cfg = TrainConfig(
        task_id="ICA-Classification",
        epochs=1,
        batch_size=4,
        model_axis_size=2,
        split_ratio=(0.6, 0.2, 0.2),
    )
    cfg = dataclasses.replace(
        cfg,
        ica_args=dataclasses.replace(
            cfg.ica_args,
            data_file="tc.npz", labels_file="labels.csv",
            num_components=comps, temporal_size=T, window_size=4,
            window_stride=4, input_size=8, hidden_size=6,
        ),
    )
    runner = FedRunner(cfg, data_path=str(tmp_path), out_dir=str(tmp_path / "out"))
    assert dict(runner.mesh.shape) == {"site": 2, "model": 2}
    model = get_task(runner.cfg.task_id).build_model(runner.cfg)
    assert model.sequence_axis == MODEL_AXIS
    results = runner.run(verbose=False)
    assert np.isfinite(results[0]["test_metrics"][0][0])


def test_model_axis_requires_enough_devices(tmp_path):
    from dinunet_implementations_tpu.runner.fed_runner import FedRunner

    for s in range(5):  # 5 sites × model 2 = 10 > 8 virtual devices
        (tmp_path / "input" / f"local{s}" / "simulatorRun").mkdir(parents=True)
    with pytest.raises(ValueError, match="model_axis_size"):
        FedRunner(
            TrainConfig(model_axis_size=2), data_path=str(tmp_path),
        )


def test_long_context_ring_trains_512_windows():
    """Long-context capability: a sequence far beyond the reference's ~98
    windows (512), sharded 4-way over the model axis — the ring LSTM carries
    the recurrence across chunks and training stays finite and learns."""
    S_WINDOWS = 512
    rng = np.random.default_rng(13)
    model = ICALstm(
        input_size=8, hidden_size=6, num_comps=2, window_size=3, num_cls=2,
        sequence_axis=MODEL_AXIS,
    )
    task = FederatedTask(model)
    engine = make_engine("dSGD")
    opt = make_optimizer("adam", 1e-2)
    B = 4
    x_np = rng.normal(size=(2, 2, B, S_WINDOWS, 2, 3)).astype(np.float32)
    y = jnp.asarray((rng.random((2, 2, B)) > 0.5).astype(np.int32))
    # plant a class signal so the loss must actually fall
    x_np += np.asarray(y)[..., None, None, None] * 0.5
    x = jnp.asarray(x_np)
    w = jnp.ones((2, 2, B), jnp.float32)
    mesh = host_mesh(2, model_axis_size=4)  # 2 sites x 4-way sequence shard
    state = init_train_state(
        task, engine, opt, jax.random.PRNGKey(0), x[0, 0], num_sites=2
    )
    fn = make_train_epoch_fn(task, engine, opt, mesh, local_iterations=1)
    losses = []
    for _ in range(4):
        state, ls = fn(state, x, y, w)
        losses.append(float(np.asarray(ls).mean()))
    assert all(np.isfinite(losses))
    assert losses[-1] < losses[0], f"loss did not fall: {losses}"


@pytest.mark.parametrize("engine_name,kw", [
    ("rankDAD", dict(dad_reduction_rank=4, dad_num_pow_iters=3, dad_tol=1e-3)),
    ("powerSGD", dict(dad_reduction_rank=4)),
])
def test_compressed_engines_with_model_axis(engine_name, kw):
    """Interaction coverage: compressed engines × sequence parallelism —
    the (2 site × 2 model) run must match the dense 2-site run under SGD
    (engine collectives ride the site axis while the model shards the
    window axis)."""
    data = _epoch_data(seed=17)
    x, y, w = data

    def run(model, mesh):
        task = FederatedTask(model)
        engine = make_engine(engine_name, **kw)
        opt = make_optimizer("sgd", 1e-2)
        state = init_train_state(
            task, engine, opt, jax.random.PRNGKey(0), x[0, 0], num_sites=2
        )
        fn = make_train_epoch_fn(task, engine, opt, mesh, local_iterations=1)
        for _ in range(2):
            state, ls = fn(state, x, y, w)
        return jax.tree.map(np.asarray, state), np.asarray(ls)

    s_dense, l_dense = run(_ica_model(), host_mesh(2))
    s_ring, l_ring = run(_ica_model(MODEL_AXIS), host_mesh(2, model_axis_size=2))
    np.testing.assert_allclose(l_ring, l_dense, atol=1e-5)
    jax.tree.map(
        lambda a, b: np.testing.assert_allclose(a, b, atol=1e-5),
        s_dense.params, s_ring.params,
    )
    # per-site engine state (e.g. powerSGD residuals) must agree too
    jax.tree.map(
        lambda a, b: np.testing.assert_allclose(a, b, atol=1e-5),
        s_dense.engine_state, s_ring.engine_state,
    )


def test_folding_combined_with_model_axis():
    """4 sites folded 2-per-device × model_axis 2 — a 4-device (2 site ×
    2 model) mesh with in-device folding — == the plain 4-site vmap run."""
    data = _epoch_data(S=4, seed=19)
    x, y, w = data

    def run(model, mesh):
        task = FederatedTask(model)
        engine = make_engine("dSGD")
        opt = make_optimizer("sgd", 1e-2)
        state = init_train_state(
            task, engine, opt, jax.random.PRNGKey(0), x[0, 0], num_sites=4
        )
        fn = make_train_epoch_fn(task, engine, opt, mesh, local_iterations=1)
        for _ in range(2):
            state, ls = fn(state, x, y, w)
        return jax.tree.map(np.asarray, state), np.asarray(ls)

    s_plain, l_plain = run(_ica_model(), None)
    # mesh: 2 devices on site axis (4 sites folded 2-per-device) × 2 model
    s_combo, l_combo = run(
        _ica_model(MODEL_AXIS), host_mesh(2, model_axis_size=2)
    )
    np.testing.assert_allclose(l_combo, l_plain, atol=1e-5)
    jax.tree.map(
        lambda a, b: np.testing.assert_allclose(a, b, atol=1e-5),
        s_plain.params, s_combo.params,
    )
