"""Device-resident input pipeline tests: index plans, on-device gather
bit-exactness, state donation, prefetch lifecycle, and the persistent
compilation cache (ISSUE 4 tentpole)."""

import threading

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from dinunet_implementations_tpu.checks.sanitize import jit_cache_size
from dinunet_implementations_tpu.core.config import TrainConfig
from dinunet_implementations_tpu.data.api import (
    SiteArrays,
    merged_sample_shape,
    stack_site_inventory,
    stored_data_index,
    stored_sample_shape,
)
from dinunet_implementations_tpu.data.batching import (
    epoch_steps,
    materialize_plan,
    plan_epoch,
    plan_epoch_positions,
)
from dinunet_implementations_tpu.engines import make_engine
from dinunet_implementations_tpu.models import ICALstm, MSANNet
from dinunet_implementations_tpu.parallel import host_mesh
from dinunet_implementations_tpu.parallel.distributed import put_site_inventory
from dinunet_implementations_tpu.robustness import FaultPlan, Preempted, poison_inputs
from dinunet_implementations_tpu.trainer import (
    FederatedTask,
    FederatedTrainer,
    init_train_state,
    make_optimizer,
    make_train_epoch_fn,
)
from dinunet_implementations_tpu.trainer import loop as trainer_loop
from dinunet_implementations_tpu.trainer.steps import _gather_batch


def _mk_site(n, d=6, seed=0):
    rng = np.random.default_rng(seed)
    X = rng.normal(size=(n, d)).astype(np.float32)
    return SiteArrays(X, (X.sum(-1) > 0).astype(np.int32),
                      np.arange(n, dtype=np.int32))


def _hetero_sites():
    # heterogeneous sizes: wrap recycling, an undersized site, a multi-wrap
    # site — the shapes the FS fixture (73-120 subjects) produces
    return [_mk_site(40, seed=1), _mk_site(21, seed=2), _mk_site(33, seed=3)]


def _toy_sites(ns, n=40, seed=0):
    return [_mk_site(n, seed=seed + i) for i in range(ns)]


# ---------------------------------------------------------------------------
# plan_epoch refactor: index plans + the wrap-mode tiling (satellite)
# ---------------------------------------------------------------------------


def _legacy_plan_epoch(sites, batch_size, seed=0, shuffle=True,
                       drop_last=True, pad_mode="wrap"):
    """The pre-refactor plan_epoch (repeated list concatenation per site),
    kept verbatim as the behavioral reference for the index-math rewrite."""
    def site_batches(order):
        n = len(order)
        if drop_last:
            n = (n // batch_size) * batch_size
        return [order[i:i + batch_size] for i in range(0, n, batch_size)]

    S = len(sites)
    feat_shape = next(s.inputs.shape[1:] for s in sites if len(s))
    rng = np.random.default_rng(seed)
    per_site = []
    for s in sites:
        order = rng.permutation(len(s)) if shuffle else np.arange(len(s))
        per_site.append(site_batches(order))
    steps = max(len(b) for b in per_site)
    inputs = np.zeros((S, steps, batch_size) + feat_shape, np.float32)
    labels = np.zeros((S, steps, batch_size), np.int32)
    weights = np.zeros((S, steps, batch_size), np.float32)
    indices = np.full((S, steps, batch_size), -1, np.int32)
    for si, (site, batches) in enumerate(zip(sites, per_site)):
        if pad_mode == "wrap" and batches:
            while len(batches) < steps:
                order = rng.permutation(len(site)) if shuffle else np.arange(len(site))
                batches = batches + site_batches(order)
            batches = batches[:steps]
        for bi, ix in enumerate(batches):
            k = len(ix)
            sel = site.take(ix)
            inputs[si, bi, :k] = sel.inputs
            labels[si, bi, :k] = sel.labels
            weights[si, bi, :k] = 1.0
            indices[si, bi, :k] = sel.indices
    return inputs, labels, weights, indices


@pytest.mark.parametrize("pad_mode,drop_last", [
    ("wrap", True), ("mask", True), ("mask", False), ("wrap", False),
])
@pytest.mark.parametrize("seed", [0, 7])
def test_plan_epoch_bitstable_across_tiling_refactor(pad_mode, drop_last, seed):
    """The wrap-mode tiling rewrite (single computed tiling of reshuffled
    orders instead of repeated list concatenation) must reproduce the legacy
    planner bit-for-bit — same RNG draw sequence, same batches."""
    sites = _hetero_sites() + [_mk_site(0, seed=9)]  # incl. an empty site
    fb = plan_epoch(sites, 8, seed=seed, pad_mode=pad_mode, drop_last=drop_last)
    li, ll, lw, lx = _legacy_plan_epoch(
        sites, 8, seed=seed, pad_mode=pad_mode, drop_last=drop_last
    )
    np.testing.assert_array_equal(fb.inputs, li)
    np.testing.assert_array_equal(fb.labels, ll)
    np.testing.assert_array_equal(fb.weights, lw)
    np.testing.assert_array_equal(fb.indices, lx)


def test_plan_positions_are_compact_and_consistent():
    sites = _hetero_sites()
    plan = plan_epoch_positions(sites, 8, seed=3, pad_mode="wrap")
    assert plan.positions.dtype == np.int32
    assert plan.steps == epoch_steps(sites, 8)
    # every live position indexes into its own site's inventory
    for si, s in enumerate(sites):
        pos = plan.positions[si]
        assert pos.max() < len(s)
        live = pos[pos >= 0]
        assert (live >= 0).all()
    # the plan is ~bytes where the dense tensor is ~kilobytes per sample
    fb = materialize_plan(sites, plan)
    assert plan.nbytes * 4 < fb.inputs.nbytes


# ---------------------------------------------------------------------------
# device path == host path, bit-exact (tentpole invariant)
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("pad_mode", ["wrap", "mask"])
@pytest.mark.parametrize("use_mesh", [False, True])
def test_device_epoch_matches_host_bit_exact(pad_mode, use_mesh):
    """The on-device gather epoch must equal the host-materialized epoch
    bit-for-bit: params, losses, and health, for both pad modes, on both the
    vmap-folded and shard_map topologies."""
    sites = _hetero_sites()
    mesh = host_mesh(3) if use_mesh else None
    task = FederatedTask(MSANNet(in_size=6, hidden_sizes=(16,), out_size=2))
    engine = make_engine("dSGD")
    opt = make_optimizer("adam", 1e-2)
    plan = plan_epoch_positions(sites, 8, seed=7, pad_mode=pad_mode,
                                drop_last=(pad_mode == "wrap"))
    fb = materialize_plan(sites, plan)
    inv = stack_site_inventory(sites)
    s0 = init_train_state(task, engine, opt, jax.random.PRNGKey(0),
                          jnp.ones((4, 6)), num_sites=3)
    fh = make_train_epoch_fn(task, engine, opt, mesh, 2)
    fd = make_train_epoch_fn(task, engine, opt, mesh, 2, pipeline="device",
                             donate_state=True)
    sh, lh = fh(s0, jnp.asarray(fb.inputs), jnp.asarray(fb.labels),
                jnp.asarray(fb.weights))
    s0d = jax.tree.map(jnp.copy, s0)
    sd, ld = fd(s0d, jnp.asarray(inv.inputs), jnp.asarray(inv.labels),
                jnp.asarray(plan.positions))
    np.testing.assert_array_equal(np.asarray(lh), np.asarray(ld))
    jax.tree.map(
        lambda a, b: np.testing.assert_array_equal(np.asarray(a), np.asarray(b)),
        (sh.params, sh.health), (sd.params, sd.health),
    )


@pytest.mark.parametrize("use_mesh", [False, True])
def test_device_epoch_matches_host_with_fault_plan(use_mesh):
    """Scheduled drops + data-layer NaN poisoning: the device path's traced
    poison gate must reproduce the host path's poisoned dense tensor —
    identical losses, params, and quarantine counters."""
    import dataclasses

    sites = _hetero_sites()
    mesh = host_mesh(3) if use_mesh else None
    L = 2
    fp = FaultPlan(drop=((1, 1, 1),), nan_at=((0, 2),))
    task = FederatedTask(MSANNet(in_size=6, hidden_sizes=(16,), out_size=2))
    engine = make_engine("dSGD")
    opt = make_optimizer("adam", 1e-2)
    plan = plan_epoch_positions(sites, 8, seed=7, pad_mode="wrap")
    fb = materialize_plan(sites, plan)
    rounds = plan.steps // L
    live = fp.liveness(3, 0, rounds)
    nan = fp.nan_mask(3, 0, rounds)
    fb = dataclasses.replace(fb, inputs=poison_inputs(fb.inputs, nan, L))
    inv = stack_site_inventory(sites)
    s0 = init_train_state(task, engine, opt, jax.random.PRNGKey(0),
                          jnp.ones((4, 6)), num_sites=3)
    fh = make_train_epoch_fn(task, engine, opt, mesh, L)
    fd = make_train_epoch_fn(task, engine, opt, mesh, L, pipeline="device",
                             donate_state=True)
    sh, lh = fh(s0, jnp.asarray(fb.inputs), jnp.asarray(fb.labels),
                jnp.asarray(fb.weights), jnp.asarray(live))
    sd, ld = fd(jax.tree.map(jnp.copy, s0), jnp.asarray(inv.inputs),
                jnp.asarray(inv.labels), jnp.asarray(plan.positions),
                jnp.asarray(live), jnp.asarray(nan.astype(np.float32)))
    np.testing.assert_array_equal(np.asarray(lh), np.asarray(ld))
    jax.tree.map(
        lambda a, b: np.testing.assert_array_equal(np.asarray(a), np.asarray(b)),
        (sh.params, sh.health), (sd.params, sd.health),
    )


def test_trainer_device_fit_matches_host_fit():
    """End-to-end: a full fit under cfg.pipeline='device' (donation +
    prefetch included) equals the host-pipeline fit exactly — losses,
    selection, and test metrics."""
    res = {}
    for pipe in ("host", "device"):
        cfg = TrainConfig(epochs=5, batch_size=8, pipeline=pipe)
        tr = FederatedTrainer(
            cfg, MSANNet(in_size=6, hidden_sizes=(16,), out_size=2),
            host_mesh(2),
        )
        res[pipe] = tr.fit(_toy_sites(2, seed=1), _toy_sites(2, n=16, seed=2),
                           _toy_sites(2, n=16, seed=3), verbose=False)
    np.testing.assert_array_equal(res["host"]["epoch_losses"],
                                  res["device"]["epoch_losses"])
    assert res["host"]["test_metrics"] == res["device"]["test_metrics"]
    assert res["host"]["best_val_epoch"] == res["device"]["best_val_epoch"]


def test_trainer_device_fit_matches_host_fit_with_faults():
    """Chaos stays green AND identical on the device path: drops + NaN
    poisoning through the full trainer produce the same epoch losses and
    health counters as the host path."""
    fp = FaultPlan(drop=((1, 2, 3),), nan_at=((1, 0),))
    res = {}
    for pipe in ("host", "device"):
        cfg = TrainConfig(epochs=4, batch_size=8, pipeline=pipe)
        tr = FederatedTrainer(
            cfg, MSANNet(in_size=6, hidden_sizes=(16,), out_size=2),
            host_mesh(2), fault_plan=fp,
        )
        res[pipe] = tr.fit(_toy_sites(2, seed=1), _toy_sites(2, n=16, seed=2),
                           _toy_sites(2, n=16, seed=3), verbose=False)
    np.testing.assert_allclose(res["host"]["epoch_losses"],
                               res["device"]["epoch_losses"], rtol=0, atol=0)
    assert res["host"]["site_health"] == res["device"]["site_health"]
    assert res["host"]["test_metrics"] == res["device"]["test_metrics"]


# ---------------------------------------------------------------------------
# the resident form (ISSUE 27): a round's batch is one row gather from an
# inventory stored as the gather writes and the model reads
# ---------------------------------------------------------------------------

# windows x components x timepoints: merged to [29, 128], stored as [32, 128]
# (29 is no multiple of the 8-row tile, like HCP's 98 windows)
ICA_SHAPE = (29, 16, 8)


def _sites_of(shape, sizes, seed=0):
    rng = np.random.default_rng(seed)
    out = []
    for n in sizes:
        x = rng.normal(size=(n,) + shape).astype(np.float32)
        y = (x.reshape(n, -1).sum(-1) > 0).astype(np.int32)
        out.append(SiteArrays(x, y, np.arange(n, dtype=np.int32)))
    return out


def _nan_in_padding(inv):
    """NaN (inputs) and 7 (labels) in every element of the host inventory
    that is not a subject's data: the zero row, rows past a site's count, the
    tile padding of every stored sample."""
    data_rows = merged_sample_shape(inv.sample_shape)[:1]
    if len(inv.inputs.shape) > 3 and data_rows[0] != inv.inputs.shape[2]:
        inv.inputs[:, :, data_rows[0]:] = np.nan
    for si, n in enumerate(inv.counts):
        inv.inputs[si, n:] = np.nan
        inv.labels[si, n:] = 7
    return inv


GATHER_CASES = {
    # name: (sample shape, site sizes, pad_mode, drop_last, pinned rows, dtype)
    "ica_windows_not_x8": (ICA_SHAPE, [24, 24, 24], "wrap", True, None, None),
    "ica_bf16_minus_one": (ICA_SHAPE, [20, 9, 13], "mask", False, None,
                           jnp.bfloat16),
    "rows_of_features": ((6,), [32, 32, 32], "wrap", True, None, None),
    "unequal_sites_wrap": ((6,), [40, 21, 33], "wrap", True, None, None),
    "unequal_sites_minus_one": ((6,), [40, 21, 33], "mask", False, None, None),
    "fixed_inventory_rows": ((6,), [40, 21, 33], "mask", False, 48, None),
    "minor_fills_a_tile": ((5, 128), [16, 11, 16], "mask", False, None, None),
}


def test_stored_sample_shape_is_a_function_of_the_shape_alone():
    assert merged_sample_shape((98, 100, 10)) == (98, 1000)
    assert stored_sample_shape((98, 100, 10)) == (104, 1000)  # HCP
    assert stored_sample_shape(ICA_SHAPE) == (32, 128)
    assert stored_sample_shape((66,)) == (66,)  # one dimension: no pad
    assert stored_sample_shape((8, 4, 4)) == (128,)  # merges to one
    assert stored_sample_shape((5, 128)) == (5, 128)  # 3 of 8 more: too much
    assert stored_sample_shape((96, 256)) == (96, 256)  # whole tiles already
    assert stored_sample_shape((4, 64, 200)) == (4, 64, 200)


@pytest.mark.parametrize("poison", [False, True])
@pytest.mark.parametrize("case", sorted(GATHER_CASES))
def test_device_gather_equals_host_materialization(case, poison):
    """Inputs, labels and weights of every round, bit for bit, from an
    inventory whose host padding held NaN before the upload; the uploaded
    zero row and pad rows are exactly zero."""
    shape, sizes, pad_mode, drop_last, rows, dtype = GATHER_CASES[case]
    sites = _sites_of(shape, sizes, seed=len(case))
    plan = plan_epoch_positions(sites, 8, seed=5, pad_mode=pad_mode,
                                drop_last=drop_last)
    if pad_mode == "mask" and sizes != [sizes[0]] * len(sizes):
        assert (plan.positions < 0).any()  # the case does hold -1 slots
    fb = materialize_plan(sites, plan)
    want = fb.inputs if dtype is None else fb.inputs.astype(dtype)
    nan = np.zeros((len(sites), plan.steps), bool)
    if poison:
        nan[1, 0] = nan[2, plan.steps - 1] = True
        want = poison_inputs(want, nan, 1)
    inv = _nan_in_padding(stack_site_inventory(sites, rows))
    n_max = rows or max(sizes)
    assert inv.rows == n_max and inv.sample_shape == shape
    assert inv.inputs.shape == (len(sites), n_max + 1) + stored_sample_shape(shape)
    inv_x, inv_y = put_site_inventory(None, inv, dtype)

    up_x, up_y = np.asarray(inv_x, np.float32), np.asarray(inv_y)
    merged = merged_sample_shape(shape)
    for si, s in enumerate(sites):
        data = up_x[si, :len(s)][stored_data_index(shape)]
        ref = s.inputs if dtype is None else np.asarray(
            jnp.asarray(s.inputs, dtype), np.float32)
        np.testing.assert_array_equal(data.reshape(s.inputs.shape), ref)
        np.testing.assert_array_equal(up_y[si, :len(s)], s.labels)
        assert not up_x[si, len(s):].any() and not up_y[si, len(s):].any()
    if len(merged) > 1:
        assert not up_x[:, :, merged[0]:].any()
    assert not up_x[:, -1].any() and not up_y[:, -1].any()

    # one round block a step ([L=1, B]), as the rounds scan gathers them
    gather = jax.jit(jax.vmap(lambda ex, ey, ix, pz: _gather_batch(
        ex, ey, ix, pz if poison else None, sample_shape=shape)))
    for step in range(plan.steps):
        xb, yb, wb = gather(inv_x, inv_y,
                            jnp.asarray(plan.positions[:, step:step + 1]),
                            jnp.asarray(nan[:, step], jnp.float32))
        np.testing.assert_array_equal(
            np.asarray(xb, np.float32)[:, 0],
            np.asarray(want[:, step], np.float32))
        np.testing.assert_array_equal(np.asarray(yb)[:, 0], fb.labels[:, step])
        np.testing.assert_array_equal(np.asarray(wb)[:, 0], fb.weights[:, step])


def test_gather_refuses_an_inventory_of_another_shape():
    with pytest.raises(ValueError, match="resident form"):
        _gather_batch(jnp.zeros((5, 30, 128)), jnp.zeros((5,), jnp.int32),
                      jnp.zeros((1, 2), jnp.int32), sample_shape=ICA_SHAPE)


def _resident_fit(monkeypatch, kind, nan_padding, epochs=3):
    """``epochs`` epochs through FederatedTrainer's device pipeline; with
    ``nan_padding`` the host inventory's padding holds NaN before upload."""
    if kind == "ica":
        model = ICALstm(input_size=8, hidden_size=8, num_comps=ICA_SHAPE[1],
                        window_size=ICA_SHAPE[2], dropout_rate=0.0)
        sites = _sites_of(ICA_SHAPE, [20, 9, 13], seed=2)
        rows = None
    else:
        model = MSANNet(in_size=6, hidden_sizes=(16,), out_size=2)
        sites = _hetero_sites()
        rows = 48
    if nan_padding:
        monkeypatch.setattr(
            trainer_loop, "stack_site_inventory",
            lambda s, r=None: _nan_in_padding(stack_site_inventory(s, r)))
    cfg = TrainConfig(epochs=epochs, batch_size=8, pipeline="device")
    tr = FederatedTrainer(cfg, model, None)
    tr.fixed_inventory_rows = rows
    state = tr.init_state(jnp.ones((8,) + sites[0].inputs.shape[1:]),
                          num_sites=len(sites))
    losses, sizes = [], []
    for epoch in range(1, epochs + 1):
        state, epoch_losses = tr.run_epoch(state, sites, epoch, batch_size=8)
        losses.append(np.asarray(epoch_losses))
        sizes.append(jit_cache_size(tr.epoch_fn))
    return np.concatenate(losses), jax.device_get(state.params), sizes


@pytest.mark.parametrize("kind", ["ica", "rows_pinned"])
def test_epoch_does_not_read_what_the_upload_did_not_write(kind, monkeypatch):
    """The padding of the host arrays is overwritten at upload, not trusted:
    NaN there changes nothing; and three epochs compile one program."""
    clean = _resident_fit(monkeypatch, kind, nan_padding=False)
    dirty = _resident_fit(monkeypatch, kind, nan_padding=True)
    assert np.isfinite(clean[0]).all()
    np.testing.assert_array_equal(clean[0], dirty[0])
    jax.tree.map(np.testing.assert_array_equal, clean[1], dirty[1])
    for sizes in (clean[2], dirty[2]):
        assert sizes == [sizes[0]] * 3 and sizes[0] in (1, None)


def test_device_epoch_matches_host_for_a_padded_sample_shape():
    """A sample the inventory stores padded and merged (29 windows in 32
    rows, 16 x 8 in 128 columns) trains bit for bit as the host pipeline's
    dense ``[S, steps, B, 29, 16, 8]`` epoch does, ``-1`` slots included."""
    sites = _sites_of(ICA_SHAPE, [20, 9, 13], seed=4)
    task = FederatedTask(ICALstm(
        input_size=8, hidden_size=8, num_comps=ICA_SHAPE[1],
        window_size=ICA_SHAPE[2], dropout_rate=0.0))
    engine, opt = make_engine("dSGD"), make_optimizer("adam", 1e-2)
    plan = plan_epoch_positions(sites, 8, seed=7, pad_mode="mask",
                                drop_last=False)
    assert (plan.positions < 0).any()
    fb = materialize_plan(sites, plan)
    inv_x, inv_y = put_site_inventory(None, stack_site_inventory(sites))
    s0 = init_train_state(task, engine, opt, jax.random.PRNGKey(0),
                          jnp.ones((4,) + ICA_SHAPE), num_sites=3)
    fh = make_train_epoch_fn(task, engine, opt, None, 1)
    fd = make_train_epoch_fn(task, engine, opt, None, 1, pipeline="device")
    sh, lh = fh(s0, jnp.asarray(fb.inputs), jnp.asarray(fb.labels),
                jnp.asarray(fb.weights))
    sd, ld = fd(s0, inv_x, inv_y, jnp.asarray(plan.positions))
    np.testing.assert_array_equal(np.asarray(lh), np.asarray(ld))
    jax.tree.map(
        lambda a, b: np.testing.assert_array_equal(np.asarray(a), np.asarray(b)),
        sh.params, sd.params,
    )


# ---------------------------------------------------------------------------
# donation sanity (satellite): donated buffers are consumed, never reused
# ---------------------------------------------------------------------------


def test_donated_state_buffers_are_released():
    """donate_state=True must actually donate: the input state's buffers are
    deleted after dispatch, and chaining from the RETURNED state works."""
    sites = _hetero_sites()
    task = FederatedTask(MSANNet(in_size=6, hidden_sizes=(8,), out_size=2))
    engine = make_engine("dSGD")
    opt = make_optimizer("adam", 1e-2)
    plan = plan_epoch_positions(sites, 8, seed=1)
    inv = stack_site_inventory(sites)
    s0 = init_train_state(task, engine, opt, jax.random.PRNGKey(0),
                          jnp.ones((4, 6)), num_sites=3)
    fd = make_train_epoch_fn(task, engine, opt, None, 1, pipeline="device",
                             donate_state=True)
    args = (jnp.asarray(inv.inputs), jnp.asarray(inv.labels),
            jnp.asarray(plan.positions))
    s1, _ = fd(s0, *args)
    leaf = s0.params["linear_0"]["kernel"]
    if not hasattr(leaf, "is_deleted"):
        pytest.skip("jax build does not expose buffer deletion state")
    assert leaf.is_deleted(), "input state must be consumed by donation"
    s2, _ = fd(s1, *args)  # chaining from the returned state stays valid
    assert np.isfinite(np.asarray(s2.params["linear_0"]["kernel"])).all()
    # the INVENTORY is not donated: it must survive every epoch
    assert not args[0].is_deleted()


def test_trainer_never_references_donated_buffers():
    """Guard for future refactors (the donation-sanity satellite): a full
    fit with donation enabled must keep best-state tracking on live buffers
    — the selected state evaluates and serializes after epochs that donated
    the states it was snapshotted from."""
    cfg = TrainConfig(epochs=6, batch_size=8, patience=50, pipeline="device",
                      donate_epoch_state=True)
    tr = FederatedTrainer(cfg, MSANNet(in_size=6, hidden_sizes=(16,), out_size=2),
                          host_mesh(2))
    res = tr.fit(_toy_sites(2, seed=4), _toy_sites(2, n=16, seed=5),
                 _toy_sites(2, n=16, seed=6), verbose=False)
    # best_state materializes fully (a donated alias would raise here)
    leaves = jax.tree.leaves(jax.tree.map(np.asarray, res["state"].params))
    assert all(np.isfinite(a).all() for a in leaves)
    assert np.isfinite(res["epoch_losses"]).all()
    # donation off must give the identical trajectory
    cfg2 = cfg.replace(donate_epoch_state=False)
    tr2 = FederatedTrainer(cfg2, MSANNet(in_size=6, hidden_sizes=(16,), out_size=2),
                           host_mesh(2))
    res2 = tr2.fit(_toy_sites(2, seed=4), _toy_sites(2, n=16, seed=5),
                   _toy_sites(2, n=16, seed=6), verbose=False)
    np.testing.assert_array_equal(res["epoch_losses"], res2["epoch_losses"])
    assert res["test_metrics"] == res2["test_metrics"]


# ---------------------------------------------------------------------------
# prefetch lifecycle (satellite): clean shutdown on Preempted, resume intact
# ---------------------------------------------------------------------------


def _prefetch_threads():
    return [t for t in threading.enumerate()
            if t.name.startswith("dinunet-epoch-prefetch") and t.is_alive()]


def test_prefetch_thread_shutdown_clean_on_preempted(tmp_path):
    """A FaultPlan kill mid-fit raises Preempted AFTER the checkpoint; the
    prefetch thread must be joined (no leak into the resumed run), and the
    resumed fit must finish with the exact uninterrupted trajectory."""
    model = MSANNet(in_size=6, hidden_sizes=(8,), out_size=2)
    train = _toy_sites(2, seed=4)
    val, test = _toy_sites(2, n=16, seed=5), _toy_sites(2, n=16, seed=6)
    cfg = TrainConfig(epochs=6, batch_size=8, pipeline="device")

    full = FederatedTrainer(cfg, model, host_mesh(2),
                            out_dir=str(tmp_path / "full"))
    res_full = full.fit(train, val, test, verbose=False)
    assert not _prefetch_threads()

    # rounds/epoch = 40//8 = 5 → kill crossing round 12 fires during epoch 3
    fp = FaultPlan(kill_at_round=12)
    killed = FederatedTrainer(cfg, model, host_mesh(2),
                              out_dir=str(tmp_path / "killed"), fault_plan=fp)
    with pytest.raises(Preempted):
        killed.fit(train, val, test, verbose=False)
    assert not _prefetch_threads(), "prefetch thread leaked across Preempted"

    resumed = FederatedTrainer(cfg, model, host_mesh(2),
                               out_dir=str(tmp_path / "killed"))
    res_res = resumed.fit(train, val, test, verbose=False, resume=True)
    assert not _prefetch_threads()
    assert len(res_res["epoch_losses"]) == len(res_full["epoch_losses"])
    np.testing.assert_allclose(res_res["epoch_losses"],
                               res_full["epoch_losses"], atol=1e-6)
    assert res_res["test_metrics"] == res_full["test_metrics"]


def test_prefetcher_builder_error_surfaces():
    """A crash on the builder thread must re-raise in the consumer, not
    vanish into the thread (and close() must still be clean)."""
    from dinunet_implementations_tpu.trainer.prefetch import EpochPlanPrefetcher

    def bad_build(epoch):
        raise RuntimeError(f"boom at {epoch}")

    pf = EpochPlanPrefetcher(bad_build, 1, 3)
    with pytest.raises(RuntimeError, match="boom"):
        pf.get(1)
    assert not _prefetch_threads()


def test_prefetcher_early_stop_close_joins():
    """Stopping mid-sequence (early stopping) leaves no thread behind even
    while the builder is blocked on the full queue."""
    from dinunet_implementations_tpu.trainer.prefetch import EpochPlanPrefetcher

    pf = EpochPlanPrefetcher(lambda e: e * 10, 1, 100)
    assert pf.get(1) == 10
    pf.close()
    pf.close()  # idempotent
    assert not _prefetch_threads()


# ---------------------------------------------------------------------------
# persistent compile cache (tentpole layer c)
# ---------------------------------------------------------------------------


def test_compile_cache_dir_populates(tmp_path):
    """cfg.compile_cache_dir wires jax's persistent compilation cache: a fit
    populates the directory so re-runs/fold re-fits skip XLA."""
    import os

    prev_dir = jax.config.jax_compilation_cache_dir
    prev_secs = jax.config.jax_persistent_cache_min_compile_time_secs
    prev_size = jax.config.jax_persistent_cache_min_entry_size_bytes
    cache = str(tmp_path / "xla-cache")
    try:
        cfg = TrainConfig(epochs=1, batch_size=8, compile_cache_dir=cache)
        tr = FederatedTrainer(cfg, MSANNet(in_size=6, hidden_sizes=(8,), out_size=2),
                              host_mesh(2))
        assert jax.config.jax_compilation_cache_dir == cache
        tr.fit(_toy_sites(2, seed=1), _toy_sites(2, n=16, seed=2),
               _toy_sites(2, n=16, seed=3), verbose=False)
        assert os.listdir(cache), "fit should populate the compilation cache"
    finally:
        jax.config.update("jax_compilation_cache_dir", prev_dir)
        jax.config.update("jax_persistent_cache_min_compile_time_secs", prev_secs)
        jax.config.update("jax_persistent_cache_min_entry_size_bytes", prev_size)


def test_cli_exposes_pipeline_and_compile_cache():
    from dinunet_implementations_tpu.runner.cli import build_parser

    args = build_parser().parse_args(
        ["--data-path", ".", "--pipeline", "host", "--compile-cache", "/tmp/cc"]
    )
    assert args.pipeline == "host"
    assert args.compile_cache == "/tmp/cc"


# ---------------------------------------------------------------------------
# sanitizer: one epoch compilation with the device pipeline + donation
# ---------------------------------------------------------------------------


def test_device_pipeline_one_epoch_compile_under_sanitizer(monkeypatch):
    """CompileGuard acceptance: the device pipeline with donation enabled
    still compiles exactly ONE epoch program per (engine, topology) fit."""
    from dinunet_implementations_tpu.checks.sanitize import (
        jit_cache_size,
        sanitized_fit,
    )

    monkeypatch.setenv("DINUNET_SANITIZE", "compile")
    cfg = TrainConfig(epochs=4, batch_size=8, pipeline="device",
                      donate_epoch_state=True)
    tr = FederatedTrainer(cfg, MSANNet(in_size=6, hidden_sizes=(16,), out_size=2),
                          host_mesh(2))
    if jit_cache_size(tr.epoch_fn) is None:
        pytest.skip("jax build exposes no jit cache counter")
    with sanitized_fit(tr, label="device-pipeline") as report:
        res = tr.fit(_toy_sites(2, seed=1), _toy_sites(2, n=16, seed=2),
                     _toy_sites(2, n=16, seed=3), verbose=False)
        report.note_result(res)
    assert jit_cache_size(tr.epoch_fn) == 1
