"""Multi-slice scale-out (r18): three-tier (slice, site, model) topology.

The tier-1 gates for the DCN tier, all on CPU emulation (the slice axis laid
over virtual devices — tests/conftest.py provisions 8):

- mesh construction + the num_slices=1 collapse (the S005-gated opt-out);
- the three-level reduction primitives: the FUSED form is bit-identical to
  the flat single-mesh reduce, the SPLIT form re-quantizes the per-slice
  partial through the DCN codec;
- sliced == unsliced trajectories BIT-EXACT site-for-site at equal total S,
  per engine, packed and unpacked, host and device pipelines;
- per-tier telemetry (dcn_bytes) and the engines' DCN wire models;
- the S005 slices-off identity / slices-on divergence pairs (the tier-1
  mirror of checks/semantic.py slices_identity_pairs);
- the DCN-tier semantic negative fixture: a model charging the dense
  per-device payload to the DCN tier trips S002;
- membership (slice, slot) placement for the daemon.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import PartitionSpec as P

from jax import shard_map
from dinunet_implementations_tpu.engines import make_engine
from dinunet_implementations_tpu.models import MSANNet
from dinunet_implementations_tpu.parallel.collectives import (
    PackedAxis,
    resolve_dcn_codec,
    three_level_psum,
)
from dinunet_implementations_tpu.parallel.mesh import (
    MODEL_AXIS,
    SITE_AXIS,
    SLICE_AXIS,
    pack_factor,
    packed_site_mesh,
    site_axis_of,
    slice_count,
    sliced_site_mesh,
)
from dinunet_implementations_tpu.trainer import (
    FederatedTask,
    init_train_state,
    make_optimizer,
    make_train_epoch_fn,
)

ENGINE_KW = {
    "dSGD": {},
    "rankDAD": dict(dad_reduction_rank=2, dad_num_pow_iters=2, dad_tol=1e-3),
    "powerSGD": dict(dad_reduction_rank=2),
}


# ---------------------------------------------------------------------------
# mesh construction
# ---------------------------------------------------------------------------


def test_sliced_mesh_shape_and_axes():
    mesh = sliced_site_mesh(2, 8, 2)  # 2 slices × 4 members, K=2
    assert mesh.axis_names == (SLICE_AXIS, SITE_AXIS, MODEL_AXIS)
    assert dict(mesh.shape) == {SLICE_AXIS: 2, SITE_AXIS: 4, MODEL_AXIS: 1}
    assert slice_count(mesh) == 2
    assert site_axis_of(mesh) == (SLICE_AXIS, SITE_AXIS)
    # the pack factor spans both tiers: 16 virtual sites over 2×4 members
    assert pack_factor(mesh, 16) == 2


def test_single_slice_collapses_to_legacy_mesh():
    """num_slices=1 is the opt-out: NO slice axis anywhere — the exact
    legacy (site, model) mesh, so the single-slice program is the legacy
    program by construction (the S005 slices-off gate double-checks the
    lowering)."""
    m1 = sliced_site_mesh(1, 8, 2)
    legacy = packed_site_mesh(8, 2)
    assert m1.axis_names == legacy.axis_names == (SITE_AXIS, MODEL_AXIS)
    assert slice_count(m1) == 1
    assert site_axis_of(m1) == SITE_AXIS


def test_sliced_mesh_validation():
    with pytest.raises(ValueError, match="num_slices"):
        sliced_site_mesh(0, 4)
    with pytest.raises(ValueError, match="must divide"):
        sliced_site_mesh(2, 3, 2)
    with pytest.raises(ValueError, match="need"):
        sliced_site_mesh(4, 16, 2)  # 4×8 members > 8 devices


def test_auto_site_mesh_resolves_slices():
    from dinunet_implementations_tpu import TrainConfig
    from dinunet_implementations_tpu.runner.fed_runner import auto_site_mesh

    mesh = auto_site_mesh(
        TrainConfig(num_slices=2, sites_per_device=2), num_sites=16
    )
    assert dict(mesh.shape) == {SLICE_AXIS: 2, SITE_AXIS: 4, MODEL_AXIS: 1}
    # num_slices=1 keeps the legacy resolution byte-for-byte
    legacy = auto_site_mesh(TrainConfig(num_slices=1), num_sites=8)
    assert SLICE_AXIS not in legacy.axis_names


# ---------------------------------------------------------------------------
# the three-level reduction primitives
# ---------------------------------------------------------------------------


def _psum_forms(vals, K):
    """(flat, fused, split-int8) reductions of the same [S, ...] payload."""
    S = vals.shape[0]
    m_flat = packed_site_mesh(S, K)
    m_sl = sliced_site_mesh(2, S // 2, K)
    flat_ax = PackedAxis(SITE_AXIS, K)
    sl_ax = PackedAxis(SITE_AXIS, K, slice_name=SLICE_AXIS)
    dcn = resolve_dcn_codec(dcn_wire_quant="int8")

    flat = jax.jit(shard_map(
        lambda v: three_level_psum(v, flat_ax),
        mesh=m_flat, in_specs=P(SITE_AXIS), out_specs=P(), check_vma=False,
    ))(vals)
    fused = jax.jit(shard_map(
        lambda v: three_level_psum(v, sl_ax),
        mesh=m_sl, in_specs=P((SLICE_AXIS, SITE_AXIS)), out_specs=P(),
        check_vma=False,
    ))(vals)
    split = jax.jit(shard_map(
        lambda v: three_level_psum(v, sl_ax, dcn_wire=dcn),
        mesh=m_sl, in_specs=P((SLICE_AXIS, SITE_AXIS)), out_specs=P(),
        check_vma=False,
    ))(vals)
    return flat, fused, split


def test_three_level_psum_fused_is_bit_exact_with_flat():
    rng = np.random.default_rng(0)
    vals = jnp.asarray(rng.normal(size=(16, 5)).astype(np.float32))
    flat, fused, split = _psum_forms(vals, K=2)
    # FUSED: one (slice, site) collective — same members, same reduction
    # order as the flat single-mesh psum, so bit-identical values
    np.testing.assert_array_equal(np.asarray(flat), np.asarray(fused))
    # SPLIT: the int8 re-quantization at the slice boundary moves the value
    # (that is the point — and the S005 divergence gate's reason)
    assert not np.array_equal(np.asarray(flat), np.asarray(split))
    np.testing.assert_allclose(
        np.asarray(split), np.asarray(flat), rtol=0.05, atol=0.05
    )


def test_three_level_psum_slice_live_gate_excludes_slice():
    """The r19 primitive contract: a dead slice's partial is gated out of
    the DCN reduce — fused AND split forms — and the result equals the
    reduce over the surviving slice's members alone (×1.0 exact, ×0 is
    exclusion). weighted_tree_sum renormalizes over survivors when the
    dead slice's weights are zeroed with it."""
    from dinunet_implementations_tpu.parallel.collectives import (
        site_weight_scale,
        weighted_tree_sum,
    )

    rng = np.random.default_rng(2)
    vals = jnp.asarray(rng.normal(size=(16, 5)).astype(np.float32))
    K = 2
    m_sl = sliced_site_mesh(2, 8, K)
    sl_ax = PackedAxis(SITE_AXIS, K, slice_name=SLICE_AXIS)
    dcn = resolve_dcn_codec(dcn_wire_quant="int8")

    def gated(dcn_wire):
        # slice 1 dead: its members' partials gate to zero
        def f(v):
            own = jax.lax.axis_index(SLICE_AXIS)
            live = jnp.where(own == 0, 1.0, 0.0)
            return three_level_psum(
                v, sl_ax, dcn_wire=dcn_wire, slice_live=live
            )

        return jax.jit(shard_map(
            f, mesh=m_sl, in_specs=P((SLICE_AXIS, SITE_AXIS)),
            out_specs=P(), check_vma=False,
        ))(vals)

    def masked_reduce(dcn_wire):
        # the equivalence baseline: the SAME collective with the dead
        # slice's member values zeroed outright (identical reduction tree,
        # so gating == exclusion must hold bit-for-bit)
        masked = jnp.concatenate([vals[:8], jnp.zeros_like(vals[8:])])
        return jax.jit(shard_map(
            lambda v: three_level_psum(v, sl_ax, dcn_wire=dcn_wire),
            mesh=m_sl, in_specs=P((SLICE_AXIS, SITE_AXIS)), out_specs=P(),
            check_vma=False,
        ))(masked)

    # the surviving slice owns the FIRST 8 virtual sites (slice-major)
    np.testing.assert_array_equal(
        np.asarray(gated(None)), np.asarray(masked_reduce(None))
    )
    np.testing.assert_allclose(
        np.asarray(gated(None)), np.asarray(vals[:8].sum(axis=0)),
        rtol=1e-6,
    )
    # split form: the survivor's partial still re-quantizes through the
    # codec; the dead slice contributes exactly zero to the slice psum
    np.testing.assert_array_equal(
        np.asarray(gated(dcn)), np.asarray(masked_reduce(dcn))
    )

    # weighted_tree_sum: zero the dead slice's weights alongside the gate
    # — the weighted mean renormalizes over the surviving slice only
    w = np.ones((16,), np.float32)
    w[8:] = 0.0  # slice 1's members carry no weight

    def wsum(v, wv):
        own = jax.lax.axis_index(SLICE_AXIS)
        live = jnp.where(own == 0, 1.0, 0.0)
        scale = site_weight_scale(wv, sl_ax)
        return weighted_tree_sum(
            {"g": v}, scale, sl_ax, dcn_wire=None, slice_live=live
        )["g"]

    out = jax.jit(shard_map(
        wsum, mesh=m_sl,
        in_specs=(P((SLICE_AXIS, SITE_AXIS)), P((SLICE_AXIS, SITE_AXIS))),
        out_specs=P(), check_vma=False,
    ))(vals, jnp.asarray(w))
    np.testing.assert_allclose(
        np.asarray(out), np.asarray(vals[:8].mean(axis=0)), rtol=1e-6
    )


def test_sliced_gather_matches_flat_order():
    from dinunet_implementations_tpu.parallel.collectives import (
        site_all_gather,
    )

    rng = np.random.default_rng(1)
    vals = jnp.asarray(rng.normal(size=(16, 3)).astype(np.float32))
    m_sl = sliced_site_mesh(2, 8, 2)
    sl_ax = PackedAxis(SITE_AXIS, 2, slice_name=SLICE_AXIS)
    out = jax.jit(shard_map(
        lambda v: site_all_gather(v, sl_ax),
        mesh=m_sl, in_specs=P((SLICE_AXIS, SITE_AXIS)), out_specs=P(),
        check_vma=False,
    ))(vals)
    # hierarchical site→slice gathers reassemble the slice-major global
    # order — exactly the data layout, bit-for-bit
    np.testing.assert_array_equal(np.asarray(out), np.asarray(vals))


# ---------------------------------------------------------------------------
# sliced == unsliced trajectories, bit-exact site-for-site
# ---------------------------------------------------------------------------


def _data(S, steps=2, B=4, F=6, seed=3):
    rng = np.random.default_rng(seed)
    x = jnp.asarray(rng.normal(size=(S, steps, B, F)).astype(np.float32))
    y = jnp.asarray((rng.random((S, steps, B)) > 0.5).astype(np.int32))
    w = jnp.ones((S, steps, B), jnp.float32)
    return x, y, w


def _build(engine_name, mesh, S, F=6, pipeline="host", engine_extra=None,
           **epoch_kw):
    model = MSANNet(in_size=F, hidden_sizes=(8,), out_size=2)
    task = FederatedTask(model)
    engine = make_engine(
        engine_name, **{**ENGINE_KW[engine_name], **(engine_extra or {})}
    )
    opt = make_optimizer("sgd", 1e-2)
    state = init_train_state(
        task, engine, opt, jax.random.PRNGKey(0),
        jnp.ones((4, F), jnp.float32), num_sites=S,
        **{k: epoch_kw[k] for k in ("telemetry",) if k in epoch_kw},
    )
    fn = make_train_epoch_fn(
        task, engine, opt, mesh, local_iterations=1, pipeline=pipeline,
        **epoch_kw,
    )
    return fn, state


@pytest.mark.parametrize("engine", ["dSGD", "rankDAD", "powerSGD"])
@pytest.mark.parametrize("pack", [1, 2])
def test_sliced_matches_unsliced_bit_exact(engine, pack):
    """Equal total S on the same device count: the sliced (2-slice) fused
    program must reproduce the flat single-mesh trajectories BIT-EXACTLY
    site-for-site — packed (K=2) and unpacked (K=1), every engine. The
    fused (slice, site) reduce IS the flat reduce (same members, same
    order); gathers reassemble the same global order; axis_index
    linearizes identically — so nothing in the math may move."""
    S = 8 * pack  # fills the 8-device set at this pack factor
    data = _data(S)
    fn_f, st = _build(engine, packed_site_mesh(S, pack), S)
    fn_s, st_s = _build(engine, sliced_site_mesh(2, S // 2, pack), S)
    s_f, s_s = st, st_s
    losses_f, losses_s = [], []
    for _ in range(2):
        s_f, l_f = fn_f(s_f, *data)
        s_s, l_s = fn_s(s_s, *data)
        losses_f.append(np.asarray(l_f))
        losses_s.append(np.asarray(l_s))
    np.testing.assert_array_equal(
        np.concatenate(losses_f), np.concatenate(losses_s)
    )
    jax.tree.map(
        lambda a, b: np.testing.assert_array_equal(
            np.asarray(a), np.asarray(b)
        ),
        s_f.params, s_s.params,
    )
    # per-VIRTUAL-site engine state survives slicing site-for-site
    jax.tree.map(
        lambda a, b: np.testing.assert_array_equal(
            np.asarray(a), np.asarray(b)
        ),
        s_f.engine_state, s_s.engine_state,
    )


def test_sliced_device_pipeline_matches_host():
    """The device-resident pipeline under slicing: on-device gather from
    the P((slice, site))-sharded inventory + three-tier aggregation must be
    bit-exact with the sliced host pipeline (one plan, two realizations —
    the r12 packing gate, extended a tier)."""
    S, N, B, steps, F = 8, 8, 4, 2, 6
    rng = np.random.default_rng(1)
    inv_x = jnp.asarray(rng.normal(size=(S, N, F)).astype(np.float32))
    inv_y = jnp.asarray((rng.random((S, N)) > 0.5).astype(np.int32))
    idx = jnp.asarray(rng.integers(0, N, size=(S, steps, B)).astype(np.int32))
    flat = np.asarray(idx).reshape(S, -1)
    x = jnp.asarray(
        np.take_along_axis(np.asarray(inv_x), flat[..., None], axis=1)
    ).reshape(S, steps, B, F)
    y = jnp.asarray(
        np.take_along_axis(np.asarray(inv_y), flat, axis=1)
    ).reshape(S, steps, B)
    w = jnp.ones((S, steps, B), jnp.float32)

    mesh = sliced_site_mesh(2, S // 2, 2)
    fn_d, st = _build("dSGD", mesh, S, pipeline="device")
    fn_h, _ = _build("dSGD", mesh, S, pipeline="host")
    s_d, l_d = fn_d(st, inv_x, inv_y, idx)
    s_h, l_h = fn_h(st, x, y, w)
    np.testing.assert_array_equal(np.asarray(l_d), np.asarray(l_h))
    jax.tree.map(
        lambda u, v: np.testing.assert_array_equal(
            np.asarray(u), np.asarray(v)
        ),
        s_d.params, s_h.params,
    )


@pytest.mark.parametrize("engine", ["dSGD", "rankDAD", "powerSGD"])
def test_dcn_codec_diverges_but_trains(engine):
    """The int8 DCN codec genuinely re-quantizes the inter-slice hop: the
    trajectory diverges from the fused f32 form (the S005 slices-dcn gate's
    value-level twin) yet stays finite and close — the quantization noise
    is per-payload-scaled, not structural."""
    S = 16
    data = _data(S)
    mesh = sliced_site_mesh(2, S // 2, 2)
    fn_n, st = _build(engine, mesh, S)
    fn_q, st_q = _build(
        engine, mesh, S, engine_extra={"dcn_wire_quant": "int8"}
    )
    s_n, l_n = fn_n(st, *data)
    s_q, l_q = fn_q(st_q, *data)
    assert np.isfinite(np.asarray(l_q)).all()
    assert not np.array_equal(np.asarray(l_n), np.asarray(l_q))
    np.testing.assert_allclose(
        np.asarray(l_q), np.asarray(l_n), atol=5e-2
    )


def test_dead_virtual_site_masks_under_slicing():
    """Chaos composes with the slice tier: a liveness mask addressed at
    VIRTUAL site granularity skips exactly that site on a sliced mesh,
    bit-identically to the flat mesh run."""
    S = 8
    data = _data(S)
    live = np.ones((S, 2), np.float32)
    live[3, :] = 0.0  # site 3 (slice 0's block) dead both rounds
    live[6, 0] = 0.0  # site 6 (slice 1's block) drops round 0
    live = jnp.asarray(live)
    fn_f, st = _build("dSGD", packed_site_mesh(S, 1), S)
    fn_s, st_s = _build("dSGD", sliced_site_mesh(2, S // 2, 1), S)
    s_f, l_f = fn_f(st, *data, live)
    s_s, l_s = fn_s(st_s, *data, live)
    np.testing.assert_array_equal(np.asarray(l_f), np.asarray(l_s))
    np.testing.assert_array_equal(
        np.asarray(s_f.health["skips"]), np.asarray(s_s.health["skips"])
    )
    assert np.asarray(s_s.health["skips"])[3] == 2


def test_buffered_async_sliced_matches_unsliced():
    """The fourth aggregation semantics (r13 staleness-bounded buffered
    async) threads the slice tier through the same packed_apply primitives:
    sliced == unsliced stays bit-exact under churn + buffering."""
    S = 8
    data = _data(S)
    live = np.ones((S, 2), np.float32)
    live[2, 0] = 0.0  # straggler: round 0 missed, buffer ages
    live = jnp.asarray(live)
    kw = dict(staleness_bound=2, staleness_decay=0.5)
    fn_f, st = _build("dSGD", packed_site_mesh(S, 1), S, **kw)
    fn_s, st_s = _build("dSGD", sliced_site_mesh(2, S // 2, 1), S, **kw)
    s_f, l_f = fn_f(st, *data, live)
    s_s, l_s = fn_s(st_s, *data, live)
    np.testing.assert_array_equal(np.asarray(l_f), np.asarray(l_s))
    np.testing.assert_array_equal(
        np.asarray(s_f.buffers["age"]), np.asarray(s_s.buffers["age"])
    )
    jax.tree.map(
        lambda a, b: np.testing.assert_array_equal(
            np.asarray(a), np.asarray(b)
        ),
        s_f.params, s_s.params,
    )


def test_overlapped_rounds_sliced_matches_unsliced():
    """The overlapped-rounds form (r14 stash apply) under slicing: the
    double-buffered pipelined update reproduces the flat mesh bit-for-bit —
    the stash collectives are the same packed_apply wire, a tier deeper."""
    S = 8
    data = _data(S)
    kw = dict(overlap_rounds=True)
    fn_f, st = _build("dSGD", packed_site_mesh(S, 1), S, **kw)
    fn_s, st_s = _build("dSGD", sliced_site_mesh(2, S // 2, 1), S, **kw)
    s_f, l_f = fn_f(st, *data)
    s_s, l_s = fn_s(st_s, *data)
    # first round applies the empty stash: NaN loss on both, identically
    np.testing.assert_array_equal(np.asarray(l_f), np.asarray(l_s))
    jax.tree.map(
        lambda a, b: np.testing.assert_array_equal(
            np.asarray(a), np.asarray(b)
        ),
        s_f.params, s_s.params,
    )


# ---------------------------------------------------------------------------
# per-tier telemetry + wire models
# ---------------------------------------------------------------------------


def test_telemetry_splits_ici_and_dcn_bytes():
    from dinunet_implementations_tpu.telemetry.metrics import (
        dcn_bytes_of,
        payload_bytes_of,
        telemetry_summary,
    )

    S = 8
    data = _data(S)
    mesh = sliced_site_mesh(2, S // 2, 2)
    fn, st = _build(
        "dSGD", mesh, S, engine_extra={"dcn_wire_quant": "int8"},
        telemetry=True,
    )
    engine = make_engine("dSGD", dcn_wire_quant="int8")
    s, _ = fn(st, *data)
    t = jax.tree.map(np.asarray, s.telemetry)
    rounds = int(t["rounds"][0])
    ici = payload_bytes_of(engine, s.params, pack=2)
    dcn = dcn_bytes_of(
        engine, s.params, pack=2, sites_per_slice=4, slices=2
    )
    assert rounds == 2
    np.testing.assert_allclose(t["payload_bytes"], ici * rounds)
    np.testing.assert_allclose(t["dcn_bytes"], dcn * rounds)
    # the int8 DCN hop is exactly ¼ of the f32 partial (flat codec vector)
    f32 = dcn_bytes_of(
        make_engine("dSGD"), s.params, pack=2, sites_per_slice=4, slices=2
    )
    assert dcn * 4 == f32
    summary = telemetry_summary(s.telemetry)
    assert summary["dcn_bytes_per_round"] == pytest.approx(dcn)
    # single-slice runs report 0 DCN bytes (no inter-slice hop exists)
    fn1, st1 = _build("dSGD", packed_site_mesh(S, 2), S, telemetry=True)
    s1, _ = fn1(st1, *data)
    assert float(np.asarray(s1.telemetry["dcn_bytes"])[0]) == 0.0


@pytest.mark.parametrize("engine", ["dSGD", "rankDAD", "powerSGD"])
def test_dcn_wire_models_consistent(engine):
    """Engine.dcn_bytes == Σ Engine.dcn_wire_shapes at several (pack,
    sites_per_slice) corners, with and without a DCN codec — the model-
    consistency half of the semantic DCN proof, cheap enough for tier-1."""
    import math

    model = MSANNet(in_size=6, hidden_sizes=(8,), out_size=2)
    task = FederatedTask(model)
    params, _ = task.init_variables(
        jax.random.PRNGKey(0), jnp.ones((4, 6), jnp.float32)
    )
    for extra in ({}, {"dcn_wire_quant": "int8"}, {"wire_quant": "int8"}):
        eng = make_engine(engine, **{**ENGINE_KW[engine], **extra})
        for pack, sps in ((1, 2), (2, 4), (4, 16)):
            shapes = eng.dcn_wire_shapes(params, pack=pack,
                                         sites_per_slice=sps)
            total = sum(math.prod(s) * d.itemsize for s, d in shapes)
            assert total == eng.dcn_bytes(params, pack=pack,
                                          sites_per_slice=sps)
            assert total > 0


def test_sliced_semantic_cells_clean_and_negative_fixture_trips():
    """The DCN-tier semantic rules: a real sliced int8 cell verifies clean,
    and the negative fixture — an engine whose model charges the DENSE
    PER-DEVICE payload to the DCN tier instead of the re-quantized
    per-slice partial — trips S002 (the model-vs-traced mismatch the rule
    exists to catch)."""
    import dataclasses

    from dinunet_implementations_tpu.checks import semantic as sem

    cell = sem.TraceCell("dSGD", "sliced", "host", dcn_quant="int8")
    prog = sem.trace_cell(cell)
    stats_shapes = tuple(
        tuple(leaf.shape)
        for leaf in jax.tree_util.tree_leaves(prog.state.batch_stats)
    )
    clean = sem.check_dcn_wire(
        prog.audit.collectives, prog.engine, prog.state.params,
        prog.block, prog.sites_per_slice, prog.path,
        stats_shapes=stats_shapes, slices=prog.slices,
    )
    assert clean == []
    # the negative fixture: dense per-device f32 leaves charged to DCN
    import numpy as np_

    broken = dataclasses.replace(
        prog.engine,
        dcn_wire_shapes=lambda g, pack=1, sites_per_slice=1: [
            (tuple(leaf.shape), np_.dtype(np_.float32))
            for leaf in jax.tree.leaves(g)
        ],
        dcn_bytes=lambda g, pack=1, sites_per_slice=1: sum(
            leaf.size * 4 for leaf in jax.tree.leaves(g)
        ),
    )
    fs = sem.check_dcn_wire(
        prog.audit.collectives, broken, prog.state.params,
        prog.block, prog.sites_per_slice, prog.path,
        stats_shapes=stats_shapes, slices=prog.slices,
    )
    assert any(f.rule == "S002" for f in fs)
    assert any("OVERCOUNTS" in f.message or "UNDERCOUNTS" in f.message
               for f in fs)


def test_s005_slices_identity_pairs():
    """Tier-1 mirror of the CLI S005 gate: slices-off must be lowering-
    identical to the legacy program, slices-on and the DCN codec must
    genuinely diverge."""
    from dinunet_implementations_tpu.checks import semantic as sem

    assert sem.check_lowering_identity(sem.slices_identity_pairs()) == []


# ---------------------------------------------------------------------------
# membership: logical sites → (slice, slot)
# ---------------------------------------------------------------------------


def test_membership_slice_placement():
    from dinunet_implementations_tpu.robustness.membership import (
        MembershipTable,
    )

    t = MembershipTable(8)
    for s in ("a", "b", "c", "d", "e"):
        t, _, _ = t.join(s)
    # dense-first assignment: slots 0..4 → slices [0, 0, 0, 0, 1] at n=2
    assert t.placements(2) == {
        "a": (0, 0), "b": (0, 1), "c": (0, 2), "d": (0, 3), "e": (1, 4),
    }
    assert t.slice_occupancy(2) == [4, 1]
    # a slice leaving the run is its band's sites leaving — same transitions
    for s in ("a", "b", "c", "d"):
        t, _ = t.leave(s)
    assert t.slice_occupancy(2) == [0, 1]
    # rebalance over 2 granules pulls occupancy even across the slices
    t2, _, _ = t.join("f")
    table, moves = t2.rebalance(2)
    assert table.slice_occupancy(2) == [1, 1]
    assert t.slice_of(0, 1) == 0  # single-slice: everything is slice 0
    with pytest.raises(Exception, match="divide"):
        t.slice_of(0, 3)


def test_dcn_worker_cli_parsing():
    from dinunet_implementations_tpu.runner.dcn_worker import (
        _config_overrides,
        _parse,
        _slice_of,
    )

    args = _parse([
        "--data-path", "/x", "--slices", "2", "--num-processes", "2",
        "--process-id", "1", "--coordinator", "h:1", "--set",
        "wire_quant=int8", "--set", "staleness_bound=2",
    ])
    assert args.slices == 2 and args.process_id == 1
    ov = _config_overrides(args.overrides)
    assert ov == {"wire_quant": "int8", "staleness_bound": 2}
    # r19 supervision flags parse, with sane defaults
    args = _parse([
        "--data-path", "/x", "--supervise", "--slices", "2",
        "--num-processes", "4", "--faults", '{"kill_slice_at":[[1,2]]}',
        "--resume", "--heartbeat-timeout-s", "15",
    ])
    assert args.supervise and args.resume
    assert args.heartbeat_timeout_s == 15 and args.max_restarts == 2
    # processes are contiguous slice granules
    assert [_slice_of(r, 4, 2) for r in range(4)] == [0, 0, 1, 1]
    assert _slice_of(3, 4, 1) == 0


# ---------------------------------------------------------------------------
# slice elasticity (r19): liveness mask, quorum holds, supervision-free
# equivalence gates
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("engine", ["dSGD", "rankDAD", "powerSGD"])
@pytest.mark.parametrize("pack", [1, 2])
def test_slice_drop_matches_site_exclusion_bit_exact(engine, pack):
    """THE r19 equivalence gate: a round with slice j masked (the
    [num_slices, rounds] slice-liveness input) produces params, losses AND
    per-site engine state BIT-IDENTICAL to the same program fed a
    site-level mask excluding slice j's sites outright — per engine,
    packed (K=2) and unpacked. ×1.0 is exact and ×0 is exclusion, so
    nothing in the math may move."""
    S = 8 * pack
    data = _data(S)
    mesh = sliced_site_mesh(2, S // 2, pack)
    # slice 1 dead in round 0, everyone back in round 1
    slice_live = jnp.asarray([[1.0, 1.0], [0.0, 1.0]], jnp.float32)
    site_live = np.ones((S, 2), np.float32)
    site_live[S // 2:, 0] = 0.0  # slice 1's slot band (slice-major layout)
    site_live = jnp.asarray(site_live)
    fn, st = _build(engine, mesh, S)
    s_sl, l_sl = fn(st, *data, None, None, slice_live)
    s_site, l_site = fn(st, *data, site_live, None, None)
    np.testing.assert_array_equal(np.asarray(l_sl), np.asarray(l_site))
    for tree_sl, tree_site in (
        (s_sl.params, s_site.params),
        (s_sl.engine_state, s_site.engine_state),
        (s_sl.health, s_site.health),
    ):
        jax.tree.map(
            lambda a, b: np.testing.assert_array_equal(
                np.asarray(a), np.asarray(b)
            ),
            tree_sl, tree_site,
        )


def test_slice_drop_matches_flat_mesh_site_exclusion():
    """The same dead slice, compared across TOPOLOGIES: the sliced run
    with slice 1 masked equals the FLAT single-mesh run with slice 1's
    site band masked — slice elasticity composes with the r18
    sliced==unsliced bit-exactness, so the whole chain is anchored to the
    legacy program."""
    S = 8
    data = _data(S)
    slice_live = jnp.asarray([[1.0, 1.0], [0.0, 1.0]], jnp.float32)
    site_live = np.ones((S, 2), np.float32)
    site_live[S // 2:, 0] = 0.0
    site_live = jnp.asarray(site_live)
    fn_s, st_s = _build("dSGD", sliced_site_mesh(2, S // 2, 1), S)
    fn_f, st_f = _build("dSGD", packed_site_mesh(S, 1), S)
    s_s, l_s = fn_s(st_s, *data, None, None, slice_live)
    s_f, l_f = fn_f(st_f, *data, site_live)
    np.testing.assert_array_equal(np.asarray(l_s), np.asarray(l_f))
    jax.tree.map(
        lambda a, b: np.testing.assert_array_equal(
            np.asarray(a), np.asarray(b)
        ),
        s_s.params, s_f.params,
    )


def test_slice_quorum_holds_round():
    """min_slices=2 with one slice dead: the round HOLDS — params /
    optimizer / engine state / health / telemetry all frozen, NaN loss,
    held_rounds counted — and the next round (quorum restored) trains
    normally. min_slices=1 on the same mask trains the surviving slice
    instead (diverging params): the floor is what declines the round, not
    the mask."""
    S = 8
    data = _data(S)
    mesh = sliced_site_mesh(2, S // 2, 1)
    slice_live = jnp.asarray([[1.0, 1.0], [0.0, 1.0]], jnp.float32)
    fn_q, st_q = _build("dSGD", mesh, S, telemetry=True, min_slices=2)
    s_h, l_h = fn_q(st_q, *data, None, None, slice_live)
    losses = np.asarray(l_h)
    assert np.isnan(losses[0]) and np.isfinite(losses[1])
    t = jax.tree.map(np.asarray, s_h.telemetry)
    assert t["held_rounds"][0] == 1 and t["rounds"][0] == 1
    # a held round is nobody's fault: health counters frozen, no skips
    assert np.asarray(s_h.health["skips"]).sum() == 0
    # the no-hold arm trains round 0 on the surviving slice — different
    # trajectory (and round 0 has a real loss)
    fn_1, st_1 = _build("dSGD", mesh, S, telemetry=True, min_slices=1)
    s_1, l_1 = fn_1(st_1, *data, None, None, slice_live)
    assert np.isfinite(np.asarray(l_1)).all()
    assert not np.array_equal(
        np.asarray(jax.tree.leaves(s_h.params)[0]),
        np.asarray(jax.tree.leaves(s_1.params)[0]),
    )


def test_slice_churn_never_retraces():
    """CompileGuard (r19): a drop → hold → rejoin scenario across epochs
    — three different slice-fault masks through one epoch fn — compiles
    the epoch exactly once; churn reaches the program only through traced
    inputs."""
    from dinunet_implementations_tpu.checks.sanitize import jit_cache_size

    S = 8
    data = _data(S)
    mesh = sliced_site_mesh(2, S // 2, 1)
    fn, st = _build("dSGD", mesh, S, min_slices=2)
    masks = (
        [[1.0, 1.0], [0.0, 1.0]],  # drop: slice 1 out round 0
        [[0.0, 0.0], [1.0, 1.0]],  # hold: slice 0 out both rounds
        [[1.0, 1.0], [1.0, 1.0]],  # rejoin: everyone back
    )
    # two warmup calls reach the steady-state layout (the freshly-built
    # state is uncommitted; its first output is mesh-committed — the known
    # one-time layout recompile the trainer's _place_state avoids)
    s, _ = fn(st, *data, None, None, jnp.asarray(masks[0], jnp.float32))
    s, _ = fn(s, *data, None, None, jnp.asarray(masks[0], jnp.float32))
    n0 = jit_cache_size(fn)
    for m in masks[1:]:
        s, _ = fn(s, *data, None, None, jnp.asarray(m, jnp.float32))
    # the drop → hold → rejoin chain adds ZERO compiles
    assert jit_cache_size(fn) == n0


def test_slice_mask_rejected_on_unsliced_topologies():
    S = 8
    data = _data(S)
    mask = jnp.ones((2, 2), jnp.float32)
    fn_flat, st_flat = _build("dSGD", packed_site_mesh(S, 1), S)
    with pytest.raises(ValueError, match="unsliced"):
        fn_flat(st_flat, *data, None, None, mask)
    fn_vmap, st_vmap = _build("dSGD", None, S)
    with pytest.raises(ValueError, match="unsliced"):
        fn_vmap(st_vmap, *data, None, None, mask)
    # and a quorum floor without a sliced mesh is a config error
    with pytest.raises(ValueError, match="min_slices"):
        _build("dSGD", packed_site_mesh(S, 1), S, min_slices=2)
    # a wrong slice-row count is a shape error, not a silently-clamped
    # own-row gather (XLA would clamp the out-of-bounds index)
    fn_s, st_s = _build("dSGD", sliced_site_mesh(2, S // 2, 1), S)
    with pytest.raises(ValueError, match="slice rows"):
        fn_s(st_s, *data, None, None, jnp.ones((3, 2), jnp.float32))


def test_slice_fault_plan_through_trainer(tmp_path):
    """End to end through FederatedTrainer (device pipeline): a FaultPlan
    with slice windows renders into the traced mask, the run completes
    with one epoch compile, and the slice-dead rounds show in the site
    health exactly like the equivalent site-level plan."""
    from dinunet_implementations_tpu import TrainConfig
    from dinunet_implementations_tpu.checks.sanitize import jit_cache_size
    from dinunet_implementations_tpu.data.api import SiteArrays
    from dinunet_implementations_tpu.robustness.faults import FaultPlan
    from dinunet_implementations_tpu.trainer import FederatedTrainer

    S = 8
    rng = np.random.default_rng(0)
    sites = []
    for s in range(S):
        y = (rng.random(8) > 0.5).astype(np.int64)
        x = rng.normal(size=(8, 6)).astype(np.float32) + y[:, None]
        sites.append(SiteArrays(x, y, np.arange(8)))
    cfg = TrainConfig(
        task_id="FS-Classification", batch_size=4, epochs=2,
        validation_epochs=1, patience=10, num_slices=2,
    )
    model = MSANNet(in_size=6, hidden_sizes=(8,), out_size=2)
    mesh = sliced_site_mesh(2, S // 2, 1)
    plan = FaultPlan(slice_drop_at=[[1, 0, 0]])
    tr = FederatedTrainer(cfg, model, mesh=mesh, fault_plan=plan)
    res = tr.fit(sites, sites, sites, verbose=False)
    assert jit_cache_size(tr.epoch_fn) == 1
    # slice 1's band skipped round 0; slice 0's sites never skipped
    skips = res["site_health"]["site_skipped_rounds"]
    assert all(v >= 1 for v in skips[S // 2:])
    assert all(v == 0 for v in skips[: S // 2])
