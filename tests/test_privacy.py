"""Privacy plane (r20, privacy/) — DP-SGD + RDP accounting, secure-
aggregation masked wires, and personalized per-site heads.

The load-bearing claims, each pinned here:

- the RDP accountant's math (closed forms, monotonicity, serialization)
  and the trainer-surfaced ε matching a from-scratch host recompute;
- DP noise counter-keyed by (seed, site, round) — chunk/resume/packing-
  independent — and the clip actually bounding what ships;
- checkpoint/resume continuing ε accumulation EXACTLY (no double count,
  no reset) and the ε budget stopping a fit cleanly;
- masked == unmasked (pads vs the pads-zeroed verification arm)
  BIT-EXACT, at full liveness AND with dead sites, packed and unpacked —
  the integer-pad cancellation argument as a test vector;
- the documented composition refusals (int8/fp8 codecs, gather-mode
  robust reducers, DCN codecs, the low-rank engines);
- personalized head rows training per site, staying out of the wire,
  checkpoint round-tripping, and rejoin-reset zeroing the head but not
  the cohort ε;
- the r20 jaxprlint fixtures: a mask psum leaking outside the rounds scan
  trips S001, and a dp-on program claiming the dp-off identity trips
  S005's divergence gate.
"""

import json
import math
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from dinunet_implementations_tpu import TrainConfig
from dinunet_implementations_tpu.engines import make_engine
from dinunet_implementations_tpu.models import MSANNet
from dinunet_implementations_tpu.privacy import (
    RdpAccountant,
    make_dp_fn,
    sampling_fraction,
)
from dinunet_implementations_tpu.privacy.accounting import (
    rdp_sampled_gaussian,
)
from dinunet_implementations_tpu.privacy.secure_agg import fraction_bits
from dinunet_implementations_tpu.trainer.steps import (
    FederatedTask,
    init_train_state,
    make_eval_fn,
    make_optimizer,
    make_train_epoch_fn,
)

S, STEPS, B, D = 4, 2, 4, 6


def _corner():
    model = MSANNet(in_size=D, hidden_sizes=(8,), out_size=2)
    task = FederatedTask(model)
    opt = make_optimizer("adam", 1e-2)
    rng = np.random.default_rng(0)
    x = jnp.asarray(rng.normal(size=(S, STEPS, B, D)).astype(np.float32))
    y = jnp.asarray((rng.random((S, STEPS, B)) > 0.5).astype(np.int32))
    w = jnp.ones((S, STEPS, B), jnp.float32)
    return task, opt, (x, y, w)


def _state(task, engine, opt, personalize=()):
    return init_train_state(
        task, engine, opt, jax.random.PRNGKey(0),
        jnp.ones((B, D), jnp.float32), num_sites=S, personalize=personalize,
    )


def _leaves_equal(a, b):
    la, lb = jax.tree.leaves(a), jax.tree.leaves(b)
    return all(
        np.array_equal(np.asarray(u), np.asarray(v)) for u, v in zip(la, lb)
    )


# ---------------------------------------------------------------------------
# RDP accountant
# ---------------------------------------------------------------------------


def test_rdp_closed_form_at_full_sampling():
    """q == 1 is the plain Gaussian mechanism: RDP_α = α/(2σ²)."""
    for sigma in (0.5, 1.0, 4.0):
        for order in (2, 8, 64):
            assert rdp_sampled_gaussian(1.0, sigma, order) == pytest.approx(
                order / (2 * sigma**2)
            )


def test_rdp_subsampling_amplifies_and_noise_helps():
    """Smaller q and larger σ both shrink the per-step RDP; σ = 0 is ∞."""
    assert rdp_sampled_gaussian(0.1, 1.0, 8) < rdp_sampled_gaussian(1.0, 1.0, 8)
    assert rdp_sampled_gaussian(0.5, 2.0, 8) < rdp_sampled_gaussian(0.5, 0.5, 8)
    assert math.isinf(rdp_sampled_gaussian(0.5, 0.0, 8))
    assert rdp_sampled_gaussian(0.0, 1.0, 8) == 0.0


def test_accountant_epsilon_monotone_and_serializes():
    acct = RdpAccountant()
    assert acct.epsilon(1e-5) == (0.0, None)
    eps = []
    for _ in range(5):
        acct.step(0.8, 0.5, steps=3)
        eps.append(acct.epsilon(1e-5)[0])
    assert all(b > a for a, b in zip(eps, eps[1:])), eps
    # JSON round trip restores the exact ledger (the resume contract)
    clone = RdpAccountant.from_json(json.loads(json.dumps(acct.to_json())))
    assert clone.epsilon(1e-5) == acct.epsilon(1e-5)
    assert clone.steps == acct.steps
    # a noiseless ledger reports infinity, never a fake finite ε
    none = RdpAccountant().step(0.0, 0.5, steps=3)
    assert math.isinf(none.epsilon(1e-5)[0])


def test_sampling_fraction_takes_the_smallest_site():
    assert sampling_fraction(8, 1, [64, 16, 32]) == pytest.approx(0.5)
    assert sampling_fraction(8, 2, [16]) == 1.0  # clamped
    assert sampling_fraction(8, 1, []) == 0.0
    assert sampling_fraction(8, 1, [0, 32]) == pytest.approx(0.25)


# ---------------------------------------------------------------------------
# DP-SGD transform
# ---------------------------------------------------------------------------


def test_dp_noise_is_counter_keyed():
    """Noise depends only on (seed, site, round, leaf) — the chunk/resume/
    packing-independence contract (the AttackPlan-noise pattern)."""
    dp = make_dp_fn(1.0, 0.5, dp_seed=7)
    g = {"a": jnp.zeros((3, 2)), "b": jnp.zeros((4,))}
    out1 = jax.jit(lambda: dp(g, jnp.int32(5), jnp.int32(2)))()
    out2 = jax.jit(lambda: dp(g, jnp.int32(5), jnp.int32(2)))()
    assert _leaves_equal(out1, out2)
    other_round = jax.jit(lambda: dp(g, jnp.int32(6), jnp.int32(2)))()
    assert not _leaves_equal(out1, other_round)
    other_site = jax.jit(lambda: dp(g, jnp.int32(5), jnp.int32(3)))()
    assert not _leaves_equal(out1, other_site)


def test_dp_clip_bounds_the_shipped_gradient():
    dp = make_dp_fn(0.5, 0.0)  # clip only
    g = {"a": jnp.full((10,), 100.0), "b": jnp.full((5,), -40.0)}
    out = dp(g, jnp.int32(0), jnp.int32(0))
    norm = math.sqrt(sum(
        float(jnp.sum(jnp.square(v))) for v in jax.tree.leaves(out)
    ))
    assert norm == pytest.approx(0.5, rel=1e-5)
    # a small gradient passes through untouched (scale clamps at 1)
    small = {"a": jnp.full((10,), 1e-3), "b": jnp.full((5,), 1e-3)}
    assert _leaves_equal(dp(small, jnp.int32(0), jnp.int32(0)), small)


def test_dp_noise_without_clip_is_rejected():
    from dinunet_implementations_tpu.privacy import dp_enabled

    with pytest.raises(ValueError, match="dp_clip"):
        make_dp_fn(0.0, 0.5)
    with pytest.raises(ValueError, match="dp_clip"):
        dp_enabled(0.0, 0.5)
    assert not dp_enabled(0.0, 0.0)
    assert dp_enabled(1.0, 0.0)  # clip-only is a valid (ε = ∞) transform


def test_dp_packed_matches_unpacked():
    """K=2 on a 2-device mesh trains like K=1 on a 4-device mesh under DP —
    the noise keys on GLOBAL site ids, so packing never reshuffles the
    mechanism (the test_packing equivalence policy: allclose at 1e-6)."""
    from dinunet_implementations_tpu.parallel.mesh import host_mesh

    task, opt, data = _corner()
    engine = make_engine("dSGD")
    kw = dict(dp_clip=1.0, dp_noise_multiplier=0.5)

    def run(mesh):
        st = _state(task, engine, opt)
        fn = make_train_epoch_fn(task, engine, opt, mesh=mesh, **kw)
        s, losses = fn(st, *data)
        return s, np.asarray(losses)

    s2, l2 = run(host_mesh(2))
    s1, l1 = run(host_mesh(4))
    np.testing.assert_allclose(l2, l1, atol=1e-6)
    jax.tree.map(
        lambda a, b: np.testing.assert_allclose(a, b, atol=1e-6),
        s2.params, s1.params,
    )


# ---------------------------------------------------------------------------
# trainer-level ε surfaces, recompute, budget, resume
# ---------------------------------------------------------------------------


def _fs_runner(tmp_path, **cfg_kw):
    from dinunet_implementations_tpu.data.demo import make_fs_demo_tree
    from dinunet_implementations_tpu.runner import FedRunner

    root = str(tmp_path / "tree")
    if not os.path.isdir(root):
        make_fs_demo_tree(root, n_sites=2, subjects=16)
    kw = dict(
        epochs=2, patience=10, batch_size=8, telemetry="on",
        dp_clip=1.0, dp_noise_multiplier=0.8,
        # donation off: an earlier test may have enabled the GLOBAL XLA
        # compile cache, and this jaxlib corrupts the heap when a
        # cache-DESERIALIZED executable runs with donated buffers (the
        # documented serving/engine.py warmup bug) — these tests re-fit
        # identical programs, the exact cache-hit recipe
        donate_epoch_state=False,
    )
    kw.update(cfg_kw)
    cfg = TrainConfig(**kw)
    return FedRunner(cfg, data_path=root,
                     out_dir=str(tmp_path / "out")), cfg, root


def test_fit_epsilon_matches_host_recompute(tmp_path):
    """Acceptance: the trainer-reported ε equals a from-scratch accountant
    recompute over the same (σ, q, rounds) trajectory — and the per-epoch
    trail in metrics.jsonl is monotone."""
    runner, cfg, root = _fs_runner(tmp_path)
    res = runner.run(verbose=False)[0]
    tdir = os.path.join(str(tmp_path / "out"), "telemetry", "fold_0")
    from dinunet_implementations_tpu.telemetry.sink import load_metrics

    rows = load_metrics(os.path.join(tdir, "metrics.jsonl"))
    epochs = [r for r in rows if r["kind"] == "epoch"]
    eps_trail = [r["dp_epsilon"] for r in epochs]
    assert all(e is not None for e in eps_trail)
    assert all(b > a for a, b in zip(eps_trail, eps_trail[1:]))
    man = json.load(open(os.path.join(tdir, "manifest.json")))
    assert man["privacy"]["dp_noise_multiplier"] == cfg.dp_noise_multiplier
    # from-scratch recompute: q from the real per-site train-split sizes
    # the runner's fold built (the conservative smallest-site corner) and
    # the per-epoch round counts the telemetry recorded
    from dinunet_implementations_tpu.runner.fed_runner import (
        FedRunner as FR,
        load_site_splits,
    )

    runner2 = FR(cfg, data_path=root, out_dir=str(tmp_path / "out2"))
    fold0 = load_site_splits(
        runner2.cfg, runner2.site_dirs, runner2.site_cfgs
    )[0]
    q = sampling_fraction(
        cfg.batch_size, cfg.local_iterations,
        [len(s) for s in fold0["train"]],
    )
    rounds = [r["rounds"] for r in epochs]
    per_epoch = [b - a for a, b in zip([0] + rounds[:-1], rounds)]
    from dinunet_implementations_tpu.privacy import (
        effective_noise_multiplier,
    )

    acct = RdpAccountant()
    for n_rounds in per_epoch:
        # the trainer composes at σ/2 — clip-of-mean sensitivity is 2C
        acct.step(
            effective_noise_multiplier(cfg.dp_noise_multiplier), q,
            steps=n_rounds,
        )
    expected, _ = acct.epsilon(cfg.dp_delta)
    assert res["dp_epsilon"] == pytest.approx(expected, rel=1e-12)
    assert res["dp_delta"] == cfg.dp_delta
    # logs.json carries the same figures (the notebook-facing surface)
    logs = json.load(open(os.path.join(
        str(tmp_path / "out"), "remote", "simulatorRun", cfg.task_id,
        "fold_0", "logs.json",
    )))
    assert logs["dp_epsilon"] == pytest.approx(res["dp_epsilon"])


def test_epsilon_budget_stops_fit_cleanly(tmp_path):
    """A tiny ε budget stops training after the first epoch that exhausts
    it — checkpointed, event recorded, best-state test still produced."""
    runner, cfg, _ = _fs_runner(
        tmp_path, epochs=8, dp_epsilon_budget=1e-3,
    )
    res = runner.run(verbose=False)[0]
    assert res["stopped_epoch"] == 1  # the very first epoch exhausts 1e-3
    assert res["dp_epsilon"] >= 1e-3
    assert "test_metrics" in res
    from dinunet_implementations_tpu.telemetry.sink import load_metrics

    rows = load_metrics(os.path.join(
        str(tmp_path / "out"), "telemetry", "fold_0", "metrics.jsonl"
    ))
    events = [r for r in rows if r.get("name") == "dp-budget"]
    assert events and events[0]["epsilon"] >= 1e-3
    # the budget stop landed AFTER the rotating checkpoint: resumable
    assert os.path.exists(os.path.join(
        str(tmp_path / "out"), "remote", "simulatorRun", cfg.task_id,
        "fold_0", "checkpoint_latest.msgpack",
    ))


def test_resume_continues_epsilon_exactly(tmp_path):
    """Checkpoint/resume of the accountant: 2 epochs + resume to 4 equals
    an uninterrupted 4-epoch run's ε EXACTLY (no double count, no reset)."""
    from dinunet_implementations_tpu.data.demo import make_fs_demo_tree
    from dinunet_implementations_tpu.runner import FedRunner

    root = str(tmp_path / "tree")
    make_fs_demo_tree(root, n_sites=2, subjects=16)
    # donation off — see _fs_runner: three identical fits in one process
    # are the documented deserialized-executable + donated-buffer segfault
    # recipe on this jaxlib
    kw = dict(patience=10, batch_size=8, telemetry="off",
              dp_clip=1.0, dp_noise_multiplier=0.8,
              donate_epoch_state=False)
    full = FedRunner(
        TrainConfig(epochs=4, **kw), data_path=root,
        out_dir=str(tmp_path / "full"),
    ).run(verbose=False)[0]
    out2 = str(tmp_path / "split")
    FedRunner(
        TrainConfig(epochs=2, **kw), data_path=root, out_dir=out2,
    ).run(verbose=False)
    resumed = FedRunner(
        TrainConfig(epochs=4, **kw), data_path=root, out_dir=out2,
    ).run(resume=True, verbose=False)[0]
    assert resumed["dp_epsilon"] == pytest.approx(
        full["dp_epsilon"], rel=1e-12
    )


# ---------------------------------------------------------------------------
# secure aggregation
# ---------------------------------------------------------------------------


def test_fraction_bits_bounds_the_int32_sum():
    assert fraction_bits(2) == 29
    assert fraction_bits(512) == 21
    for s in (2, 7, 512, 4096):
        assert s * 2 ** fraction_bits(s) <= 2**31


def test_masked_equals_nopads_bitexact_full_liveness():
    """THE secure-agg claim: real pads vs the pads-zeroed verification arm
    are BIT-IDENTICAL — integer cancellation is exact in any reduction
    order."""
    task, opt, data = _corner()
    outs = {}
    for mode in ("mask", "mask-nopads"):
        engine = make_engine("dSGD", secure_agg=mode)
        st = _state(task, engine, opt)
        fn = make_train_epoch_fn(task, engine, opt, mesh=None)
        s, losses = fn(st, *data)
        outs[mode] = (s.params, np.asarray(losses))
    assert _leaves_equal(outs["mask"][0], outs["mask-nopads"][0])
    np.testing.assert_array_equal(outs["mask"][1], outs["mask-nopads"][1])


def test_masked_equals_nopads_bitexact_with_dead_sites():
    """Dropout handling: pads gate per PAIR on the round's liveness, so
    cancellation stays exact over the SURVIVING cohort — bit-identical
    params with a site dead mid-epoch, packed and unpacked."""
    from dinunet_implementations_tpu.parallel.mesh import host_mesh

    task, opt, data = _corner()
    live = np.ones((S, STEPS), np.float32)
    live[1, :] = 0.0  # site 1 never arrives
    live[3, 1] = 0.0  # site 3 drops for round 1
    live = jnp.asarray(live)
    for mesh in (None, host_mesh(2)):
        outs = {}
        for mode in ("mask", "mask-nopads"):
            engine = make_engine("dSGD", secure_agg=mode)
            st = _state(task, engine, opt)
            fn = make_train_epoch_fn(task, engine, opt, mesh=mesh)
            s, _ = fn(st, *data, live)
            outs[mode] = s.params
        assert _leaves_equal(outs["mask"], outs["mask-nopads"]), (
            f"mask ≠ nopads on mesh={mesh}"
        )


def test_secure_agg_packed_matches_unpacked_bitexact():
    """Integer aggregation is reduction-order-proof: K=2 and K=1 packings
    produce BIT-IDENTICAL trajectories (stronger than the float engines'
    allclose equivalence)."""
    from dinunet_implementations_tpu.parallel.mesh import host_mesh

    task, opt, data = _corner()
    engine = make_engine("dSGD", secure_agg="mask")
    outs = []
    for mesh in (host_mesh(2), host_mesh(4)):
        st = _state(task, engine, opt)
        fn = make_train_epoch_fn(task, engine, opt, mesh=mesh)
        s, _ = fn(st, *data)
        outs.append(s.params)
    assert _leaves_equal(*outs)


def test_secure_agg_composition_refusals():
    """The documented refusal matrix: float codec grids and gather-based
    robust reducers shred/defeat the pads; the low-rank engines have no
    dense psum wire to mask. bf16 + norm_clip compose."""
    for wq in ("int8", "fp8"):
        with pytest.raises(ValueError, match="wire_quant"):
            make_engine("dSGD", secure_agg="mask", wire_quant=wq)
    with pytest.raises(ValueError, match="DCN"):
        make_engine("dSGD", secure_agg="mask", dcn_wire_quant="int8")
    with pytest.raises(ValueError, match="robust_agg"):
        make_engine("dSGD", secure_agg="mask", robust_agg="trimmed_mean")
    for eng in ("rankDAD", "powerSGD"):
        with pytest.raises(ValueError, match="dSGD"):
            make_engine(eng, secure_agg="mask")
    # allowed compositions construct fine
    make_engine("dSGD", secure_agg="mask", wire_quant="bf16")
    make_engine("dSGD", secure_agg="mask", precision_bits="16")
    make_engine("dSGD", secure_agg="mask", robust_agg="norm_clip")
    with pytest.raises(ValueError, match="secure_agg"):
        make_engine("dSGD", secure_agg="bogus")


def test_secure_agg_wire_model_is_int32_dense():
    """Wire bytes unchanged: the int32 grid matches the f32 dense wire
    byte-for-byte (+ the [pack] liveness gather), K-invariant — the model
    S002 proves on the +secureagg cells."""
    from dinunet_implementations_tpu.telemetry.metrics import (
        modeled_wire_shapes,
        payload_bytes_of,
    )

    params = {"k": jnp.zeros((6, 8)), "b": jnp.zeros((8,))}
    legacy = make_engine("dSGD")
    masked = make_engine("dSGD", secure_agg="mask")
    for pack in (1, 4):
        base = payload_bytes_of(legacy, params, pack=pack)
        sec = payload_bytes_of(masked, params, pack=pack)
        assert sec == base + 4 * pack  # + the liveness-vector gather
        shapes = modeled_wire_shapes(masked, params, pack=pack)
        total = sum(
            int(np.prod(s)) * d.itemsize for s, d in shapes
        )
        assert total == sec
        assert {str(d) for s, d in shapes if s != (pack,)} == {"int32"}


def test_secure_agg_requires_round_counter():
    """The masks are keyed per (pair, round): an aggregate call without the
    traced round counter (a legacy caller) fails loudly instead of
    silently re-using one round's pads forever."""
    engine = make_engine("dSGD", secure_agg="mask")
    g = {"k": jnp.ones((2, 3))}
    with pytest.raises(ValueError, match="round counter"):
        engine.aggregate(g, {}, jnp.float32(1.0), "site")


# ---------------------------------------------------------------------------
# personalized heads
# ---------------------------------------------------------------------------

PAT = ("fc_out",)


def test_personalized_heads_train_per_site_and_stay_off_the_wire():
    task, opt, data = _corner()
    engine = make_engine("dSGD")
    st0 = _state(task, engine, opt, personalize=PAT)
    fn = make_train_epoch_fn(task, engine, opt, mesh=None, personalize=PAT)
    st1, _ = fn(st0, *data)

    def pkey(kp):
        return "/".join(str(getattr(k, "key", getattr(k, "idx", k)))
                        for k in kp)

    before = jax.tree_util.tree_flatten_with_path(st0.params)[0]
    after = jax.tree_util.tree_flatten_with_path(st1.params)[0]
    for (kp, b), (_, a) in zip(before, after):
        if "fc_out" in pkey(kp):
            # the global head copy is FROZEN (zero aggregate → zero Adam)
            np.testing.assert_array_equal(np.asarray(b), np.asarray(a))
        else:
            assert not np.array_equal(np.asarray(b), np.asarray(a))
    # per-site head rows genuinely diverged (sites hold different data)
    rows = np.asarray(jax.tree.leaves(st1.personal["params"])[0])
    assert rows.shape[0] == S
    assert not np.allclose(rows[0], rows[1])
    # engine state was initialized on the SHARED subtree only: the wire
    # model (what ships) must not charge the head leaves
    from dinunet_implementations_tpu.privacy.personalize import (
        head_leaf_paths,
        strip_tree,
    )
    from dinunet_implementations_tpu.telemetry.metrics import (
        payload_bytes_of,
    )

    paths = head_leaf_paths(st0.params, PAT)
    shared = strip_tree(st0.params, paths, keep_head=False)
    assert payload_bytes_of(engine, shared) < payload_bytes_of(
        engine, st0.params
    )


def test_personalized_eval_uses_each_sites_head():
    task, opt, data = _corner()
    engine = make_engine("dSGD")
    st = _state(task, engine, opt, personalize=PAT)
    # give site 0 a deliberately different head row — SCALED, not shifted
    # (adding a constant to every fc_out column would move both logits
    # equally and leave the softmax untouched)
    personal = st.personal
    bumped = jax.tree.map(
        lambda leaf: leaf.at[0].set(leaf[0] * 3.0), personal["params"]
    )
    st = st.replace(personal={**personal, "params": bumped})
    eval_fn = make_eval_fn(task, mesh=None, personalize=PAT)
    x = jnp.broadcast_to(data[0][0:1], data[0].shape)  # same inputs per site
    probs, _, _ = eval_fn(st, x, data[1], data[2])
    probs = np.asarray(probs)
    assert not np.allclose(probs[0], probs[1])  # site 0's head differs
    np.testing.assert_allclose(probs[1], probs[2], atol=1e-6)


def test_personalized_checkpoint_roundtrip_and_resume(tmp_path):
    from dinunet_implementations_tpu.trainer.checkpoint import (
        load_checkpoint,
        save_checkpoint,
    )

    task, opt, data = _corner()
    engine = make_engine("dSGD")
    st = _state(task, engine, opt, personalize=PAT)
    fn = make_train_epoch_fn(task, engine, opt, mesh=None, personalize=PAT)
    st1, _ = fn(st, *data)
    path = str(tmp_path / "ck.msgpack")
    save_checkpoint(path, st1)
    restored = load_checkpoint(path, _state(task, engine, opt,
                                            personalize=PAT))
    assert _leaves_equal(restored.personal, st1.personal)
    # a legacy (unpersonalized) checkpoint restores into a personalized run
    # with fresh common-model rows, never a failed resume
    st_plain = _state(task, engine, opt)
    save_checkpoint(str(tmp_path / "legacy.msgpack"), st_plain)
    fresh = load_checkpoint(
        str(tmp_path / "legacy.msgpack"),
        _state(task, engine, opt, personalize=PAT),
    )
    assert fresh.personal is not None


def test_rejoin_resets_head_row_but_not_cohort_epsilon():
    """The membership contract (satellite): reset_slot_state zeroes the
    rejoining slot's head back to the CURRENT global copy and resets its
    optimizer row — while the cohort's privacy ledger (trainer-side, a
    property of the mechanism's history) is untouched."""
    from dinunet_implementations_tpu.privacy.personalize import (
        head_leaf_paths,
        strip_tree,
    )
    from dinunet_implementations_tpu.robustness.membership import (
        reset_slot_state,
    )

    task, opt, data = _corner()
    engine = make_engine("dSGD")
    st = _state(task, engine, opt, personalize=PAT)
    fn = make_train_epoch_fn(task, engine, opt, mesh=None, personalize=PAT)
    st1, _ = fn(st, *data)
    acct = RdpAccountant().step(0.8, 0.5, steps=4)
    ledger_before = json.dumps(acct.to_json())
    st2 = reset_slot_state(st1, slot=1, engine=engine)
    paths = head_leaf_paths(st1.params, PAT)
    fresh_head = strip_tree(st1.params, paths, keep_head=True)
    for leaf, fresh in zip(
        jax.tree.leaves(st2.personal["params"]),
        jax.tree.leaves(fresh_head),
    ):
        # slot 1 back to the (frozen) global head copy
        np.testing.assert_array_equal(np.asarray(leaf)[1], np.asarray(fresh))
    # the other slots keep their personalized rows
    for a, b in zip(
        jax.tree.leaves(st2.personal["params"]),
        jax.tree.leaves(st1.personal["params"]),
    ):
        np.testing.assert_array_equal(np.asarray(a)[0], np.asarray(b)[0])
    # the cohort ε is not slot state: the ledger is untouched by rejoin
    assert json.dumps(acct.to_json()) == ledger_before


@pytest.mark.parametrize("engine_name,kw", [
    ("rankDAD", dict(dad_reduction_rank=2, dad_num_pow_iters=2)),
    ("powerSGD", dict(dad_reduction_rank=2)),
])
def test_rejoin_reset_works_with_stateful_engines(engine_name, kw):
    """Review regression: under personalization, engine state lives on the
    SHARED subtree — reset_slot_state must re-init the rejoining slot's
    engine row from that subtree too, or rankDAD/powerSGD rejoins crash on
    a tree-structure mismatch (dSGD's empty engine state hid this)."""
    from dinunet_implementations_tpu.robustness.membership import (
        reset_slot_state,
    )

    task, opt, data = _corner()
    engine = make_engine(engine_name, **kw)
    st = _state(task, engine, opt, personalize=PAT)
    fn = make_train_epoch_fn(task, engine, opt, mesh=None, personalize=PAT)
    st1, _ = fn(st, *data)
    st2 = reset_slot_state(st1, slot=1, engine=engine)
    # slot 1's engine row is fresh; the others survive
    for leaf1, leaf2 in zip(
        jax.tree.leaves(st1.engine_state), jax.tree.leaves(st2.engine_state)
    ):
        np.testing.assert_array_equal(
            np.asarray(leaf1)[0], np.asarray(leaf2)[0]
        )


def test_personalize_pattern_validation():
    from dinunet_implementations_tpu.privacy.personalize import (
        head_leaf_paths,
    )

    task, opt, _ = _corner()
    engine = make_engine("dSGD")
    st = _state(task, engine, opt)
    with pytest.raises(ValueError, match="no parameter leaf"):
        head_leaf_paths(st.params, ("nonexistent_layer",))
    with pytest.raises(ValueError, match="EVERY parameter"):
        head_leaf_paths(st.params, ("kernel", "bias", "scale", "mean", "var"))


# ---------------------------------------------------------------------------
# jaxprlint negative fixtures (satellite)
# ---------------------------------------------------------------------------


def test_mask_psum_outside_rounds_scan_trips_s001():
    """A secure-agg implementation whose pad material crosses the site axis
    OUTSIDE the rounds scan is per-epoch stray communication — S001 must
    flag it (the r20 mirror of the training rule's outside-scan case)."""
    from dinunet_implementations_tpu.checks.semantic import (
        audit_jaxpr,
        check_collective_axes,
    )
    from jax import shard_map
    from dinunet_implementations_tpu.parallel.mesh import SITE_AXIS, host_mesh
    from jax.sharding import PartitionSpec as P

    mesh = host_mesh(2)

    def leaky(x):
        # the pad psum OUTSIDE any scan — the leak under test
        pad = jax.lax.bitcast_convert_type(
            jax.random.bits(jax.random.PRNGKey(0), x.shape, jnp.uint32),
            jnp.int32,
        )
        tot = jax.lax.psum(x.astype(jnp.int32) + pad, SITE_AXIS)

        def body(c, _):
            return c + jax.lax.psum(x, SITE_AXIS), None

        out, _ = jax.lax.scan(body, jnp.zeros_like(x), None, length=2)
        return out + tot.astype(x.dtype)

    fn = lambda x: shard_map(  # noqa: E731
        leaky, mesh=mesh, in_specs=P(SITE_AXIS), out_specs=P(SITE_AXIS),
        check_vma=False,
    )(x)
    jaxpr = jax.make_jaxpr(fn)(jnp.ones((2, 3), jnp.float32))
    findings = check_collective_axes(
        audit_jaxpr(jaxpr).collectives, "trace://fixture/secureagg-leak"
    )
    assert any("OUTSIDE" in f.message for f in findings), findings


def test_dp_on_claiming_dp_off_identity_trips_s005():
    """A dp-on program claiming the dp-off wire/program model must trip the
    S005 divergence gate — and the real dp-on pair must genuinely
    diverge (the inverse gate that keeps 'the mechanism ran' honest)."""
    from dinunet_implementations_tpu.checks.semantic import (
        TraceCell,
        check_lowering_identity,
        identity_text_fn,
    )

    text = identity_text_fn(TraceCell("dSGD", "vmap", "host"))
    base = text()
    dp_text = text(dp_clip=1.0, dp_noise_multiplier=0.5)
    # the lie: "my dp-on program is the dp-off program" → finding
    lied = check_lowering_identity(
        [("dp-claims-off", base, dp_text, True)]
    )
    assert lied and lied[0].rule == "S005"
    # the truth: dp-on genuinely diverges → no finding
    assert check_lowering_identity(
        [("dp-on", base, dp_text, False)]
    ) == []


# ---------------------------------------------------------------------------
# manifest + schema surfaces
# ---------------------------------------------------------------------------


def test_privacy_manifest_is_required_and_verbatim():
    from dinunet_implementations_tpu.telemetry.sink import (
        build_manifest,
        validate_manifest,
    )

    cfg = TrainConfig()
    man = build_manifest(cfg)
    assert validate_manifest(man) == []
    assert man["privacy"] is None  # plane off → explicit null
    stripped = {k: v for k, v in man.items() if k != "privacy"}
    assert any("privacy" in p for p in validate_manifest(stripped))
    on = build_manifest(cfg.replace(
        dp_clip=1.0, dp_noise_multiplier=0.5, secure_agg="mask",
        personalize=("fc_out",),
    ))
    assert on["privacy"] == {
        "dp_clip": 1.0, "dp_noise_multiplier": 0.5, "dp_seed": 0,
        "dp_delta": 1e-5, "dp_epsilon_budget": 0.0, "secure_agg": "mask",
        "secure_agg_seed": 0, "personalize": ["fc_out"],
    }


def test_epoch_row_schema_requires_dp_epsilon():
    from dinunet_implementations_tpu.telemetry.sink import (
        ROW_REQUIRED,
        validate_metrics_rows,
    )

    assert "dp_epsilon" in ROW_REQUIRED["epoch"]
    row = {k: 0 for k in ROW_REQUIRED["epoch"] if k != "dp_epsilon"}
    row["kind"] = "epoch"
    assert any("dp_epsilon" in p for p in validate_metrics_rows([row]))
