"""Aggregation-engine tests (SURVEY.md §4 implication (b): parity of each
engine against analytic expectations)."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax import shard_map
from jax.sharding import PartitionSpec as P

from dinunet_implementations_tpu.engines import (
    make_engine,
    available_engines,
    subspace_iteration,
)
from dinunet_implementations_tpu.parallel import SITE_AXIS, host_mesh

S = 4


def _tree(seed, scale=1.0):
    rng = np.random.default_rng(seed)
    return {
        "dense": {"kernel": jnp.asarray(rng.normal(size=(S, 12, 8)) * scale, jnp.float32),
                  "bias": jnp.asarray(rng.normal(size=(S, 8)) * scale, jnp.float32)},
        "head": {"kernel": jnp.asarray(rng.normal(size=(S, 8, 2)) * scale, jnp.float32)},
    }


def _weights():
    return jnp.asarray([3.0, 5.0, 2.0, 7.0])


def _pooled(tree, w):
    w = np.asarray(w)

    def f(g):
        g = np.asarray(g)
        return (g * w.reshape(-1, *([1] * (g.ndim - 1)))).sum(0) / w.sum()

    return jax.tree.map(f, tree)


def _run_engine(name, tree, w, **cfg):
    mesh = host_mesh(S)
    eng = make_engine(name, **cfg)
    state = eng.init(jax.tree.map(lambda g: g[0], tree))

    def fn(g, wv):
        g = jax.tree.map(lambda x: x[0], g)  # shard_map gives [1, ...] per site
        agg, st = eng.aggregate(g, state, wv[0], SITE_AXIS)
        return jax.tree.map(lambda x: x[None], agg)

    out = shard_map(
        fn, mesh=mesh,
        in_specs=(jax.tree.map(lambda _: P(SITE_AXIS), tree), P(SITE_AXIS)),
        out_specs=jax.tree.map(lambda _: P(SITE_AXIS), tree),
    )(tree, w)
    return jax.tree.map(lambda x: np.asarray(x[0]), out)


def test_registry():
    assert available_engines() == ["dSGD", "powerSGD", "rankDAD"]
    with pytest.raises(ValueError):
        make_engine("nope")


def test_dsgd_equals_pooled():
    tree, w = _tree(0), _weights()
    agg = _run_engine("dSGD", tree, w)
    expect = _pooled(tree, w)
    jax.tree.map(
        lambda a, e: np.testing.assert_allclose(a, e, rtol=1e-4, atol=1e-6), agg, expect
    )


@pytest.mark.slow
def test_rankdad_full_rank_equals_pooled():
    """With rank >= min(m, n) the power iteration is exact → rankDAD == dSGD."""
    tree, w = _tree(1), _weights()
    agg = _run_engine("rankDAD", tree, w, dad_reduction_rank=8, dad_num_pow_iters=25,
                      dad_tol=1e-9)
    expect = _pooled(tree, w)
    jax.tree.map(lambda a, e: np.testing.assert_allclose(a, e, atol=1e-4), agg, expect)


def test_rankdad_low_rank_compresses():
    """rank-1 compression of a rank-1 matrix is exact; of a full-rank matrix
    it is lossy but bounded by the spectral tail."""
    rng = np.random.default_rng(2)
    u = rng.normal(size=(S, 12, 1)).astype(np.float32)
    v = rng.normal(size=(S, 1, 8)).astype(np.float32)
    tree = {"k": jnp.asarray(u @ v)}
    w = _weights()
    agg = _run_engine("rankDAD", tree, w, dad_reduction_rank=1, dad_num_pow_iters=10,
                      dad_tol=1e-9)
    expect = _pooled(tree, w)
    np.testing.assert_allclose(agg["k"], expect["k"], atol=1e-4)


@pytest.mark.slow
def test_powersgd_error_feedback_converges():
    """Error-feedback property: a single compressed round is lossy, but the
    *time-averaged* updates converge to the true gradient — telescoping gives
    (1/T)·Σ Ĝ_t = Ḡ + (Ḡ − M_{T+1})/T with M bounded, so error ~ 1/T."""
    mesh = host_mesh(S)
    tree, w = _tree(3), _weights()
    eng = make_engine("powerSGD", dad_reduction_rank=2)
    expect = _pooled(tree, w)

    def multi_round(g, wv):
        g0 = jax.tree.map(lambda x: x[0], g)
        st = eng.init(g0)
        accs = []
        acc = jax.tree.map(jnp.zeros_like, g0)
        for t in range(24):
            agg, st = eng.aggregate(g0, st, wv[0], SITE_AXIS)
            acc = jax.tree.map(lambda a, x: a + x, acc, agg)
            if t + 1 in (4, 24):
                accs.append(jax.tree.map(lambda a: a / (t + 1), acc))
        return jax.tree.map(lambda x: x[None], {"t4": accs[0], "t24": accs[1]})

    spec_in = jax.tree.map(lambda _: P(SITE_AXIS), tree)
    out = shard_map(
        multi_round, mesh=mesh,
        in_specs=(spec_in, P(SITE_AXIS)),
        out_specs={"t4": spec_in, "t24": spec_in},
    )(tree, w)
    avg4 = jax.tree.map(lambda x: np.asarray(x[0]), out["t4"])
    avg24 = jax.tree.map(lambda x: np.asarray(x[0]), out["t24"])

    def err(a):
        return np.linalg.norm(a["dense"]["kernel"] - expect["dense"]["kernel"])

    assert err(avg24) < err(avg4)  # averaging converges
    np.testing.assert_allclose(
        avg24["dense"]["kernel"], expect["dense"]["kernel"], atol=0.25
    )
    # dense (1-D) path is exact every round
    np.testing.assert_allclose(avg24["dense"]["bias"], expect["dense"]["bias"], rtol=1e-4)


@pytest.mark.slow
def test_powersgd_bias_dense_exact():
    tree, w = _tree(4), _weights()
    agg = _run_engine("powerSGD", tree, w, dad_reduction_rank=2)
    expect = _pooled(tree, w)
    np.testing.assert_allclose(agg["dense"]["bias"], expect["dense"]["bias"], rtol=1e-5)


def test_subspace_iteration_exact_on_lowrank():
    rng = np.random.default_rng(5)
    G = (rng.normal(size=(20, 3)) @ rng.normal(size=(3, 15))).astype(np.float32)
    P, Q = subspace_iteration(jnp.asarray(G), 3, 20, 1e-10)
    np.testing.assert_allclose(np.asarray(P @ Q.T), G, atol=1e-3)


def test_subspace_iteration_explicit_key_used():
    """A caller-supplied PRNG key must actually seed the init Ω (advisor
    finding r3: it was silently discarded): factorization quality holds with
    an explicit key, and on a full-rank wide matrix stopped after a single
    iteration (where Ω still matters) the result differs from the default."""
    rng = np.random.default_rng(11)
    G = jnp.asarray(
        (rng.normal(size=(20, 3)) @ rng.normal(size=(3, 15))).astype(np.float32)
    )
    P, Q = subspace_iteration(G, 3, 20, 1e-10, key=jax.random.PRNGKey(123))
    np.testing.assert_allclose(np.asarray(P @ Q.T), np.asarray(G), atol=1e-3)
    Gf = jnp.asarray(rng.normal(size=(8, 24)).astype(np.float32))
    P_d, _ = subspace_iteration(Gf, 4, 1, 0.0)
    P_k, _ = subspace_iteration(Gf, 4, 1, 0.0, key=jax.random.PRNGKey(123))
    assert not np.allclose(np.asarray(P_d), np.asarray(P_k))


def test_subspace_iteration_tol_early_exit():
    """A huge tol stops after the first refinement (initial delta is inf, so
    exactly one iteration runs) — same result as num_iters=1, under jit."""
    rng = np.random.default_rng(6)
    G = jnp.asarray(rng.normal(size=(16, 10)).astype(np.float32))
    P1, Q1 = jax.jit(lambda g: subspace_iteration(g, 4, 100, 1e9))(G)
    P2, Q2 = subspace_iteration(G, 4, 1, 0.0)
    np.testing.assert_allclose(np.asarray(P1 @ Q1.T), np.asarray(P2 @ Q2.T), atol=1e-5)


@pytest.mark.slow
def test_engines_precision16_still_close():
    tree, w = _tree(7), _weights()
    for name in ("dSGD", "rankDAD", "powerSGD"):
        agg = _run_engine(name, tree, w, precision_bits="16", dad_reduction_rank=8,
                          dad_num_pow_iters=20, dad_tol=1e-9)
        expect = _pooled(tree, w)
        np.testing.assert_allclose(
            agg["dense"]["bias"], expect["dense"]["bias"], rtol=0.02, err_msg=name
        )


def test_subspace_iteration_decaying_spectrum_quality():
    """Review regression (r3): the TPU-friendly CholeskyQR2 orthonormalization
    must not collapse small-singular-value directions — on a 4-decade decaying
    spectrum, P stays orthonormal and the rank-r reconstruction matches the
    optimal truncation (the failure mode was a trace-relative Cholesky shift
    swamping every direction below ~1e-3 of sigma_1)."""
    rng = np.random.default_rng(42)
    m, n, r = 200, 80, 6
    spectrum = np.array([1.0, 0.5, 0.2, 0.1] + [1e-4] * 6, np.float32)
    U, _ = np.linalg.qr(rng.normal(size=(m, len(spectrum))))
    V, _ = np.linalg.qr(rng.normal(size=(n, len(spectrum))))
    G = jnp.asarray((U * spectrum) @ V.T, jnp.float32)

    P, Q = subspace_iteration(G, r, 20, 1e-9)
    orth_err = float(jnp.abs(P.T @ P - jnp.eye(r)).max())
    assert orth_err < 1e-4, f"P not orthonormal: {orth_err:.2e}"
    rec_err = float(jnp.linalg.norm(P @ Q.T - G) / jnp.linalg.norm(G))
    optimal = float(np.linalg.norm(spectrum[r:]) / np.linalg.norm(spectrum))
    assert rec_err < 1.5 * optimal + 1e-6, (
        f"reconstruction {rec_err:.3e} vs optimal truncation {optimal:.3e}"
    )


def test_subspace_iteration_rank_deficient_and_zero_safe():
    """NaN-safety: true gradient rank < r (bounded by batch size) and the
    all-zero leaf must both stay finite."""
    rng = np.random.default_rng(43)
    u = rng.normal(size=(50, 2)).astype(np.float32)
    v = rng.normal(size=(20, 2)).astype(np.float32)
    G_lowrank = jnp.asarray(u @ v.T)  # true rank 2 < r=6
    for G in (G_lowrank, jnp.zeros((50, 20), jnp.float32)):
        P, Q = subspace_iteration(G, 6, 5, 1e-3)
        assert bool(jnp.isfinite(P).all() and jnp.isfinite(Q).all())
    # the low-rank case must still reconstruct its true subspace
    P, Q = subspace_iteration(G_lowrank, 6, 20, 1e-9)
    rec = float(jnp.linalg.norm(P @ Q.T - G_lowrank) / jnp.linalg.norm(G_lowrank))
    assert rec < 1e-3, f"rank-2 reconstruction error {rec:.2e}"


def test_orthonormalize_zero_input_recovers():
    """Review regression (r3): orthonormalize(0) must return an ORTHONORMAL
    basis (as Householder QR does), not zeros — powerSGD warm-starts its q
    factor from P, and P=0 would freeze the leaf's gradient forever."""
    from dinunet_implementations_tpu.engines.lowrank import orthonormalize

    P = orthonormalize(jnp.zeros((12, 4), jnp.float32))
    np.testing.assert_allclose(
        np.asarray(P.T @ P), np.eye(4), atol=1e-5
    )


@pytest.mark.slow
def test_subspace_iteration_multi_matches_solo():
    """Lockstep groups must keep solo semantics: same subspace, same
    reconstruction, per-member trip counts."""
    import numpy as np

    from dinunet_implementations_tpu.engines.lowrank import (
        subspace_iteration,
        subspace_iteration_multi,
    )

    rng = np.random.default_rng(0)
    Gs = [
        jnp.asarray(rng.normal(size=(40, 24)).astype("float32")),
        jnp.asarray(rng.normal(size=(64, 16)).astype("float32")),
        jnp.asarray(rng.normal(size=(24, 48)).astype("float32")),
    ]
    multi = subspace_iteration_multi(Gs, 6, 8, 1e-4)
    for G, (Pm, Qm) in zip(Gs, multi):
        Ps, Qs_ = subspace_iteration(G, 6, 8, 1e-4)
        # same projector (bases may differ by rotation only)
        proj_m = Pm @ Pm.T
        proj_s = Ps @ Ps.T
        np.testing.assert_allclose(np.asarray(proj_m), np.asarray(proj_s),
                                   atol=1e-3)
        np.testing.assert_allclose(np.asarray(Pm @ Qm.T),
                                   np.asarray(Ps @ Qs_.T), atol=1e-3)
        # orthonormality of the lockstep result
        np.testing.assert_allclose(np.asarray(Pm.T @ Pm), np.eye(6),
                                   atol=1e-4)


def test_rankdad_warm_start_round1_identical_to_cold():
    """At init the warm-start state holds the cold-start default Ω draw
    (lowrank.default_omega), so the FIRST aggregate round is identical with
    warm starts on or off."""
    tree, w = _tree(8), _weights()
    kw = dict(dad_reduction_rank=3, dad_num_pow_iters=3, dad_tol=1e-3)
    warm = _run_engine("rankDAD", tree, w, dad_warm_start=True, **kw)
    cold = _run_engine("rankDAD", tree, w, dad_warm_start=False, **kw)
    jax.tree.map(
        lambda a, e: np.testing.assert_allclose(a, e, atol=1e-6), warm, cold
    )


def _run_engine_rounds(name, trees, w, **cfg):
    """Run several aggregate rounds threading the engine state; returns the
    per-round aggregates (list of trees)."""
    mesh = host_mesh(S)
    eng = make_engine(name, **cfg)
    state0 = eng.init(jax.tree.map(lambda g: g[0], trees[0]))

    def fn(w_all, *gs):
        st = state0
        outs = []
        for g in gs:
            g = jax.tree.map(lambda x: x[0], g)
            agg, st = eng.aggregate(g, st, w_all[0], SITE_AXIS)
            outs.append(jax.tree.map(lambda x: x[None], agg))
        return tuple(outs)

    spec = jax.tree.map(lambda _: P(SITE_AXIS), trees[0])
    outs = shard_map(
        fn, mesh=mesh,
        in_specs=(P(SITE_AXIS),) + (spec,) * len(trees),
        out_specs=(spec,) * len(trees),
    )(w, *trees)
    return [jax.tree.map(lambda x: np.asarray(x[0]), o) for o in outs]


def _gapped_tree(seed, m=12, n=8, r=4, gap=1e-3):
    """Per-site matrices with a CLEAN spectral gap after σ_r, so the rank-r
    subspace is well-conditioned and the power iteration actually converges
    within the iteration budget (a random Gaussian's σ_r ≈ σ_{r+1} makes the
    truncated subspace ill-conditioned — convergence rate (σ_{r+1}/σ_r)^k)."""
    rng = np.random.default_rng(seed)
    spec = np.array([1.0, 0.7, 0.5, 0.3] + [gap] * (min(m, n) - r), np.float32)
    mats = []
    for _ in range(S):
        U, _ = np.linalg.qr(rng.normal(size=(m, len(spec))))
        V, _ = np.linalg.qr(rng.normal(size=(n, len(spec))))
        mats.append((U * spec) @ V.T)
    return {"k": jnp.asarray(np.stack(mats).astype(np.float32))}


@pytest.mark.slow
def test_rankdad_warm_start_converged_parity_with_cold():
    """Acceptance (r6): at dad_num_pow_iters high enough to converge, a
    warm-started round-2 aggregate equals the cold-start round-2 aggregate —
    the warm Ω changes the ITERATE, not the converged subspace."""
    trees = [_gapped_tree(9), _gapped_tree(10)]
    w = _weights()
    kw = dict(dad_reduction_rank=4, dad_num_pow_iters=25, dad_tol=1e-9)
    warm = _run_engine_rounds("rankDAD", trees, w, dad_warm_start=True, **kw)
    cold = _run_engine_rounds("rankDAD", trees, w, dad_warm_start=False, **kw)
    for a, e in zip(warm, cold):
        jax.tree.map(
            lambda x, y: np.testing.assert_allclose(x, y, atol=1e-4), a, e
        )


def test_rankdad_warm_state_roundtrips_epoch_scan():
    """Acceptance (r6): the warm-start Ω must round-trip through the jitted
    epoch scan exactly like powerSGD's Q/error-feedback — per-site leaves,
    updated every round, finite — and a second epoch must consume the state
    the first one produced."""
    import jax.numpy as jnp

    from dinunet_implementations_tpu.checks import CompileGuard
    from dinunet_implementations_tpu.engines.lowrank import lowrank_rank_groups
    from dinunet_implementations_tpu.models import MSANNet
    from dinunet_implementations_tpu.trainer import (
        FederatedTask,
        init_train_state,
        make_optimizer,
        make_train_epoch_fn,
    )

    model = MSANNet(in_size=6, hidden_sizes=(8,), out_size=2)
    task = FederatedTask(model)
    eng = make_engine("rankDAD", dad_reduction_rank=3, dad_num_pow_iters=3,
                      dad_tol=1e-3)
    opt = make_optimizer("adam", 1e-2)
    rng = np.random.default_rng(0)
    Ssites = 3
    x = jnp.asarray(rng.normal(size=(Ssites, 4, 4, 6)).astype(np.float32))
    y = jnp.asarray((rng.random((Ssites, 4, 4)) > 0.5).astype(np.int32))
    w = jnp.ones((Ssites, 4, 4), jnp.float32)
    state = init_train_state(task, eng, opt, jax.random.PRNGKey(0), x[0, 0],
                             num_sites=Ssites)
    om0 = [np.asarray(o) for o in jax.tree.leaves(state.engine_state["omega"])]
    # per-site leading axis, like powerSGD's q/e
    assert all(o.shape[0] == Ssites for o in om0)
    epoch_fn = make_train_epoch_fn(task, eng, opt, mesh=None, local_iterations=2)
    # two rank classes (r=3 for [6, 8], r=2 for the [8, 2] head) share the one
    # loop, and the warm state a round hands on never changes the program
    assert [r for r, _ in lowrank_rank_groups(state.params, 3)[0]] == [2, 3]
    guard = CompileGuard({"epoch_fn": epoch_fn})
    state1, losses1 = epoch_fn(state, x, y, w)
    om1 = [np.asarray(o) for o in jax.tree.leaves(state1.engine_state["omega"])]
    assert all(np.isfinite(o).all() for o in om1)
    # the scan must actually UPDATE the warm state (Ω ← Q ≠ the random init)
    assert any(not np.allclose(a, b) for a, b in zip(om0, om1))
    state2, losses2 = epoch_fn(state1, x, y, w)
    assert np.isfinite(np.asarray(losses2)).all()
    assert guard.check(context="rankDAD, two rank classes") == {"epoch_fn": 1}


def test_rankdad_mixed_precision_iteration_close_to_f32():
    """precision_bits="16" runs the big power-iteration matmuls in bf16 with
    f32 accumulation — the aggregate must track the f32 engine within bf16
    noise (relative Frobenius error, not bitwise)."""
    tree, w = _tree(12), _weights()
    kw = dict(dad_reduction_rank=8, dad_num_pow_iters=20, dad_tol=1e-9)
    f32 = _run_engine("rankDAD", tree, w, precision_bits="32", **kw)
    b16 = _run_engine("rankDAD", tree, w, precision_bits="16", **kw)

    def rel(a, b):
        return np.linalg.norm(a - b) / max(np.linalg.norm(b), 1e-12)

    errs = jax.tree.leaves(jax.tree.map(rel, b16, f32))
    assert max(errs) < 0.05, f"bf16 iteration drifted: {errs}"


def test_subspace_iteration_grouped_mixed_ranks_matches_per_group():
    """One shared while_loop over several rank classes must reproduce the
    per-group results (the rank classes were previously separate while_loops,
    which XLA serializes — audit r6)."""
    from dinunet_implementations_tpu.engines.lowrank import (
        subspace_iteration_grouped,
        subspace_iteration_multi,
    )

    rng = np.random.default_rng(21)
    g1 = [jnp.asarray(rng.normal(size=(24, 16)).astype(np.float32)),
          jnp.asarray(rng.normal(size=(40, 12)).astype(np.float32))]
    g2 = [jnp.asarray(rng.normal(size=(30, 3)).astype(np.float32))]
    grouped = subspace_iteration_grouped(
        [(g1, 6, None), (g2, 6, None)], 8, 1e-4
    )
    solo1 = subspace_iteration_multi(g1, 6, 8, 1e-4)
    solo2 = subspace_iteration_multi(g2, 6, 8, 1e-4)
    for (Pg, Qg), (Ps, Qs_) in zip(grouped[0] + grouped[1], solo1 + solo2):
        np.testing.assert_allclose(
            np.asarray(Pg @ Qg.T), np.asarray(Ps @ Qs_.T), atol=1e-4
        )


def test_subspace_iteration_grouped_nothing_to_factorize_traces_no_loop():
    """A gradient tree of vectors only has no rank class: the grouped
    iteration returns at once (a ``while_loop`` cannot carry an empty tuple)
    and rankDAD's dense fallback carries the whole exchange."""
    from dinunet_implementations_tpu.engines.lowrank import (
        subspace_iteration_grouped,
    )

    assert subspace_iteration_grouped([], 5, 1e-3) == []
    traced = jax.make_jaxpr(lambda: subspace_iteration_grouped([], 5, 1e-3))()
    assert not traced.eqns
    tree = {"bias": _tree(11)["dense"]["bias"]}
    out = _run_engine("rankDAD", tree, _weights(), dad_reduction_rank=3)
    np.testing.assert_allclose(
        out["bias"], _pooled(tree, _weights())["bias"], atol=1e-6
    )


def test_rankdad_zero_gradient_round_recovers():
    """A zero gradient zeroes the stored Ω; the next round's CholeskyQR
    fallback re-seeds from canonical basis vectors, so the subspace must
    recover as soon as the gradient returns."""
    rng = np.random.default_rng(22)
    zero = {"k": jnp.zeros((S, 12, 8), jnp.float32)}
    live = {"k": jnp.asarray(rng.normal(size=(S, 12, 8)).astype(np.float32))}
    w = _weights()
    kw = dict(dad_reduction_rank=8, dad_num_pow_iters=25, dad_tol=1e-9)
    out_zero, out_live = _run_engine_rounds(
        "rankDAD", [zero, live], w, dad_warm_start=True, **kw
    )
    np.testing.assert_allclose(out_zero["k"], np.zeros((12, 8)), atol=1e-7)
    expect = _pooled(live, w)
    np.testing.assert_allclose(out_live["k"], expect["k"], atol=1e-4)


@pytest.mark.slow
def test_small_cholesky_and_inverse_match_lapack():
    """The TPU-path unrolled Cholesky / triangular inverse (used to avoid
    the per-matrix-cost LAPACK custom-calls) must match LAPACK numerics."""
    import numpy as np

    from dinunet_implementations_tpu.engines.lowrank import (
        _small_cholesky,
        _small_tril_inverse,
    )

    rng = np.random.default_rng(0)
    for shape in [(10, 10), (7, 4, 4), (32, 7, 10, 10)]:
        r = shape[-1]
        A = rng.normal(size=shape[:-2] + (r, r + 3)).astype("float32")
        G = jnp.asarray(
            A @ np.swapaxes(A, -1, -2) + 0.1 * np.eye(r, dtype="float32")
        )
        L = _small_cholesky(G)
        np.testing.assert_allclose(
            np.asarray(L), np.linalg.cholesky(np.asarray(G)),
            atol=3e-5, rtol=1e-4,
        )
        X = _small_tril_inverse(L)
        np.testing.assert_allclose(
            np.asarray(X @ L), np.broadcast_to(np.eye(r), G.shape), atol=1e-5
        )
