"""The CholeskyQR that rankDAD and powerSGD share returns finite factors for
every input (ISSUE 27).

On the accepted tree rankDAD training turned to NaN all at once after a few
hundred rounds: once the gradients are small and numerically rank-deficient a
pivot of the second CholeskyQR round goes negative in float32, the factor is
NaN and nothing stands after the engine. Held here: (a) the factorization on
hard inputs, on both backends' paths, bit for bit the unguarded one where
that one holds; (b) rankDAD through ``FederatedTrainer`` at the benchmark's
rehearsal size past the round the accepted tree died at; (c) powerSGD
likewise.
"""

import hashlib
import time
import types

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from dinunet_implementations_tpu.engines import lowrank

M, R = 64, 10


def _hard_input(name):
    rng = np.random.default_rng(27)
    base = (rng.standard_normal((M, 3)).astype(np.float32)
            @ rng.standard_normal((3, R)).astype(np.float32))
    noise = np.float32(1e-7) * rng.standard_normal((M, R)).astype(np.float32)
    if name == "rank3_plus_noise":
        return base + noise
    if name == "rank3_scaled_1e-12":
        return (base + noise) * np.float32(1e-12)
    if name == "two_equal_columns":
        y = rng.standard_normal((M, R)).astype(np.float32)
        y[:, 4] = y[:, 1]
        return y
    if name == "all_zero":
        return np.zeros((M, R), np.float32)
    assert name == "well_conditioned"
    return rng.standard_normal((M, R)).astype(np.float32)


HARD = ["rank3_plus_noise", "rank3_scaled_1e-12", "two_equal_columns",
        "all_zero", "well_conditioned"]
# sha256 of the accepted tree's ``_cholqr_multi([well_conditioned])[0][0]``
# bytes on the LAPACK path (commit f03c505, this container's jax 0.9.0 CPU)
PARENT_WELL_CONDITIONED_SHA = "ee3783b48d1528bd5d3df7952e727f35f50c65fdf9f43756a38315b29433fb00"


@pytest.fixture(params=["lapack", "unrolled"])
def path(request, monkeypatch):
    """Both backends' factorizations: LAPACK (what a CPU runs) and the
    unrolled Cholesky the TPU runs, reached here by answering the module's
    backend question."""
    if request.param == "unrolled":
        monkeypatch.setattr(lowrank.jax, "default_backend", lambda: "tpu")
    return request.param


def _unguarded(y):
    """CholeskyQR2 as the accepted tree ran it: two rounds, no check."""
    q1, _ = lowrank._cholqr_once_multi([y], 1e-6)
    q2, _ = lowrank._cholqr_once_multi(q1, 1e-7)
    return np.asarray(q2[0])


@pytest.mark.parametrize("name", HARD)
def test_cholqr_is_finite_and_orthonormal_on_the_span(name, path):
    y = _hard_input(name)
    qs, colnorms = lowrank._cholqr_multi([jnp.asarray(y)])
    q = np.asarray(qs[0], np.float64)
    assert np.isfinite(q).all() and np.isfinite(np.asarray(colnorms[0])).all()
    # no direction is stretched, and the well-defined ones are orthonormal
    sv = np.linalg.svd(q, compute_uv=False)
    assert sv.max() <= 1 + 1e-4
    rank = {"rank3_plus_noise": 3, "rank3_scaled_1e-12": 3,
            "two_equal_columns": R - 1, "all_zero": R,
            "well_conditioned": R}[name]
    assert (sv > 1 - 1e-3).sum() >= rank
    # span(Y) lies in span(Q)
    nc = np.linalg.norm(y.astype(np.float64), axis=0)
    yn = y.astype(np.float64)[:, nc > 0] / nc[nc > 0]
    if yn.size:
        coef = np.linalg.lstsq(q, yn, rcond=None)[0]
        assert np.abs(q @ coef - yn).max() <= 1e-4
    if name in ("well_conditioned", "all_zero"):
        np.testing.assert_allclose(q.T @ q, np.eye(R), atol=1e-5)
    # where the unguarded factorization holds, the result is bit for bit its
    unguarded = _unguarded(jnp.asarray(y))
    if np.isfinite(unguarded).all():
        np.testing.assert_array_equal(np.asarray(qs[0]), unguarded)
    if name == "well_conditioned" and path == "lapack":
        sha = hashlib.sha256(np.asarray(qs[0]).tobytes()).hexdigest()
        assert sha == PARENT_WELL_CONDITIONED_SHA


@pytest.mark.parametrize("from_column", [0, 6])
def test_a_second_round_that_breaks_down_keeps_the_first_rounds_columns(
        from_column, path, monkeypatch):
    """The horizon itself, made to happen: the second round's Cholesky
    returns NaN from a column on (LAPACK refuses the whole matrix, the
    unrolled one takes the root of a negative pivot). Those columns of Q are
    the first round's, normalized; the others are untouched."""
    y = jnp.asarray(_hard_input("well_conditioned"))
    q1, _ = lowrank._cholqr_once_multi([y], 1e-6)
    q2, _ = lowrank._cholqr_once_multi(q1, 1e-7)
    calls = []

    def second_call_breaks(real):
        def factor(g):
            calls.append(1)
            out = real(g)
            if len(calls) == 2:
                out = out.at[..., from_column:, from_column:].set(jnp.nan)
            return out
        return factor

    if path == "lapack":
        monkeypatch.setattr(lowrank.jnp.linalg, "cholesky",
                            second_call_breaks(jnp.linalg.cholesky))
    else:
        monkeypatch.setattr(lowrank, "_small_cholesky",
                            second_call_breaks(lowrank._small_cholesky))
    qs, _ = lowrank._cholqr_multi([y])
    q = np.asarray(qs[0])
    assert len(calls) == 2 and np.isfinite(q).all()
    normalized_q1 = np.asarray(lowrank._normalize_cols(q1[0])[0])
    np.testing.assert_array_equal(q[:, from_column:],
                                  normalized_q1[:, from_column:])
    np.testing.assert_array_equal(q[:, :from_column],
                                  np.asarray(q2[0])[:, :from_column])


def test_sound_rows_and_normalize_cols_on_hand_made_breakdowns():
    eye = np.eye(4, dtype=np.float32)
    linv = np.stack([eye * 3.0, eye * 3.0])
    linv[0, 2] = np.nan                  # a row that is not a number
    linv[1, 1, 0] = np.float32(2e6)      # delta * |x|^2 = 4e6
    linv[1, 3, 3] = np.float32(5e3)      # 25: rounding reaches this, it stands
    delta = jnp.full((2, 1, 1), 1e-6, jnp.float32)
    out = np.asarray(lowrank._sound_rows(jnp.asarray(linv), delta))
    np.testing.assert_array_equal(out[0, 2], eye[2])
    np.testing.assert_array_equal(out[1, 1], eye[1])
    keep = np.ones((2, 4), bool)
    keep[0, 2] = keep[1, 1] = False
    np.testing.assert_array_equal(out[keep], linv[keep])
    # a column with a NaN, and one whose sum of squares overflows, take the
    # basis vector like an all-zero one; the others are divided as before
    y = np.ones((4, 4), np.float32)
    y[1, 0], y[:, 1], y[:, 2] = np.nan, 0.0, 1e30
    yn, _ = lowrank._normalize_cols(jnp.asarray(y))
    np.testing.assert_array_equal(np.asarray(yn)[:, :3], eye[:, :3])
    np.testing.assert_array_equal(np.asarray(yn)[:, 3], np.full(4, 0.5))


# --- through the trainer -----------------------------------------------------


def _train(engine, seed, rounds, limit_s):
    """``(losses, params)`` of ``rounds`` federated rounds at the benchmark's
    rehearsal size ``tiny`` (8 sites, 2 rounds an epoch), the cell's own
    configuration, model and seeded data."""
    from benchmarks.drivers import train
    from benchmarks.lib import cells
    from dinunet_implementations_tpu.trainer.loop import FederatedTrainer

    deadline = time.monotonic() + limit_s
    cell = cells.load_cell("icalstm-hcp32.rankdad")
    cfg, model, sites = train.build(
        cell, types.SimpleNamespace(rehearse="tiny", seed=seed))
    cfg = cfg.replace(agg_engine=engine)
    trainer = FederatedTrainer(cfg, model, None)
    state = trainer.init_state(
        jnp.ones((cfg.batch_size,) + sites[0].inputs.shape[1:], jnp.float32),
        num_sites=len(sites))
    losses, epoch = [], 0
    while len(losses) < rounds:
        assert time.monotonic() < deadline, (
            f"{len(losses)} rounds in {limit_s} s: the test's own time limit")
        epoch += 1
        state, epoch_losses = trainer.run_epoch(
            state, sites, epoch, batch_size=cfg.batch_size)
        losses.extend(np.asarray(epoch_losses).ravel().tolist())
    return np.asarray(losses), jax.device_get(state.params)


@pytest.mark.parametrize("engine,seed,rounds", [
    ("rankDAD", 3, 600),   # the accepted tree: NaN from round 336 on
    ("rankDAD", 7, 600),   # round 360
    ("powerSGD", 3, 300),
    ("powerSGD", 7, 300),
])
def test_training_stays_finite_past_the_horizon(engine, seed, rounds):
    losses, params = _train(engine, seed, rounds, limit_s=240)
    bad = np.flatnonzero(~np.isfinite(losses))
    assert bad.size == 0, f"first non-finite loss at round {bad[0]}"
    for leaf in jax.tree.leaves(params):
        assert np.isfinite(np.asarray(leaf)).all()
    assert losses[-50:].mean() < losses[:10].mean()  # and it still trains
