"""Pallas fused LSTM kernel vs the XLA scan reference path (interpret mode on
CPU; the same kernel compiles on TPU)."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from dinunet_implementations_tpu.models.icalstm import ICALstm, LSTMCell


def _params(key, D, H):
    ks = jax.random.split(key, 4)
    return {
        "w_ih": jax.random.normal(ks[0], (D, 4 * H)) * 0.2,
        "b_ih": jax.random.normal(ks[1], (4 * H,)) * 0.1,
        "w_hh": jax.random.normal(ks[2], (H, 4 * H)) * 0.2,
        "b_hh": jax.random.normal(ks[3], (4 * H,)) * 0.1,
    }


@pytest.mark.parametrize("B,T,D,H", [(4, 7, 5, 8), (16, 11, 6, 12)])
def test_pallas_forward_matches_scan(B, T, D, H):
    key = jax.random.PRNGKey(0)
    x = jax.random.normal(key, (B, T, D))
    params = _params(key, D, H)
    scan = LSTMCell(H, use_pallas=False)
    pal = LSTMCell(H, use_pallas=True)
    hs_s, (h_s, c_s) = scan.apply({"params": params}, x)
    hs_p, (h_p, c_p) = pal.apply({"params": params}, x)
    np.testing.assert_allclose(np.asarray(hs_p), np.asarray(hs_s), atol=1e-5)
    np.testing.assert_allclose(np.asarray(h_p), np.asarray(h_s), atol=1e-5)
    np.testing.assert_allclose(np.asarray(c_p), np.asarray(c_s), atol=1e-5)


def test_pallas_backward_matches_scan():
    B, T, D, H = 8, 6, 5, 8
    key = jax.random.PRNGKey(1)
    x = jax.random.normal(key, (B, T, D))
    params = _params(key, D, H)

    def loss(params, module):
        hs, (hT, cT) = module.apply({"params": params}, x)
        # use hs, hT AND cT so every cotangent path is exercised
        return jnp.sum(hs**2) + jnp.sum(jnp.sin(hT)) + jnp.sum(cT**2)

    g_scan = jax.grad(loss)(params, LSTMCell(H, use_pallas=False))
    g_pal = jax.grad(loss)(params, LSTMCell(H, use_pallas=True))
    for k in params:
        np.testing.assert_allclose(
            np.asarray(g_pal[k]), np.asarray(g_scan[k]), atol=1e-4, err_msg=k
        )


def test_pallas_input_grad_matches_scan():
    B, T, D, H = 4, 5, 6, 8
    key = jax.random.PRNGKey(2)
    x = jax.random.normal(key, (B, T, D))
    params = _params(key, D, H)

    def loss_x(x, module):
        hs, _ = module.apply({"params": params}, x)
        return jnp.sum(hs**3)

    gx_s = jax.grad(loss_x)(x, LSTMCell(H, use_pallas=False))
    gx_p = jax.grad(loss_x)(x, LSTMCell(H, use_pallas=True))
    np.testing.assert_allclose(np.asarray(gx_p), np.asarray(gx_s), atol=1e-4)


def test_pallas_under_vmap():
    """The folded-sites trainer vmaps over a leading site axis — the kernel
    must batch correctly."""
    S, B, T, D, H = 3, 4, 5, 6, 8
    key = jax.random.PRNGKey(3)
    x = jax.random.normal(key, (S, B, T, D))
    params = _params(key, D, H)
    scan = LSTMCell(H, use_pallas=False)
    pal = LSTMCell(H, use_pallas=True)
    f_s = jax.vmap(lambda xx: scan.apply({"params": params}, xx)[0])
    f_p = jax.vmap(lambda xx: pal.apply({"params": params}, xx)[0])
    np.testing.assert_allclose(np.asarray(f_p(x)), np.asarray(f_s(x)), atol=1e-5)


def test_pallas_batch_padding():
    """B not a multiple of the kernel tile is padded and sliced back."""
    B, T, D, H = 5, 4, 3, 8  # B=5: odd size
    key = jax.random.PRNGKey(4)
    x = jax.random.normal(key, (B, T, D))
    params = _params(key, D, H)
    hs_s, _ = LSTMCell(H, use_pallas=False).apply({"params": params}, x)
    hs_p, _ = LSTMCell(H, use_pallas=True).apply({"params": params}, x)
    np.testing.assert_allclose(np.asarray(hs_p), np.asarray(hs_s), atol=1e-5)


@pytest.mark.slow
def test_icalstm_pallas_end_to_end_grad():
    """Full ICALstm model trains identically (small tolerance) on both paths."""
    key = jax.random.PRNGKey(5)
    x = jax.random.normal(key, (4, 6, 5, 4))
    y = jnp.array([0, 1, 0, 1])
    m_scan = ICALstm(input_size=16, hidden_size=12, num_comps=5, window_size=4)
    variables = m_scan.init({"params": key, "dropout": key}, x, train=True)

    def loss(v, module):
        logits = module.apply(v, x, train=False)
        return -jnp.mean(
            jnp.take_along_axis(jax.nn.log_softmax(logits), y[:, None], 1)
        )

    # same params work on both paths (param structure is identical)
    g_s = jax.grad(loss)(variables, m_scan)["params"]
    m_pal = ICALstm(
        input_size=16, hidden_size=12, num_comps=5, window_size=4,
        use_pallas=True,
    )
    g_p = jax.grad(loss)(variables, m_pal)["params"]
    jax.tree.map(
        lambda a, b: np.testing.assert_allclose(
            np.asarray(a), np.asarray(b), atol=1e-4
        ),
        g_p,
        g_s,
    )


@pytest.mark.slow
def test_multi_tile_dw_accumulation():
    """Review finding regression: with B > one kernel tile, dW must accumulate
    across ALL batch tiles (was wiped at each tile's first step)."""
    from dinunet_implementations_tpu.ops import lstm_pallas

    old = lstm_pallas.B_TILE
    lstm_pallas.B_TILE = 8  # force 3 tiles at B=24 without a huge test
    try:
        B, T, D, H = 24, 5, 4, 8
        key = jax.random.PRNGKey(6)
        x = jax.random.normal(key, (B, T, D))
        params = _params(key, D, H)

        def loss(p, module):
            hs, _ = module.apply({"params": p}, x)
            return jnp.sum(hs**2)

        g_s = jax.grad(loss)(params, LSTMCell(H, use_pallas=False))
        g_p = jax.grad(loss)(params, LSTMCell(H, use_pallas=True))
        np.testing.assert_allclose(
            np.asarray(g_p["w_hh"]), np.asarray(g_s["w_hh"]), atol=1e-4
        )
    finally:
        lstm_pallas.B_TILE = old


def test_bf16_inputs_roundtrip():
    """Review finding regression: non-f32 inputs must work and preserve dtype."""
    B, T, D, H = 4, 5, 6, 8
    key = jax.random.PRNGKey(7)
    x = jax.random.normal(key, (B, T, D)).astype(jnp.bfloat16)
    params = jax.tree.map(lambda a: a.astype(jnp.bfloat16), _params(key, D, H))
    hs, (hT, cT) = LSTMCell(H, use_pallas=True).apply({"params": params}, x)
    assert hs.dtype == jnp.bfloat16
    hs_s, _ = LSTMCell(H, use_pallas=False).apply({"params": params}, x)
    np.testing.assert_allclose(
        np.asarray(hs, np.float32), np.asarray(hs_s, np.float32), atol=0.05
    )


def test_lstm_recurrence_rejects_indivisible_batch():
    from dinunet_implementations_tpu.ops import lstm_pallas

    old = lstm_pallas.B_TILE
    lstm_pallas.B_TILE = 8
    try:
        D, H = 5, 4
        with pytest.raises(AssertionError, match="multiple of the kernel tile"):
            lstm_pallas.lstm_recurrence_fused(
                jnp.ones((3, 12, D)), jnp.ones((4, D, H)), jnp.ones((4, H)),
                jnp.ones((4, H, H)), jnp.ones((12, H)), jnp.ones((12, H)),
            )
    finally:
        lstm_pallas.B_TILE = old


@pytest.mark.slow
def test_compute_dtype_bf16_close_to_f32():
    """Mixed-precision mode (bf16 matmuls/streams, f32 carries+accum) must
    track the f32 path closely — forward and gradients — incl. under vmap."""
    S, B, T, D, H = 3, 4, 6, 5, 8
    key = jax.random.PRNGKey(8)
    x = jax.random.normal(key, (S, B, T, D))
    params = _params(key, D, H)
    f32 = LSTMCell(H, use_pallas=True)
    b16 = LSTMCell(H, use_pallas=True, compute_dtype="bfloat16")

    out_f = jax.vmap(lambda xx: f32.apply({"params": params}, xx)[0])(x)
    out_b = jax.vmap(lambda xx: b16.apply({"params": params}, xx)[0])(x)
    np.testing.assert_allclose(np.asarray(out_b), np.asarray(out_f), atol=0.05)

    def loss(p, module):
        hs = jax.vmap(lambda xx: module.apply({"params": p}, xx)[0])(x)
        return jnp.sum(hs**2)

    g_f = jax.grad(loss)(params, f32)
    g_b = jax.grad(loss)(params, b16)
    for k in params:
        a, b = np.asarray(g_b[k], np.float32), np.asarray(g_f[k])
        denom = max(np.abs(b).max(), 1.0)
        assert np.abs(a - b).max() / denom < 0.06, k


def test_scan_path_bf16_carry_types():
    """Review regression: the lax.scan fallback with compute_dtype set must
    not violate scan carry-type invariance (bf16 h0 vs f32 carry)."""
    key = jax.random.PRNGKey(9)
    x = jax.random.normal(key, (2, 4, 5))
    params = _params(key, 5, 8)
    hs, (hT, cT) = LSTMCell(8, use_pallas=False, compute_dtype="bfloat16").apply(
        {"params": params}, x
    )
    assert np.isfinite(np.asarray(hs, np.float32)).all()
    hs_f, _ = LSTMCell(8, use_pallas=False).apply({"params": params}, x)
    np.testing.assert_allclose(
        np.asarray(hs, np.float32), np.asarray(hs_f), atol=0.05
    )


def test_lstm_recurrence_direct_f32_x_bf16_compute_grad():
    """ADVICE r2 regression (dtype-contract class): a direct
    lstm_recurrence_fused call with f32 x and compute_dtype='bfloat16' must
    return an f32 dx cotangent (custom_vjp requires cotangent avals to match
    the primal avals)."""
    from dinunet_implementations_tpu.ops.lstm_pallas import lstm_recurrence_fused

    B, T, D, H = 4, 5, 6, 8
    key = jax.random.PRNGKey(9)
    x = jax.random.normal(key, (T, B, D))
    wih4 = jax.random.normal(key, (4, D, H)) * 0.2
    b4 = jnp.zeros((4, H))
    whh4 = jax.random.normal(key, (4, H, H)) * 0.2
    h0 = jnp.zeros((B, H))
    c0 = jnp.zeros((B, H))

    def loss(x):
        hs, (hT, cT) = lstm_recurrence_fused(x, wih4, b4, whh4, h0, c0, jnp.bfloat16)
        return jnp.sum(hs.astype(jnp.float32) ** 2) + jnp.sum(hT + cT)

    g = jax.grad(loss)(x)
    assert g.dtype == jnp.float32
    assert np.isfinite(np.asarray(g)).all()


def test_fused_grad_with_bf16_weights_matches_primal_dtypes():
    """Review regression (r3): a direct lstm_recurrence_fused call with
    non-f32 weights must return cotangents at the PRIMAL dtypes (custom_vjp
    aval check) — dwih/db/dwhh, not just dx."""
    from dinunet_implementations_tpu.ops.lstm_pallas import lstm_recurrence_fused

    B, T, D, H = 4, 5, 6, 8
    key = jax.random.PRNGKey(11)
    bf16 = jnp.bfloat16
    x = jax.random.normal(key, (T, B, D)).astype(bf16)
    wih4 = (jax.random.normal(key, (4, D, H)) * 0.2).astype(bf16)
    b4 = jnp.zeros((4, H), bf16)
    whh4 = (jax.random.normal(key, (4, H, H)) * 0.2).astype(bf16)
    h0 = jnp.zeros((B, H))
    c0 = jnp.zeros((B, H))

    def loss(x, wih4, b4, whh4):
        hs, _ = lstm_recurrence_fused(x, wih4, b4, whh4, h0, c0, bf16)
        return jnp.sum(hs.astype(jnp.float32) ** 2)

    gx, gwih, gb, gwhh = jax.grad(loss, argnums=(0, 1, 2, 3))(x, wih4, b4, whh4)
    assert gx.dtype == bf16 and gwih.dtype == bf16
    assert gb.dtype == bf16 and gwhh.dtype == bf16
    for g in (gx, gwih, gb, gwhh):
        assert np.isfinite(np.asarray(g, np.float32)).all()


def test_fused_terminal_carry_is_f32_even_under_bf16():
    """Ring-relay contract: (hT, cT) come from the kernel's f32 scratch, not
    the bf16 streams — so chunk-boundary relays never quantize the carry."""
    from dinunet_implementations_tpu.ops.lstm_pallas import lstm_forward_fused

    B, T, D, H = 4, 6, 5, 8
    key = jax.random.PRNGKey(10)
    x = jax.random.normal(key, (B, T, D)).astype(jnp.bfloat16)
    p = _params(key, D, H)
    hs, (hT, cT) = lstm_forward_fused(
        x, p["w_ih"], p["b_ih"] + p["b_hh"], p["w_hh"],
        jnp.zeros((B, H)), jnp.zeros((B, H)), compute_dtype=jnp.bfloat16,
    )
    assert hs.dtype == jnp.bfloat16
    assert hT.dtype == jnp.float32 and cT.dtype == jnp.float32
    # and the f32 carry is strictly more precise than the bf16 stream's last
    # step: they agree to bf16 resolution
    np.testing.assert_allclose(
        np.asarray(hs[:, -1].astype(jnp.float32)), np.asarray(hT), atol=0.01
    )


# ---------------------------------------------------------------------------
# the bidirectional MODULE on the per-direction kernels: BiLSTM(time_pool=
# "mean") is what ICALstm's dense path runs (two kernel calls, the reverse one
# over the flipped input, each direction pooled on its own)
# ---------------------------------------------------------------------------


def _scan_lstm(x, p, h0, c0):
    """Plain reference, independent of the package: ``p`` is an LSTMCell
    param dict (blocked ``[D, 4H]`` / ``[H, 4H]`` layout, gates i, f, o, g)."""
    w_ih, b, w_hh = p["w_ih"], p["b_ih"] + p["b_hh"], p["w_hh"]
    H = w_hh.shape[0]
    xi = x @ w_ih + b

    def step(carry, xt):
        h, c = carry
        pre = xt + h @ w_hh
        i = jax.nn.sigmoid(pre[..., :H])
        f = jax.nn.sigmoid(pre[..., H : 2 * H])
        o = jax.nn.sigmoid(pre[..., 2 * H : 3 * H])
        g = jnp.tanh(pre[..., 3 * H :])
        c = f * c + i * g
        h = o * jnp.tanh(c)
        return (h, c), h

    (hT, cT), hs = jax.lax.scan(step, (h0, c0), jnp.swapaxes(xi, 0, 1))
    return jnp.swapaxes(hs, 0, 1), (hT, cT)


def _scan_bidir_pool(x, params, h0, c0):
    """What BiLSTM(time_pool="mean") must return: both directions start from
    the same ``(h0, c0)``, the reverse one reads the flipped input, the pooled
    halves and the terminal carries concatenate on the feature axis."""
    hsf, (hTf, cTf) = _scan_lstm(x, params["fwd"], h0, c0)
    hsr, (hTr, cTr) = _scan_lstm(jnp.flip(x, 1), params["rev"], h0, c0)
    return (
        jnp.concatenate([hsf.mean(1), hsr.mean(1)], -1),
        (jnp.concatenate([hTf, hTr], 1), jnp.concatenate([cTf, cTr], 1)),
    )


def _assert_trees_close(got, want, atol):
    jax.tree.map(
        lambda a, b: np.testing.assert_allclose(
            np.asarray(a), np.asarray(b), atol=atol
        ),
        got, want,
    )


def _bidir_case(B, T, D, H, with_h0, seed=20):
    ks = jax.random.split(jax.random.PRNGKey(seed), 5)
    x = jax.random.normal(ks[0], (B, T, D))
    params = {"fwd": _params(ks[1], D, H), "rev": _params(ks[2], D, H)}
    h0 = (
        (jax.random.normal(ks[3], (B, H)) * 0.3,
         jax.random.normal(ks[4], (B, H)) * 0.3)
        if with_h0 else None
    )
    return x, params, h0


# tile 2 at B=3 pads the rows to 4 and runs two batch tiles
@pytest.mark.parametrize("with_h0", [False, True], ids=["zero-h0", "given-h0"])
@pytest.mark.parametrize(
    "B,T,D,H,tile", [(4, 6, 5, 8, 128), (3, 5, 4, 8, 2)],
    ids=["whole-tile", "rows-padded"],
)
def test_bidir_module_pooled_on_per_direction_kernels(
    monkeypatch, B, T, D, H, tile, with_h0
):
    from dinunet_implementations_tpu.models.icalstm import BiLSTM
    from dinunet_implementations_tpu.ops import lstm_pallas

    monkeypatch.setattr(lstm_pallas, "B_TILE", tile)
    x, params, h0 = _bidir_case(B, T, D, H, with_h0)
    got = BiLSTM(2 * H, use_pallas=True, time_pool="mean").apply(
        {"params": params}, x, h0
    )
    scan = BiLSTM(2 * H, use_pallas=False, time_pool="mean").apply(
        {"params": params}, x, h0
    )
    z = jnp.zeros((B, H))
    ref = _scan_bidir_pool(x, params, *(h0 if with_h0 else (z, z)))
    assert got[0].shape == (B, 2 * H)
    assert got[1][0].shape == got[1][1].shape == (B, 2 * H)
    _assert_trees_close(got, scan, atol=1e-5)
    _assert_trees_close(got, ref, atol=1e-5)


@pytest.mark.parametrize(
    "compute_dtype,tol", [(None, 1e-4), ("bfloat16", 0.06)],
    ids=["float32", "bfloat16"],
)
def test_bidir_module_grad_through_pool_and_carries_rows_padded(
    monkeypatch, compute_dtype, tol
):
    """Every cotangent path of the module at a row-padded shape: the pooled
    output (dhs of both kernels), the terminal carries (dhT, dcT), the input,
    the start state and both directions' weights, against the plain float32
    reference (bfloat16: relative to the largest entry, as the cell's own
    mixed-precision test does)."""
    from dinunet_implementations_tpu.models.icalstm import BiLSTM
    from dinunet_implementations_tpu.ops import lstm_pallas

    monkeypatch.setattr(lstm_pallas, "B_TILE", 2)
    B, T, D, H = 3, 5, 4, 8
    x, params, h0 = _bidir_case(B, T, D, H, with_h0=True, seed=28)
    module = BiLSTM(
        2 * H, use_pallas=True, time_pool="mean", compute_dtype=compute_dtype
    )

    def loss(forward):
        def f(x, params, h0):
            pooled, (hT, cT) = forward(x, params, h0)
            return (
                jnp.sum(pooled.astype(jnp.float32) ** 2)
                + jnp.sum(jnp.sin(hT)) + jnp.sum(cT**2)
            )

        return f

    got = jax.jit(jax.grad(
        loss(lambda x, p, h0: module.apply({"params": p}, x, h0)),
        argnums=(0, 1, 2),
    ))(x, params, h0)
    ref = jax.jit(jax.grad(
        loss(lambda x, p, h0: _scan_bidir_pool(x, p, *h0)), argnums=(0, 1, 2)
    ))(x, params, h0)
    assert got[2][0].shape == got[2][1].shape == (B, H)

    def close(a, b):
        a, b = np.asarray(a, np.float32), np.asarray(b)
        assert a.shape == b.shape
        assert np.abs(a - b).max() / max(np.abs(b).max(), 1.0) < tol

    jax.tree.map(close, got, ref)


@pytest.mark.parametrize("weights", ["shared", "per-site"])
def test_bidir_module_vmapped_over_sites(monkeypatch, weights):
    """Both branches of the kernels' vmap rules, forward and backward, at the
    module: weights shared by the sites fold the site axis into kernel rows
    (3 sites x 4 rows = 12, padded to two tiles of 8); weights that differ by
    site (a personalized layer) run site by site under ``lax.map``."""
    from dinunet_implementations_tpu.models.icalstm import BiLSTM
    from dinunet_implementations_tpu.ops import lstm_pallas

    monkeypatch.setattr(lstm_pallas, "B_TILE", 8)
    S, B, T, D, H = 3, 4, 5, 4, 8
    x, params, _ = _bidir_case(S * B, T, D, H, with_h0=False, seed=33)
    x = x.reshape(S, B, T, D)
    p_axis = None
    if weights == "per-site":
        scale = jnp.arange(1.0, S + 1.0) / S
        params = jax.tree.map(
            lambda a: a[None] * scale.reshape(S, *([1] * a.ndim)), params
        )
        p_axis = 0
    module = BiLSTM(2 * H, use_pallas=True, time_pool="mean")
    z = jnp.zeros((B, H))

    def run(forward):
        def f(x, params):
            pooled, (hT, cT) = jax.vmap(forward, in_axes=(0, p_axis))(x, params)
            return (
                jnp.sum(pooled**2) + jnp.sum(jnp.sin(hT)) + jnp.sum(cT**2),
                (pooled, hT, cT),
            )

        return jax.jit(jax.value_and_grad(f, argnums=(0, 1), has_aux=True))(x, params)

    (_, got), got_g = run(lambda xs, p: module.apply({"params": p}, xs))
    (_, ref), ref_g = run(lambda xs, p: _scan_bidir_pool(xs, p, z, z))
    assert got[0].shape == (S, B, 2 * H)
    _assert_trees_close(got, ref, atol=1e-5)
    _assert_trees_close(got_g, ref_g, atol=1e-4)


@pytest.mark.slow
def test_icalstm_pallas_vmapped_over_sites_end_to_end():
    """The EXACT program the federated bench compiles: the full
    ICALstm(use_pallas=True) model vmapped over a leading site axis — logits
    and parameter gradients must match the scan path."""
    S = 3
    key = jax.random.PRNGKey(42)
    x = jax.random.normal(key, (S, 4, 6, 5, 4))  # [S, B, windows, C, W]
    y = jnp.tile(jnp.array([0, 1, 0, 1]), (S, 1))
    kwargs = dict(input_size=16, hidden_size=12, num_comps=5, window_size=4)
    m_scan = ICALstm(use_pallas=False, **kwargs)
    m_pal = ICALstm(use_pallas=True, **kwargs)
    variables = m_scan.init({"params": key, "dropout": key}, x[0], train=True)

    def loss(v, module):
        def per_site(xs, ys):
            logits = module.apply(v, xs, train=False)
            return -jnp.mean(
                jnp.take_along_axis(
                    jax.nn.log_softmax(logits), ys[:, None], 1
                )
            )

        return jnp.mean(jax.vmap(per_site)(x, y))

    np.testing.assert_allclose(
        np.asarray(loss(variables, m_pal)),
        np.asarray(loss(variables, m_scan)),
        rtol=1e-5,
    )
    g_p = jax.grad(loss)(variables, m_pal)["params"]
    g_s = jax.grad(loss)(variables, m_scan)["params"]
    jax.tree.map(
        lambda a, b: np.testing.assert_allclose(
            np.asarray(a), np.asarray(b), atol=1e-4
        ),
        g_p,
        g_s,
    )
