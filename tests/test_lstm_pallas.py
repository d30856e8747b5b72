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


# ---------------------------------------------------------------------------
# the block a grid step carries (ISSUE 31): Tb timesteps of R rows, walked
# inside the kernel; the backward's c_{t-1} from the same block plus a
# one-timestep halo; steps past T in the last time block leave the carry alone
# ---------------------------------------------------------------------------


def _force_block(monkeypatch, Tb, R=None, granule=None):
    """Every kernel call takes the block ``(Tb, R)`` through ``lstm_block``'s
    override argument (``R`` None: all of the call's rows; a ``Tb`` beyond a
    call's ``T`` is cut to it). ``granule`` re-points the row granule the
    callers pad to."""
    from dinunet_implementations_tpu.ops import lstm_pallas

    chosen = lstm_pallas.lstm_block
    if granule is not None:
        monkeypatch.setattr(lstm_pallas, "B_TILE", granule)
    monkeypatch.setattr(
        lstm_pallas, "lstm_block",
        lambda T, rows, D, H, dtype, override=None: chosen(
            T, rows, D, H, dtype, override=(min(Tb, T), R or rows)),
    )


def _cell_case(B, T, D, H, seed):
    ks = jax.random.split(jax.random.PRNGKey(seed), 4)
    return (
        jax.random.normal(ks[0], (B, T, D)),
        _params(ks[1], D, H),
        (jax.random.normal(ks[2], (B, H)) * 0.3,
         jax.random.normal(ks[3], (B, H)) * 0.3),
    )


def _cell_value_and_grads(forward, x, params, h0):
    """Outputs and every gradient of a cell: ``hs`` feeds ``dhs``, and the
    terminal carry enters the loss so that ``dhT`` and ``dcT`` are non-zero."""
    def loss(x, params, h0):
        hs, (hT, cT) = forward(x, params, h0)
        return (
            jnp.sum(hs.astype(jnp.float32) ** 2)
            + jnp.sum(jnp.sin(hT)) + jnp.sum(cT**2),
            (hs, hT, cT),
        )

    (_, outs), grads = jax.jit(
        jax.value_and_grad(loss, argnums=(0, 1, 2), has_aux=True)
    )(x, params, h0)
    return outs, grads


def _assert_rel_close(got, want, tol):
    def close(a, b):
        a, b = np.asarray(a, np.float32), np.asarray(b, np.float32)
        assert a.shape == b.shape
        assert np.abs(a - b).max() / max(np.abs(b).max(), 1.0) < tol

    jax.tree.map(close, got, want)


def _check_block_against_scan_and_reference(monkeypatch, case, compute_dtype):
    T, Tb, B, R, granule = case
    D, H = 5, 8
    _force_block(monkeypatch, Tb, R, granule)
    x, params, h0 = _cell_case(B, T, D, H, seed=31)
    pal = LSTMCell(H, use_pallas=True, compute_dtype=compute_dtype)
    scan = LSTMCell(H, use_pallas=False, compute_dtype=compute_dtype)
    got = _cell_value_and_grads(
        lambda x, p, h0: pal.apply({"params": p}, x, h0), x, params, h0)
    want_scan = _cell_value_and_grads(
        lambda x, p, h0: scan.apply({"params": p}, x, h0), x, params, h0)
    want_ref = _cell_value_and_grads(
        lambda x, p, h0: _scan_lstm(x, p, *h0), x, params, h0)
    assert got[0][1].dtype == got[0][2].dtype == jnp.float32
    if compute_dtype is None:
        _assert_trees_close(got[0], want_scan[0], atol=1e-5)
        _assert_trees_close(got[1], want_scan[1], atol=1e-4)
        _assert_trees_close(got[0], want_ref[0], atol=1e-5)
        _assert_trees_close(got[1], want_ref[1], atol=1e-4)
    else:
        _assert_rel_close(got, want_scan, 0.06)
        _assert_rel_close(got, want_ref, 0.06)


# (T, Tb, rows, R, row granule): several time blocks; a T that Tb does not
# divide (the last block's tail leaves the carry alone), prime or not; rows
# padded to R, and R of two granules; one block that is the whole sequence
BLOCK_CASES = {
    "T7-Tb2-tail1": (7, 2, 8, 8, 8),
    "T7-Tb7-one-block": (7, 7, 8, 8, 8),
    "T13-Tb4-tail3-rows6-padded-to-R8-of-two-granules": (13, 4, 6, 8, 4),
    "T13-Tb5-two-row-tiles": (13, 5, 16, 8, 8),
    "T98-Tb14-seven-blocks": (98, 14, 4, 4, 4),
    "T98-Tb16-tail14": (98, 16, 4, 4, 4),
}


@pytest.mark.parametrize(
    "compute_dtype", [None, "bfloat16"], ids=["float32", "bfloat16"])
@pytest.mark.parametrize("case", BLOCK_CASES.values(), ids=BLOCK_CASES.keys())
def test_block_forward_and_gradients_match_scan_and_reference(
        monkeypatch, case, compute_dtype):
    _check_block_against_scan_and_reference(monkeypatch, case, compute_dtype)


SEEDED_DEFECTS = {
    # the block boundary's c_{t-1} read from the block's own first step
    "halo-from-the-wrong-timestep": (
        "_block_start_prev", lambda tb, Tb: tb * Tb,
        "T13-Tb5-two-row-tiles"),
    # time 0 reads the (clamped) halo, not the start state
    "c0-not-used-at-time-0": (
        "_c_before_block", lambda tb, c0, halo: halo,
        "T7-Tb7-one-block"),
    # the tail's steps past T advance the carry, and hT / cT come after them
    "terminal-carry-written-after-a-padded-step": (
        "_hold", lambda live, new, old: new,
        "T7-Tb2-tail1"),
}


@pytest.mark.parametrize(
    "defect", SEEDED_DEFECTS.values(), ids=SEEDED_DEFECTS.keys())
def test_block_cases_fail_under_a_seeded_defect(monkeypatch, defect):
    """The parity cases above are sharp: each of the three ways a block can
    go wrong turns the named case red (and it is green without the defect)."""
    from dinunet_implementations_tpu.ops import lstm_pallas

    name, broken, case = defect
    _check_block_against_scan_and_reference(monkeypatch, BLOCK_CASES[case], None)
    monkeypatch.setattr(lstm_pallas, name, broken)
    with pytest.raises(AssertionError):
        _check_block_against_scan_and_reference(
            monkeypatch, BLOCK_CASES[case], None)


@pytest.mark.parametrize("weights", ["shared", "per-site"])
def test_block_under_the_vmap_fold(monkeypatch, weights):
    """The vmap rules with a block of several timesteps: shared weights fold 3
    sites x 4 rows into 12 kernel rows, padded to 16 = one R of two granules;
    per-site weights run site by site, each call's 4 rows one tile. T = 5
    with Tb = 2: three time blocks, the last with a one-step tail."""
    from dinunet_implementations_tpu.models.icalstm import BiLSTM

    _force_block(monkeypatch, Tb=2, granule=8)
    S, B, T, D, H = 3, 4, 5, 4, 8
    x, params, _ = _bidir_case(S * B, T, D, H, with_h0=False, seed=35)
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
    _assert_trees_close(got, ref, atol=1e-5)
    _assert_trees_close(got_g, ref_g, atol=1e-4)


# -- lstm_block as a function ------------------------------------------------

# the shapes the repo's configurations hand the kernels (D 256, H 174 a
# direction): the benchmark's cells fold 32 sites x 16 rows; a site alone (the
# per-site-weights path, a ring microbatch) brings 16, 8 or 4 rows; the ring
# LSTM hands chunks of T / model_axis steps (98 / 2; 96 and 100 over 4)
CONFIG_SHAPES = [
    (98, 512), (98, 2048), (98, 128), (98, 16),
    (49, 16), (49, 8), (25, 16), (24, 4), (24, 128),
]


@pytest.mark.parametrize("dtype", ["bfloat16", "float32"])
@pytest.mark.parametrize("T,rows", CONFIG_SHAPES)
def test_lstm_block_stays_under_its_vmem_budget(T, rows, dtype):
    from dinunet_implementations_tpu.ops import lstm_pallas

    for D in (256, None):  # the forward call, the backward call
        Tb, R = lstm_pallas.lstm_block(T, rows, D, 174, dtype)
        assert 1 <= Tb <= min(T, lstm_pallas.MAX_BLOCK_STEPS)
        assert rows % R == 0 and R % min(lstm_pallas.B_TILE, rows) == 0
        assert lstm_pallas.block_vmem_bytes(Tb, R, D, 174, dtype) <= (
            lstm_pallas.VMEM_BUDGET)
        assert lstm_pallas.VMEM_BUDGET < 16 << 20  # the compiler's own scope


def test_lstm_block_engages_at_the_benchmark_cells_shape():
    """T 98, 512 folded rows, bfloat16: several timesteps a grid step in both
    calls, with no step past T; the forward takes all four row granules."""
    from dinunet_implementations_tpu.ops.lstm_pallas import lstm_block

    Tb_f, R_f = lstm_block(98, 512, 256, 174, "bfloat16")
    Tb_b, R_b = lstm_block(98, 512, None, 174, "bfloat16")
    assert Tb_f > 1 and Tb_b > 1
    assert 98 % Tb_f == 0 and 98 % Tb_b == 0
    assert (R_f, R_b) == (512, 128)
    assert (512 // R_f) * (98 // Tb_f) <= 392 // 4
    assert (512 // R_b) * (98 // Tb_b) <= 392 // 4


@pytest.mark.parametrize(
    "T,rows,want_Tb",
    [
        (1, 512, 1),  # nothing to walk
        (98, 3, 14),  # a row tile that is no whole sublane tile walks too
        (13, 16, 13),  # a short prime T: one block, no tail
        (97, 16, 14),  # a long prime T: seven blocks, ONE step past T
        (98, 16, 14),
    ],
)
def test_lstm_block_degrades_and_pads_as_documented(T, rows, want_Tb):
    from dinunet_implementations_tpu.ops.lstm_pallas import lstm_block

    for D in (256, None):
        assert lstm_block(T, rows, D, 174, "bfloat16")[0] == want_Tb


def test_lstm_block_override_is_checked_for_what_cannot_run():
    from dinunet_implementations_tpu.ops.lstm_pallas import lstm_block

    assert lstm_block(98, 512, 256, 174, "bfloat16", override=(5, 256)) == (5, 256)
    for bad in [(0, 128), (99, 128), (7, 96), (7, 0)]:
        with pytest.raises(ValueError, match="does not fit"):
            lstm_block(98, 512, 256, 174, "bfloat16", override=bad)
