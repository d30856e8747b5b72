"""Rotary and the hand-over to the attention kernels as one pass (ISSUE 37).

``ops/rope_pallas.py`` in interpret mode on the CPU against the instructions it
replaces (``rotary`` -> cast -> ``moveaxis`` -> scale, and ``jax.vjp`` of
them), at the two cells' head layouts; under the trainer's site fold and a
block's checkpoint; the block chooser's table; and the dispatch rule: who
takes the new path follows the call's shapes alone.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from dinunet_implementations_tpu.models import afmoe
from dinunet_implementations_tpu.ops import rope_pallas
from test_afmoe import _kernel_calls

THETA = 1.5e6
# (query heads, key-value heads, head width): the third cell's and Trinity's
LAYOUTS = {"smallthinker": (28, 4, 128), "trinity": (32, 4, 128)}


def xla_path(q, k, n, g, d, cdt, chip=True):
    """What the kernel replaces, as ``Attention`` and ``kernel_attention``
    word it: ``q [.., T, n * d]``, ``k [.., T, g * d]`` float32 -> the splash
    kernels' operands. ``chip``: as the TPU compiler runs those words — it
    keeps the float32 between the queries' rounding and their scale
    (``xla_allow_excess_precision``: one rounding, of the product); without,
    the words as the CPU runs them (a rounding, then a multiply in ``cdt``)."""
    lead, t = q.shape[:-2], q.shape[-2]
    pos = jnp.arange(t)
    q = afmoe.rotary(q.reshape(*lead, t, n, d), pos, THETA)
    k = afmoe.rotary(k.reshape(*lead, t, g, d), pos, THETA).astype(cdt)
    if chip:
        q = (q * jnp.asarray(d ** -0.5, cdt).astype(jnp.float32)).astype(cdt)
    else:
        q = q.astype(cdt) * (d ** -0.5)
    qh = jnp.moveaxis(q.reshape(*lead, t, g, n // g, d), -4, -2)
    return qh, jnp.moveaxis(k, -3, -2)


def kernel_path(q, k, d, cdt):
    return rope_pallas.rope_heads(q, k, (), d, THETA, None, cdt)


def operands(n, g, d, t=256, lead=(2,), seed=0):
    kq, kk = jax.random.split(jax.random.PRNGKey(seed))
    return (jax.random.normal(kq, (*lead, t, n * d), jnp.float32),
            jax.random.normal(kk, (*lead, t, g * d), jnp.float32))


def to_heads(x, heads, groups, d):
    """``[.., T, heads * d]`` in the kernels' layout, numpy."""
    lead, t = x.shape[:-2], x.shape[-2]
    x = np.moveaxis(x.reshape(*lead, t, groups, heads // groups, d), -4, -2)
    return x if heads > groups else x.reshape(*lead, groups, t, d)


def term_bound(x, heads, d, scale=1.0):
    """``|x| + |partner|``, elementwise over ``[.., T, heads * d]``: at least
    each of the rotation's two products and their sum. The two programs
    contract the multiply-adds differently, so they agree to one ulp of THIS,
    not of a result that the two products may cancel in."""
    x4 = np.abs(np.asarray(x, np.float32)).reshape(*x.shape[:-1], heads, d)
    return ((x4 + np.roll(x4, d // 2, -1)) * scale).reshape(x.shape)


def assert_one_ulp(got, want, bound):
    got, want = np.asarray(got, np.float32), np.asarray(want, np.float32)
    assert got.shape == want.shape
    worst = np.abs(got - want) / np.spacing(bound.astype(np.float32))
    assert worst.max() <= 1.0, worst.max()


def assert_equal_but_for_roundings(got, want, bound=None):
    """The standard in the compute dtype: equal, but for at most one element
    in 10,000, each by one ulp of that dtype (where the rotation's two
    products cancel: by one float32 ulp of their ``term_bound``)."""
    assert got.dtype == want.dtype and got.shape == want.shape
    a, b = np.asarray(got, np.float32), np.asarray(want, np.float32)
    off = a != b
    assert off.mean() <= 1e-4, off.mean()
    up = jnp.nextafter(jnp.abs(want), jnp.asarray(jnp.inf, want.dtype))
    ulp = np.asarray(up, np.float32) - np.abs(b)
    if bound is not None:
        ulp = np.maximum(ulp, np.spacing(bound.astype(np.float32)))
    assert np.all(np.abs(a - b)[off] <= ulp[off])


@pytest.mark.parametrize("layout", LAYOUTS)
def test_forward_in_float32_is_the_xla_path_to_one_ulp(layout):
    n, g, d = LAYOUTS[layout]
    q, k = operands(n, g, d)
    got = jax.jit(lambda q, k: kernel_path(q, k, d, jnp.float32))(q, k)
    want = jax.jit(lambda q, k: xla_path(q, k, n, g, d, jnp.float32))(q, k)
    assert got[0].shape == (2, g, n // g, 256, d) and got[1].shape == (2, g, 256, d)
    assert_one_ulp(got[0], want[0], to_heads(term_bound(q, n, d, d ** -0.5), n, g, d))
    assert_one_ulp(got[1], want[1], to_heads(term_bound(k, g, d), g, g, d))


@pytest.mark.parametrize("layout", LAYOUTS)
def test_forward_in_bfloat16_rounds_once(layout):
    """Equal to the path as the chip runs it but for one element in 10,000;
    within one bfloat16 ulp of the words as the CPU runs them (the keys, which
    have no scale, equal there too)."""
    n, g, d = LAYOUTS[layout]
    q, k = operands(n, g, d, seed=1)
    got = jax.jit(lambda q, k: kernel_path(q, k, d, jnp.bfloat16))(q, k)
    want = jax.jit(lambda q, k: xla_path(q, k, n, g, d, jnp.bfloat16))(q, k)
    for a, b in zip(got, want):
        assert_equal_but_for_roundings(a, b)
    words = jax.jit(lambda q, k: xla_path(q, k, n, g, d, jnp.bfloat16, False))(q, k)
    assert_equal_but_for_roundings(got[1], words[1])
    a, b = (np.asarray(x, np.float32) for x in (got[0], words[0]))
    assert np.all(np.abs(a - b) <= np.abs(b) * 2.0 ** -7)  # one ulp of 8 bits


def cotangents(n, g, d, cdt, t=256, lead=(2,)):
    kq, kk = jax.random.split(jax.random.PRNGKey(7))
    return (jax.random.normal(kq, (*lead, g, n // g, t, d), jnp.float32).astype(cdt),
            jax.random.normal(kk, (*lead, g, t, d), jnp.float32).astype(cdt))


def flat(dy):
    """The kernels' layout (``[.., G, R, T, d]`` or ``[.., G, T, d]``) back as
    ``[.., T, heads * d]``, numpy float32."""
    dy = np.asarray(dy, np.float32)
    if dy.ndim == 5:  # one leading axis and the grouped query heads
        dy = dy.reshape(dy.shape[0], -1, *dy.shape[-2:])
    dy = np.moveaxis(dy, -3, -2)
    return dy.reshape(*dy.shape[:-2], -1)


@pytest.mark.parametrize("layout", LAYOUTS)
def test_cotangents_in_float32_are_the_xla_paths_to_one_ulp(layout):
    n, g, d = LAYOUTS[layout]
    q, k = operands(n, g, d)
    grads = cotangents(n, g, d, jnp.float32)
    _, back = jax.vjp(lambda q, k: kernel_path(q, k, d, jnp.float32), q, k)
    _, want = jax.vjp(lambda q, k: xla_path(q, k, n, g, d, jnp.float32), q, k)
    (dq, dk), (wq, wk) = back(grads), want(grads)
    assert dq.dtype == dk.dtype == jnp.float32
    assert_one_ulp(dq, wq, term_bound(flat(grads[0]), n, d, d ** -0.5))
    assert_one_ulp(dk, wk, term_bound(flat(grads[1]), g, d))


@pytest.mark.parametrize("layout", LAYOUTS)
def test_cotangents_rounded_for_the_projections_backward(layout):
    """bfloat16 operands: the cotangent is written in bfloat16 (what the
    projections' backward matmuls round it to): the XLA path's float32
    cotangent, rounded, but for one element in 10,000."""
    n, g, d = LAYOUTS[layout]
    q, k = operands(n, g, d, seed=2)
    grads = cotangents(n, g, d, jnp.bfloat16)
    _, back = jax.vjp(lambda q, k: kernel_path(q, k, d, jnp.bfloat16), q, k)
    _, want = jax.vjp(lambda q, k: xla_path(q, k, n, g, d, jnp.bfloat16), q, k)
    bounds = (term_bound(flat(grads[0]), n, d, d ** -0.5),
              term_bound(flat(grads[1]), g, d))
    for a, b, bound in zip(back(grads), want(grads), bounds):
        assert a.dtype == jnp.float32  # the custom_vjp widens it again
        assert_equal_but_for_roundings(a.astype(jnp.bfloat16),
                                       b.astype(jnp.bfloat16), bound)


EPS = 1e-5


def normed_xla_path(q, k, wq, wk, n, g, d, cdt):
    """``xla_path`` behind Trinity's QK-norm (``rms_norm`` of every head)."""
    lead, t = q.shape[:-2], q.shape[-2]
    q = afmoe.rms_norm(q.reshape(*lead, t, n, d), wq, EPS).reshape(q.shape)
    k = afmoe.rms_norm(k.reshape(*lead, t, g, d), wk, EPS).reshape(k.shape)
    return xla_path(q, k, n, g, d, cdt)


def norm_weights(d):
    kq, kk = jax.random.split(jax.random.PRNGKey(11))
    return (1 + 0.1 * jax.random.normal(kq, (d,), jnp.float32),
            1 + 0.1 * jax.random.normal(kk, (d,), jnp.float32))


@pytest.mark.parametrize("cdt", [jnp.float32, jnp.bfloat16], ids=["f32", "bf16"])
def test_the_qk_norm_inside_the_kernel_is_rms_norms(cdt):
    """Trinity's layer hands over its RAW projections and the norms' weights:
    forward as ``rms_norm`` -> ``rotary`` -> cast -> ``moveaxis`` -> scale
    (float32: to float32 roundings of the normed head; bfloat16: but for one
    element in 10,000), the cotangents of q, k and both weights as
    ``jax.vjp`` of that path gives them."""
    n, g, d = LAYOUTS["trinity"]
    q, k = operands(n, g, d, seed=3)
    wq, wk = norm_weights(d)

    def kernel(q, k, wq, wk):
        return rope_pallas.rope_heads(q, k, (wq, wk), d, THETA, EPS, cdt)

    def xla(q, k, wq, wk):
        return normed_xla_path(q, k, wq, wk, n, g, d, cdt)

    (qh, kh), back = jax.vjp(kernel, q, k, wq, wk)
    (wqh, wkh), want = jax.vjp(xla, q, k, wq, wk)
    if cdt == jnp.float32:  # a normed head's entries are O(1), the queries' scaled
        assert np.abs(np.asarray(qh - wqh)).max() <= 4 * 2.0 ** -23 * d ** -0.5 * 4
        assert np.abs(np.asarray(kh - wkh)).max() <= 4 * 2.0 ** -23 * 4
    else:
        assert_equal_but_for_roundings(qh, wqh)
        assert_equal_but_for_roundings(kh, wkh)
    grads = cotangents(n, g, d, cdt)
    tol = 2.0 ** -20 if cdt == jnp.float32 else 2.0 ** -7  # a rounding of cdt
    for a, b in zip(back(grads), want(grads)):
        assert a.shape == b.shape and a.dtype == b.dtype == jnp.float32
        assert np.abs(np.asarray(a - b)).max() <= tol * np.abs(np.asarray(b)).max()


def test_under_the_trainers_site_fold():
    """``jax.vmap`` over two sites of a batch of one, forward and transpose:
    each site's result is its own call's, to the bit."""
    n, g, d = LAYOUTS["smallthinker"]
    q, k = operands(n, g, d, t=128, lead=(2, 1))
    grads = cotangents(n, g, d, jnp.bfloat16, t=128, lead=(2, 1))

    def both(q, k, dq, dk):
        out, back = jax.vjp(lambda q, k: kernel_path(q, k, d, jnp.bfloat16), q, k)
        return out, back((dq, dk))

    folded = jax.jit(jax.vmap(both))(q, k, *grads)
    for site in range(2):
        alone = jax.jit(both)(q[site], k[site], grads[0][site], grads[1][site])
        for a, b in zip(jax.tree.leaves(folded), jax.tree.leaves(alone)):
            assert np.array_equal(np.asarray(a[site], np.float32),
                                  np.asarray(b, np.float32))


def attention(gated, window=64, heads=(4, 2, 128)):
    n, g, d = heads
    return afmoe.Attention(n, g, d, window, THETA, 1e-5, 64, 128,
                           compute_dtype="bfloat16", qk_norm=gated, gate=gated)


def _rope_calls(fn, *args) -> list[str]:
    return [c for c in _kernel_calls(jax.make_jaxpr(fn)(*args).jaxpr)
            if c.startswith(rope_pallas.KERNEL_NAMES)]


@pytest.mark.parametrize("gated", [False, True], ids=["smallthinker", "trinity"])
def test_a_sliding_layer_under_the_blocks_checkpoint(monkeypatch, gated):
    """The whole attention layer (without and with the QK-norm, which then
    runs inside the kernel) under ``jax.checkpoint(policy=BLOCK_KEEPS)`` and
    the site fold, with and without the new path: one forward call in the
    forward pass, one in the recomputation, one transposed call; the output
    and every parameter's gradient, the norms' weights among them, as the old
    instructions give them, to the roundings of bfloat16 operands."""
    monkeypatch.setattr(afmoe, "_auto_pallas", lambda: True)
    layer = attention(gated)
    a = jax.random.normal(jax.random.PRNGKey(3), (2, 1, 128, 64), jnp.float32)
    params = layer.init(jax.random.PRNGKey(4), a[0])

    def grad():  # traced anew each time: jax keeps a function's jaxpr
        def loss(p, a):
            run = jax.checkpoint(lambda p, a: layer.apply(p, a),
                                 policy=afmoe.BLOCK_KEEPS)
            return jnp.sum(jax.vmap(run, in_axes=(None, 0))(p, a) ** 2)

        return jax.value_and_grad(loss, argnums=(0, 1))

    calls = _rope_calls(grad(), params, a)
    assert sorted(calls) == [rope_pallas.ROPE_BWD] + [rope_pallas.ROPE_FWD] * 2
    new = jax.jit(grad())(params, a)
    monkeypatch.setattr(rope_pallas, "rope_block", lambda *a: None)
    assert _rope_calls(grad(), params, a) == []
    old = jax.jit(grad())(params, a)
    # the CPU's matmuls take the old path's float32 cotangent as it is where
    # the chip's round it to bfloat16 first, as the new path does for both
    for x, y in zip(jax.tree.leaves(new), jax.tree.leaves(old)):
        x, y = np.asarray(x, np.float32), np.asarray(y, np.float32)
        assert np.abs(x - y).max() <= 2 ** -7 * np.abs(y).max()


@pytest.mark.parametrize("shape,rows", [
    # (positions, query heads a key-value head, head width, dtype, QK-norm)
    ((16384, 7, 128, jnp.bfloat16), 512),  # smallthinker-21b-ep8.dsgd-fold2-long
    ((8192, 8, 128, jnp.bfloat16, True), 512),  # trinity-mini-ep16.dsgd-fold2
    ((8192, 8, 128, jnp.float32), 512),  # float32 operands: 10.0 MiB
    ((8192, 8, 128, jnp.float32, True), 256),  # and the raw rows again: 15.0
    ((8192, 8, 256, jnp.bfloat16), 256),  # 512 rows would hold 15.5 MiB
    ((8192, 16, 256, jnp.float32), 128),
    ((1536, 7, 128, jnp.bfloat16), 512), ((768, 7, 128, jnp.bfloat16), 256),
    ((640, 7, 128, jnp.bfloat16), 128), ((128, 7, 128, jnp.bfloat16), 128),
    ((8192, 1, 64, jnp.bfloat16), None),  # half a lane tile: not taken
    ((8192, 20, 192, jnp.bfloat16), None),
    ((200, 7, 128, jnp.bfloat16), None),  # no block of rows divides it
])
def test_rope_blocks_at_the_cells_shapes(shape, rows):
    """The counter of a static chooser is its answer (as
    ``test_attention_blocks_at_the_cells_shapes``): a later PR that moves a
    cell's block moves this table knowingly."""
    assert rope_pallas.rope_block(*shape) == rows
    if rows:
        assert rope_pallas.block_vmem_bytes(rows, *shape[1:]) <= rope_pallas.VMEM_BUDGET


def test_block_vmem_bytes_counts_what_a_step_holds():
    # 512 rows of seven query heads and one key head of 128, two float32
    # tables, all twice: float32 in and bfloat16 out (the forward call) ...
    assert rope_pallas.block_vmem_bytes(512, 7, 128, jnp.bfloat16) == 2 * (
        512 * 8 * 128 * (4 + 2) + 2 * 512 * 128 * 4)
    # ... and with the norm inside the backward call is the larger: bfloat16
    # in and out and the float32 raw rows
    assert rope_pallas.block_vmem_bytes(512, 8, 128, jnp.bfloat16, True) == 2 * (
        512 * 9 * 128 * (2 + 2 + 4) + 2 * 512 * 128 * 4)


def test_tables_are_rotarys_own():
    """``x * cos + roll(x, d / 2) * sin`` with the kernel's tables is
    ``rotary(x)`` to the bit (the same angles, the sign moved into the sine)."""
    x = jax.random.normal(jax.random.PRNGKey(5), (64, 3, 128), jnp.float32)
    cos, sin = rope_pallas.rope_tables(64, 128, THETA)
    got = x * cos[:, None] + jnp.roll(x, 64, -1) * sin[:, None]
    assert np.array_equal(got, afmoe.rotary(x, jnp.arange(64), THETA))


# -- the dispatch rule: by what the call can see ---------------------------------


def test_the_sliding_layer_at_whole_lane_tiles_takes_the_kernel(monkeypatch):
    layer = attention(gated=False)
    a = jnp.zeros((1, 128, 64))
    params = jax.eval_shape(layer.init, jax.random.PRNGKey(0), a)
    assert _rope_calls(layer.apply, params, a) == []  # a CPU: the XLA blocks
    monkeypatch.setattr(afmoe, "_auto_pallas", lambda: True)
    assert _rope_calls(layer.apply, params, a) == [rope_pallas.ROPE_FWD]


@pytest.mark.parametrize("case", ["no-positions", "head-dim-64", "ragged-length",
                                  "latent-attention"])
def test_everything_else_runs_the_old_instructions(monkeypatch, case):
    """A full layer without positions, a head of half a lane tile, a length
    that is no whole kernel block, and latent attention's 64-of-256 slice:
    no Pallas call of the new names in the jaxpr, on the kernel path too."""
    monkeypatch.setattr(afmoe, "_auto_pallas", lambda: True)
    t = 128
    if case == "no-positions":
        layer = attention(gated=False, window=None)
    elif case == "head-dim-64":
        layer = attention(gated=True, heads=(4, 2, 64))
    elif case == "ragged-length":
        layer, t = attention(gated=False), 640  # the XLA blocks: no kernel
    else:
        layer = afmoe.LatentAttention(
            num_heads=2, q_lora_rank=32, kv_lora_rank=32, qk_nope_head_dim=192,
            qk_rope_head_dim=64, v_head_dim=256, rope_theta=THETA, eps=1e-5,
            q_block=64, kv_chunk=128, compute_dtype="bfloat16")
    a = jnp.zeros((1, t, 64))
    params = jax.eval_shape(layer.init, jax.random.PRNGKey(0), a)
    grad = jax.grad(lambda p, a: layer.apply(p, a).sum())
    calls = _kernel_calls(jax.make_jaxpr(grad)(params, a).jaxpr)
    assert all(c.startswith("splash_mqa") for c in calls), calls
    assert bool(calls) == (case != "ragged-length")
