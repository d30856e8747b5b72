"""Rotary positions and the hand-over to the attention kernels as one pass.

A layer with rotary positions whose attention runs as the splash-attention
kernels (``models/afmoe.py splash_heads``) needs its queries and keys rotated
in float32, the queries scaled by ``d ** -0.5``, both rounded to the compute
dtype, and all of it in the kernels' layout: ``[G, R, T, d]`` (``R`` query
heads a key-value head) and ``[G, T, d]``, where the projection leaves ``[T,
heads * d]``. Written as ``rotary`` -> cast -> ``moveaxis`` -> scale the TPU
compiler makes five passes over float32 copies of the queries on the way in
and five on the way back: a 64-lane half of a 128-lane head is a relayout to
it however the rotation is worded (PERF.md section 6, PR 37). Here the partner
of lane ``i`` is one lane roll away (``pltpu.roll`` by ``d / 2``: lane ``i +-
d/2`` either way), and the kernels' layout is the output's block shape: each
tensor is read once and written once.

One call serves a layer's queries AND keys: grid ``(rows / tb, G)``, rows
outermost so that the two ``[tb, d]`` table blocks (cosine, signed sine) stay
put while the key-value heads go by; a step carries one key-value head's group
of query heads (``[tb, R * d]`` float32, seven or eight heads of 128 at the
cells) and that head's keys (``[tb, d]``). A grid step costs 0.36-0.75 us
whatever it carries (PERF.md section 6, PRs 31, 33), so the step is a whole
group and not a head. Leading axes (the batch, the trainer's site fold) are
``vmap``s: each prepends a grid axis.

The rotation is linear and orthogonal: its transpose is the same kernel with
the sine's sign turned. The backward call reads the splash kernels' ``dq`` /
``dk`` in their layout, scales and rotates back in float32 and writes ``[T,
heads * d]`` in the compute dtype: the projections' backward matmuls, which
consume it, round it to that themselves, so no float32 copy of it is ever
whole; ``custom_vjp`` widens it.

A layer with a QK-norm (an ``rms_norm`` of every head before the rotation)
hands the kernel its RAW projections and the two ``[d]`` weights, and the norm
runs inside, forward and transpose: between the projection and this kernel the
compiler lays a normed ``[T, heads, d]`` out head-major for its reduction and
relayouts all of it in float32 for any consumer that wants rows (PERF.md
section 6, PR 37). The weights' cotangents are summed over the grid in
``[1, d]`` output blocks that stay in VMEM.

Same mathematics and roundings as the XLA path AS THE CHIP RUNS IT: float32
norm and rotation in ``rms_norm``'s and ``rotary``'s order, the scale by the
compute dtype's ``d ** -0.5``, ONE rounding. (``kernel_attention`` words it
as a rounding and then a multiply in the compute dtype; the TPU compiler keeps
the float32 between the two, ``xla_allow_excess_precision``, and so does the
kernel: on the chip it agrees with that path to the bit in the keys and to
one element in 10,000 in the queries, where a second rounding in between
moved 27 % of them by an ulp. PERF.md section 6, PR 37.) The norm's transpose
is worded from its derivative, so it agrees with autodiff's to float32
roundings, not to the bit. ``rotary()`` and the XLA path stay for
every caller that does not meet :func:`rope_block`'s conditions.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

# Stable kernel names, as ``lstm_pallas.LSTM_FWD`` / ``LSTM_BWD``: the TPU
# compiler names the Mosaic call's instruction after them (``%rope_fwd.3``;
# under transforms ``%vmap_jvp_rope_fwd__.6``), which is what
# benchmarks/layer_metrics/rotary_kernel_ms_per_round.json matches.
ROPE_FWD = "rope_fwd"
ROPE_BWD = "rope_bwd"
KERNEL_NAMES = (ROPE_FWD, ROPE_BWD)

LANES = 128  # a head the kernel takes is a whole number of lane tiles
BLOCK_ROWS = (512, 256, 128)  # a grid step's rows: the largest that fits
# What one grid step may hold, as ``block_vmem_bytes`` counts it: 14 of the 16
# MiB the compiler scopes a kernel to; no ``vmem_limit_bytes`` (PERF.md
# section 6, PR 31: XLA uses the VMEM a kernel's scope leaves free).
VMEM_BUDGET = 14 << 20


def _interpret() -> bool:
    return jax.default_backend() == "cpu"


def block_vmem_bytes(tb: int, heads_per_kv: int, head_dim: int, out_dtype,
                     norm: bool = False) -> int:
    """VMEM one grid step of ``tb`` rows holds, the larger of the two calls:
    a group's queries and one head's keys in float32 and in ``out_dtype``
    (forward), or twice in ``out_dtype`` plus, with the QK-norm inside, the
    float32 raw projections again (backward); and both tables; each twice
    (the pipeline's double buffer)."""
    out = jnp.dtype(out_dtype).itemsize
    item = max(4 + out, 2 * out + (4 if norm else 0))
    heads = heads_per_kv + 1  # the group's queries and the head's keys
    return 2 * tb * head_dim * (heads * item + 2 * 4)


def rope_block(t: int, heads_per_kv: int, head_dim: int, out_dtype,
               norm: bool = False) -> int | None:
    """Rows of one grid step, or None where the kernel does not take the
    call: ``head_dim`` is no whole number of lane tiles, or no block of
    BLOCK_ROWS divides ``t`` and fits. A pure function of the call's shape:
    512 at both cells (seven heads of 128 in bfloat16: 7.0 MiB; eight with
    the norm inside: 10.0), 256 for eight heads of 256."""
    if head_dim % LANES:
        return None
    return next((tb for tb in BLOCK_ROWS if t % tb == 0 and block_vmem_bytes(
        tb, heads_per_kv, head_dim, out_dtype, norm) <= VMEM_BUDGET),
        None)


def rope_tables(t: int, head_dim: int, theta: float):
    """``(cos, sin) [t, head_dim]`` float32 of positions ``0 .. t - 1``, the
    sine signed for the roll: ``rotary(x) = x * cos + roll(x, d / 2) * sin``
    (rotate-half pairing: the first half's partner enters negated)."""
    inv = 1.0 / (theta ** (jnp.arange(0, head_dim, 2, dtype=jnp.float32) / head_dim))
    ang = jnp.arange(t).astype(jnp.float32)[:, None] * inv[None, :]
    cos, sin = jnp.cos(ang), jnp.sin(ang)
    return (jnp.concatenate([cos, cos], -1), jnp.concatenate([-sin, sin], -1))


def _scale(head_dim: int, dtype) -> float:
    """``head_dim ** -0.5`` as ``dtype`` holds it: the constant the queries
    are scaled by in ``dtype``'s arithmetic."""
    # a Python number and a dtype: static, rounded on the host
    return float(np.asarray(head_dim ** -0.5, jnp.dtype(dtype)))  # jaxlint: disable=R005


def _turn(x, cos, sin):
    return x * cos + pltpu.roll(x, x.shape[-1] // 2, 1) * sin


def _rsqrt_var(x, eps: float):
    """``models/afmoe.py rms_norm``'s statistic over a head's lanes."""
    return jax.lax.rsqrt(jnp.mean(jnp.square(x), axis=-1, keepdims=True) + eps)


def _fwd_kernel(scale, eps, q, k, cos, sin, *refs):
    # q [tb, R * d], k [tb, d] float32 -> qo [R, tb, d], ko [tb, d]; with the
    # QK-norm inside (``eps``), its two [1, d] weights come before the outputs
    *weights, qo, ko = refs
    d = k.shape[-1]
    c, s = cos[...], sin[...]

    def head(x, w=None):
        if w is not None:  # rms_norm, in its order of operations
            x = x * _rsqrt_var(x, eps) * w[...]
        return _turn(x, c, s)

    for h in range(qo.shape[0]):
        qo[h] = (head(q[:, h * d:(h + 1) * d], *weights[:1]) * scale).astype(qo.dtype)
    ko[...] = head(k[...], *weights[1:]).astype(ko.dtype)


def _bwd_kernel(scale, eps, dq, dk, cos, sin, *refs):
    # the transpose (the rotation with the sine's sign turned): dq [R, tb, d],
    # dk [tb, d] -> dxq [tb, R * d], dxk [tb, d]. With the QK-norm inside, the
    # raw q and k and the two weights come in too, and the weights' cotangents
    # [1, d] go out, summed over every step of the grid
    d = dk.shape[-1]
    c, s = cos[...], -sin[...]
    if len(refs) == 2:
        (dxq, dxk), q_norm, k_norm = refs, (), ()
    else:
        q, k, wq, wk, dxq, dxk, dwq, dwk = refs
        q_norm, k_norm = (q, wq, dwq), (k, wk, dwk)

        @pl.when((pl.program_id(0) == 0) & (pl.program_id(1) == 0))
        def _():
            dwq[...] = jnp.zeros_like(dwq)
            dwk[...] = jnp.zeros_like(dwk)

    def head(g, cols, x=None, w=None, dw=None):
        g = _turn(g, c, s)
        if x is None:
            return g
        # y = xhat * w, xhat = x * r: the norm's own transpose
        r = _rsqrt_var(x[:, cols], eps)
        xhat = x[:, cols] * r
        dw[...] += jnp.sum(g * xhat, axis=0, keepdims=True)
        g = g * w[...]
        return r * (g - xhat * jnp.mean(g * xhat, axis=-1, keepdims=True))

    for h in range(dq.shape[0]):
        cols = slice(h * d, (h + 1) * d)
        g = dq[h].astype(jnp.float32) * scale
        dxq[:, cols] = head(g, cols, *q_norm).astype(dxq.dtype)
    dxk[...] = head(dk[...].astype(jnp.float32), slice(None),
                    *k_norm).astype(dxk.dtype)


def _specs(tb: int, heads_per_kv: int, head_dim: int):
    """Block specs over the grid ``(rows / tb, G)``: the projections' side
    ``[T, G * R * d]`` / ``[T, G * d]``, the attention kernels' side ``[G, R,
    T, d]`` / ``[G, T, d]``, a ``[T, d]`` table and a ``[1, d]`` weight."""
    d, r = head_dim, heads_per_kv

    def vmem(shape, index):
        return pl.BlockSpec(shape, index, memory_space=pltpu.VMEM)

    flat = [vmem((tb, r * d), lambda i, g: (i, g)),
            vmem((tb, d), lambda i, g: (i, g))]
    heads = [vmem((None, r, tb, d), lambda i, g: (g, 0, i, 0)),
             vmem((None, tb, d), lambda i, g: (g, i, 0))]
    return (flat, heads, vmem((tb, d), lambda i, g: (i, 0)),
            vmem((1, d), lambda i, g: (0, 0)))


def _over_leading(fn, leading: int):
    """``fn`` of one sequence's operands, mapped over ``leading`` axes."""
    for _ in range(leading):
        fn = jax.vmap(fn)
    return fn


def _fwd_call(q, k, weights, head_dim, theta, eps, out_dtype):
    t, d = q.shape[-2], head_dim
    groups = k.shape[-1] // d
    r = q.shape[-1] // (groups * d)
    tb = rope_block(t, r, d, out_dtype, len(weights) > 0)
    flat, heads, table, weight = _specs(tb, r, d)
    call = pl.pallas_call(
        functools.partial(_fwd_kernel, _scale(d, out_dtype), eps),
        grid=(t // tb, groups),
        in_specs=[*flat, table, table] + [weight] * len(weights),
        out_specs=heads,
        out_shape=[jax.ShapeDtypeStruct((groups, r, t, d), out_dtype),
                   jax.ShapeDtypeStruct((groups, t, d), out_dtype)],
        interpret=_interpret(),
        name=ROPE_FWD,
    )
    cos, sin = rope_tables(t, d, theta)
    weights = [w.astype(jnp.float32).reshape(1, d) for w in weights]
    return _over_leading(lambda a, b: call(a, b, cos, sin, *weights),
                         q.ndim - 2)(q, k)


def _bwd_call(dq, dk, raw, weights, theta, eps):
    (groups, r, t, d) = dq.shape[-4:]
    norm = [jax.ShapeDtypeStruct((1, d), jnp.float32)] * len(weights)
    tb = rope_block(t, r, d, dq.dtype, len(weights) > 0)
    flat, heads, table, weight = _specs(tb, r, d)
    call = pl.pallas_call(
        functools.partial(_bwd_kernel, _scale(d, dq.dtype), eps),
        grid=(t // tb, groups),
        in_specs=[*heads, table, table] + (flat if raw else []) + [weight] * len(norm),
        out_specs=flat + [weight] * len(norm),
        out_shape=[jax.ShapeDtypeStruct((t, groups * r * d), dq.dtype),
                   jax.ShapeDtypeStruct((t, groups * d), dq.dtype), *norm],
        interpret=_interpret(),
        name=ROPE_BWD,
    )
    cos, sin = rope_tables(t, d, theta)
    weights = [w.astype(jnp.float32).reshape(1, d) for w in weights]
    return _over_leading(lambda *a: call(*a[:2], cos, sin, *a[2:], *weights),
                         dk.ndim - 3)(dq, dk, *raw)


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6))
def rope_heads(q, k, weights, head_dim: int, theta: float, eps, out_dtype):
    """Queries ``q [.., T, G * R * d]`` and keys ``k [.., T, G * d]`` (float32,
    as projections with operands in ``out_dtype`` leave them; ``d =
    head_dim``) at positions ``0 .. T - 1`` -> ``(qh [.., G, R, T, d], kh [..,
    G, T, d])`` in ``out_dtype``: rotated in float32, the queries scaled by
    ``out_dtype``'s ``d ** -0.5``, rounded once — the operands ``splash_mqa_*``
    takes. With ``weights = (wq, wk) [d]`` and ``eps`` every head is first
    ``rms_norm``ed over its lanes (the QK-norm); ``weights = ()`` without.
    The cotangents of ``q`` and ``k`` come back through the transposed kernel
    rounded to ``out_dtype``: what their consumer, the projections' backward
    matmuls, rounds them to itself. The call must satisfy :func:`rope_block`."""
    return _fwd_call(q, k, weights, head_dim, theta, eps, out_dtype)


def _rope_heads_fwd(q, k, weights, head_dim, theta, eps, out_dtype):
    assert q.dtype == k.dtype == jnp.float32, (q.dtype, k.dtype)
    out = _fwd_call(q, k, weights, head_dim, theta, eps, out_dtype)
    # the norm's transpose reads the raw projections again; rotary's nothing
    return out, ((q, k) if weights else (), weights)


def _rope_heads_bwd(head_dim, theta, eps, out_dtype, res, grads):
    raw, weights = res
    dq, dk, *dws = _bwd_call(*grads, raw, weights, theta, eps)
    # a weight's cotangent: one [1, d] sum a sequence, summed over the batch
    dws = tuple(dw.reshape(-1, dw.shape[-1]).sum(0).astype(w.dtype)
                for dw, w in zip(dws, weights))
    return dq.astype(jnp.float32), dk.astype(jnp.float32), dws


rope_heads.defvjp(_rope_heads_fwd, _rope_heads_bwd)
