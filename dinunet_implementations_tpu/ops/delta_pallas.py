"""The gated delta rule's chunk loop as one Pallas kernel each way.

``models/afmoe.py chunked_delta_rule`` in XLA runs a ``lax.scan`` over the
chunks of a sequence whose carry is the float32 ``[d_k, d_v]`` state of every
value head: each step slices its inputs out of HBM stacks, does two small
products and writes the state and ``V'`` back, and JAX differentiates the
scan by stacking every step (PERF.md section 5: the chunk loop's per-step
work, not its FLOPs, took a third of the Qwen3-Next cell's round). Here the
loop is the grid's last axis, walked in order, and the state lives in VMEM
scratch across a sequence's chunks; the arithmetic of a chunk is
``chunked_delta_rule``'s, written out for one head.

**Forward** (``DELTA_FWD``): grid ``(B, H / hb, chunks)``; a step holds one
sequence's chunk of ``hb`` value heads, reads ``q`` and ``k`` as the 128-lane
column blocks of ``[B, T, K * d_k]`` that those heads read (value head ``h``
reads key head ``h // (H / K)``: no head is repeated in HBM), ``v`` and writes
``o`` as column blocks of ``[B, T, H * d_v]``, and takes the gates and their
chunk-wise cumulative sums as ``[B, chunks, C, H]`` (all heads: a step picks
its own by a one-hot product). At every segment's first chunk it writes the
state it starts from, the backward pass's residual (the states the XLA path's
checkpoint keeps).

**Backward** (``DELTA_BWD``): grid ``(B, H / hb, segments * 2 * segment)``,
the segments in reverse; a segment's first ``segment`` steps rebuild its chunk
states from the edge state into VMEM scratch, and keep each chunk's triangular
inverse there too; the next ``segment`` walk its chunks backwards carrying
``dS`` in VMEM and write the cotangents of ``q``, ``k`` (summed over the value
heads that read a key head), ``v``, ``beta`` and the cumulative gates; XLA
turns the last into the gates' cotangent (a reverse cumulative sum). The
forward's output and edge states carry a checkpoint name, so that a block's
checkpoint that keeps them never runs the forward kernel twice. The blocked algorithm is the WY form of flash-linear-attention's
``chunk_gated_delta_rule`` (a state pass forward, a ``dS`` pass backward); the
arithmetic is this repository's: every exponent a difference ``G_i - G_j <=
0`` masked before ``exp``, no ratio of two decays, and the triangular inverse
by forward substitution on 16-row diagonal blocks merged pairwise (never a
power series, whose terms grow where a chunk's keys are alike).

**Precision**, as the XLA path's: ``k k^T``, the inverse, ``U``, ``W``, the
state's two products and every product of the ``dS`` chain are float32 at
HIGHEST; the output's three products and their transposes take ``cdt``
operands with float32 accumulation. The state is never rounded.

A grid step holds ``hb`` heads (:func:`delta_block`) so that its fixed cost
and the serial latency of the state's products are paid once for the block;
leading axes (the trainer's site fold) are ``vmap``s, each a grid axis.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.ad_checkpoint import checkpoint_name
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

# Stable kernel names, as ``lstm_pallas.LSTM_FWD`` / ``LSTM_BWD``: the TPU
# compiler names the Mosaic call's instruction after them (``%delta_fwd.3``;
# under transforms ``%vmap_delta_fwd_.2``), which is what
# benchmarks/layer_metrics/qwen3_next_delta_kernel_ms_per_round.json matches.
DELTA_FWD = "delta_fwd"
DELTA_BWD = "delta_bwd"
KERNEL_NAMES = (DELTA_FWD, DELTA_BWD)

CHUNK = 64  # positions a chunk holds: the kernels take no other
LANES = 128  # a head the kernels take is a whole number of lane tiles
SUBSTITUTION_ROWS = 16  # the diagonal blocks the inverse solves row by row
HEAD_BLOCKS = (8, 4, 2, 1)  # value heads a grid step: the largest that fits
# What one grid step may hold, as ``block_vmem_bytes`` counts it: 14 of the 16
# MiB the compiler scopes a kernel to; no ``vmem_limit_bytes`` (XLA uses the
# VMEM a kernel's scope leaves free, PERF.md section 6).
VMEM_BUDGET = 14 << 20
# the body's own temporaries: at eight heads a step the compiler's scoped
# stack for the backward kernel held 5 MB more than the blocks and scratch
WORK_BYTES = 5 << 20
_HIGHEST = jax.lax.Precision.HIGHEST


def _interpret() -> bool:
    return jax.default_backend() == "cpu"


def _tile(n: int, to: int) -> int:
    return -(-n // to) * to


def _segment(t: int, segment: int) -> int:
    """Chunks a segment holds: ``segment``, or all ``t`` has if fewer."""
    return min(segment, -(-t // CHUNK))


def block_vmem_bytes(hb: int, heads: int, key_heads: int, d_k: int, d_v: int,
                     segment: int) -> int:
    """VMEM one grid step of ``hb`` value heads holds, the larger of the two
    calls: the blocks it streams, twice (the pipeline's double buffer), its
    scratch and ``WORK_BYTES``. Forward: ``q``, ``k``, ``v``, ``o``, the
    gates of every head, the edge state; scratch one state a head. Backward:
    ``q``, ``k``, ``v``, ``dO`` and the cotangents of the four, the gates in
    and out, the edge state; scratch ``segment`` states, their chunks'
    ``[C, C]`` inverses and ``dS`` a head."""
    kb = hb * key_heads // heads  # key heads the block reads
    col = CHUNK * kb * d_k * 4  # a [C, kb * d_k] float32 block
    val = CHUNK * hb * d_v * 4
    gates = CHUNK * _tile(heads, LANES) * 4  # [C, H], lanes padded
    rows = _tile(hb, 8) * LANES * 4  # [hb, C], padded to whole tiles
    state = hb * d_k * d_v * 4
    fwd = 2 * (2 * col + 2 * val + 2 * gates + state) + state
    bwd = (2 * (4 * col + 3 * val + 2 * gates + 2 * rows + state)
           + (segment + 1) * state + segment * hb * CHUNK * CHUNK * 4)
    return max(fwd, bwd) + WORK_BYTES


def delta_block(t: int, heads: int, key_heads: int, d_k: int, d_v: int,
                segment: int) -> int | None:
    """Value heads a grid step holds, or None where the kernels do not take
    the call: a head is no whole number of lane tiles, the value heads are no
    whole number of groups of the key heads, or no block of HEAD_BLOCKS holds
    whole groups and fits. A pure function of the call's shape, the chooser
    and the dispatch predicate at once: 4 at the Qwen3-Next cell (32 value
    heads, 16 key heads of 128, segments of 16 chunks), 8 where a sequence
    is one segment of 4 chunks. ``segment`` is the chunks of one segment,
    as many as ``t`` has up to that."""
    if d_k % LANES or d_v % LANES or heads % key_heads or t < 1:
        return None
    per, segment = heads // key_heads, _segment(t, segment)
    return next((hb for hb in HEAD_BLOCKS
                 if heads % hb == 0 and hb % per == 0
                 and block_vmem_bytes(hb, heads, key_heads, d_k, d_v, segment)
                 <= VMEM_BUDGET), None)


# -- one chunk, one head ---------------------------------------------------------


def _dot(a, b, precision=_HIGHEST):  # a @ b
    return jax.lax.dot_general(a, b, (((1,), (0,)), ((), ())),
                               precision=precision,
                               preferred_element_type=jnp.float32)


def _dot_nt(a, b, precision=_HIGHEST):  # a @ b^T
    return jax.lax.dot_general(a, b, (((1,), (1,)), ((), ())),
                               precision=precision,
                               preferred_element_type=jnp.float32)


def _dot_tn(a, b, precision=_HIGHEST):  # a^T @ b
    return jax.lax.dot_general(a, b, (((0,), (0,)), ((), ())),
                               precision=precision,
                               preferred_element_type=jnp.float32)


def _unit_lower_inverse(a, rows, cols):
    """``(I + a)^-1`` of strictly lower triangular ``a [n, n]`` float32, as
    ``models/afmoe.py unit_lower_inverse``: forward substitution on the
    diagonal blocks of SUBSTITUTION_ROWS rows (all blocks at once, a row of
    each a step: ``X[i] -= a[i, r] X[r]`` below row ``r`` of its block), then
    pairs of blocks merged, ``X <- X - X a' X`` with ``a'`` the lower-left
    quadrant of each doubled diagonal block."""
    n = a.shape[-1]
    m = min(SUBSTITUTION_ROWS, n)
    x = (rows == cols).astype(jnp.float32)
    for r in range(m - 1):
        # column r of each diagonal block, across its rows' lanes
        c = jnp.concatenate([jnp.broadcast_to(a[b:b + m, b + r:b + r + 1], (m, n))
                             for b in range(0, n, m)], axis=0)
        x3 = x.reshape(n // m, m, n)
        x = (x3 - c.reshape(n // m, m, n) * x3[:, r:r + 1, :]).reshape(n, n)
    size = m
    while size < n:
        lower_left = (((rows & -2 * size) == (cols & -2 * size))
                      & ((rows & size) != 0) & ((cols & size) == 0))
        x = x - _dot(x, _dot(jnp.where(lower_left, a, 0.0), x))
        size *= 2
    return x


class _Chunk:
    """What one head's chunk computes without the state it starts from:
    the masked exponents, ``A``, its inverse ``T`` (or the one given),
    ``beta v`` and ``beta e k``, ``[U | W]`` and the decays (``e =
    exp(G)``, ``f = exp(G_C - G)``, ``exp(G_C)``). ``gam`` is the cumulative
    log-gate as a column ``[C, 1]``, ``gam_row`` the same as a row ``[1,
    C]``."""

    def __init__(self, kk, k, v, beta, gam, gam_row, rows, cols, t=None):
        n = k.shape[0]
        self.diff = gam - gam_row
        self.strict = jnp.exp(jnp.where(rows > cols, self.diff, -jnp.inf))
        self.kks = kk * self.strict
        self.a = beta * self.kks
        self.t = _unit_lower_inverse(self.a, rows, cols) if t is None else t
        self.e = jnp.exp(gam)
        self.bvk = jnp.concatenate([v * beta, k * (beta * self.e)], axis=1)
        self.uw = _dot(self.t, self.bvk)
        self.last = gam_row[:, n - 1:n]  # [1, 1]
        self.f = jnp.exp(self.last - gam)
        self.dv = v.shape[-1]

    def vn(self, s):
        """``V' = U - W S``."""
        return self.uw[:, :self.dv] - _dot(self.uw[:, self.dv:], s)

    def decay(self, s):
        """``exp(G_C) S`` (the scalar made a column first: Mosaic does not
        broadcast ``[1, 1]`` both ways at once)."""
        return jnp.exp(self.last + jnp.zeros((s.shape[0], 1), jnp.float32)) * s

    def state_after(self, k_t, s, vn):
        """``exp(G_C) S + (k f)^T V'`` as ``k^T (f V')``: ``k_t`` is ``k``
        transposed once for the value heads that read it."""
        return self.decay(s) + _dot(k_t, vn * self.f)

    def causal(self, rows, cols):
        return jnp.exp(jnp.where(rows >= cols, self.diff, -jnp.inf))


def _cast(cdt):
    return (lambda x: x) if cdt is None else (lambda x: x.astype(cdt))


def _gates(b_ref, g_ref, hb, first):
    """The block's ``beta`` and cumulative gates as columns ``[C, hb]`` (from
    every head's ``[C, H]`` by a one-hot product: the block's ``first`` head
    is a grid index) and the gates as rows ``[hb, C]`` (a transposing product
    with the identity)."""
    n, heads = g_ref.shape
    pick = (jax.lax.broadcasted_iota(jnp.int32, (heads, hb), 0)
            == jax.lax.broadcasted_iota(jnp.int32, (heads, hb), 1) + first
            ).astype(jnp.float32)
    beta, gam = _dot(b_ref[...], pick), _dot(g_ref[...], pick)
    return beta, gam, _dot_tn(gam, _eye(n))


def _eye(n):
    return (jax.lax.broadcasted_iota(jnp.int32, (n, n), 0)
            == jax.lax.broadcasted_iota(jnp.int32, (n, n), 1)).astype(jnp.float32)


def _iotas(n):
    return (jax.lax.broadcasted_iota(jnp.int32, (n, n), 0),
            jax.lax.broadcasted_iota(jnp.int32, (n, n), 1))


def _groups(q_ref, k_ref, dk, per, with_q=True):
    """``(j, q, k, k^T, k k^T, value heads)`` of each key head in the block."""
    for j in range(k_ref.shape[1] // dk):
        k = k_ref[:, j * dk:(j + 1) * dk]
        q = q_ref[:, j * dk:(j + 1) * dk] if with_q else None
        yield j, q, k, k.T, _dot_nt(k, k), range(j * per, (j + 1) * per)


# -- the kernels ---------------------------------------------------------------
#
# Each body reads every head's state before it writes anything and writes
# every head's results at its end: a store to a ref between two heads would
# order the second head's loads after it (8 % of the backward kernel's time
# on a v5e).


def _fwd_kernel(cdt, segment, q_ref, k_ref, v_ref, b_ref, g_ref, o_ref,
                edge_ref, s_ref):
    # q, k [C, kb * dk]; v, o [C, hb * dv]; b, g [C, H]; edge, s [hb, dk, dv]
    c = pl.program_id(2)
    hb, dk, dv = s_ref.shape
    n, per = q_ref.shape[0], hb // (q_ref.shape[1] // dk)
    cast = _cast(cdt)

    @pl.when(c == 0)
    def _():
        s_ref[...] = jnp.zeros_like(s_ref)

    @pl.when(c % segment == 0)
    def _():
        edge_ref[...] = s_ref[...]

    rows, cols = _iotas(n)
    beta, gam, gam_t = _gates(b_ref, g_ref, hb, pl.program_id(1) * hb)
    states, outs = [s_ref[h] for h in range(hb)], []
    for _, q, k, k_t, kk, heads in _groups(q_ref, k_ref, dk, per):
        qk = _dot_nt(cast(q), cast(k), None)
        for h in heads:
            ch = _Chunk(kk, k, v_ref[:, h * dv:(h + 1) * dv], beta[:, h:h + 1],
                        gam[:, h:h + 1], gam_t[h:h + 1, :], rows, cols)
            vn = ch.vn(states[h])
            outs.append(_dot(cast(q * ch.e), cast(states[h]), None)
                        + _dot(cast(qk * ch.causal(rows, cols)), cast(vn), None))
            states[h] = ch.state_after(k_t, states[h], vn)
    o_ref[...] = jnp.concatenate(outs, axis=1)
    s_ref[...] = jnp.stack(states)


def _bwd_kernel(cdt, segment, q_ref, k_ref, v_ref, b_ref, g_ref, edge_ref,
                do_ref, dq_ref, dk_ref, dv_ref, db_ref, dg_ref, states_ref,
                ds_ref, t_ref):
    # q, k, dq, dk [C, kb * dk]; v, do, dv [C, hb * dv]; b, g [C, H]; db, dg
    # [hb, C]; edge [hb, dk, dv]; states [segment, hb, dk, dv]; ds [hb, dk,
    # dv]; t [segment, hb, C, C], the chunks' inverses
    step, first = pl.program_id(2), pl.program_id(1) * states_ref.shape[1]
    within = step % (2 * segment)
    back = within >= segment
    w = within - jnp.where(back, segment, 0)
    _, hb, dk, dv = states_ref.shape
    n, per = q_ref.shape[0], hb // (q_ref.shape[1] // dk)
    cast = _cast(cdt)
    rows, cols = _iotas(n)

    @pl.when(within == 0)  # a segment starts from its edge state
    def _():
        states_ref[0] = edge_ref[...]

    @pl.when(step == segment)  # the sequence's last chunk: nothing comes after
    def _():
        ds_ref[...] = jnp.zeros_like(ds_ref)

    @pl.when(jnp.logical_not(back))
    def _():  # rebuild the segment's chunk states, keep the chunks' inverses
        beta, gam, gam_t = _gates(b_ref, g_ref, hb, first)
        states, inverses, after = states_ref[w], [], []
        for _, _, k, k_t, kk, heads in _groups(q_ref, k_ref, dk, per, False):
            for h in heads:
                ch = _Chunk(kk, k, v_ref[:, h * dv:(h + 1) * dv],
                            beta[:, h:h + 1], gam[:, h:h + 1],
                            gam_t[h:h + 1, :], rows, cols)
                inverses.append(ch.t)
                after.append(ch.state_after(k_t, states[h], ch.vn(states[h])))
        t_ref[w] = jnp.stack(inverses)

        @pl.when(w < segment - 1)
        def _():
            states_ref[w + 1] = jnp.stack(after)

    @pl.when(back)
    def _():  # chunk segment - 1 - w of the segment, backwards
        ci = segment - 1 - w
        beta, gam, gam_t = _gates(b_ref, g_ref, hb, first)
        lane = jax.lax.broadcasted_iota(jnp.int32, (n, hb), 1)
        sub = jax.lax.broadcasted_iota(jnp.int32, (hb, n), 0)
        at_last = jax.lax.broadcasted_iota(jnp.int32, (n, 1), 0) == n - 1
        db_col = dg_col = jnp.zeros((n, hb), jnp.float32)
        dg_row = jnp.zeros((hb, n), jnp.float32)
        states, ds_in, inverses = states_ref[ci], ds_ref[...], t_ref[ci]
        ds_out, dvs, dqs, dks = [], [], [], []
        for _, q, k, k_t, kk, heads in _groups(q_ref, k_ref, dk, per):
            qk = _dot_nt(cast(q), cast(k), None)
            dq = dk_acc = jnp.zeros_like(k)
            dkk = dqk = jnp.zeros_like(kk)
            for h in heads:
                b_h, v = beta[:, h:h + 1], v_ref[:, h * dv:(h + 1) * dv]
                ch = _Chunk(kk, k, v, b_h, gam[:, h:h + 1], gam_t[h:h + 1, :],
                            rows, cols, inverses[h])
                s, ds1 = states[h], ds_in[h]
                vn = ch.vn(s)
                causal = ch.causal(rows, cols)
                p = qk * causal
                do = do_ref[:, h * dv:(h + 1) * dv]
                qe, kf = q * ch.e, k * ch.f
                # o = (q e) S + P V', S' = exp(G_C) S + (k f)^T V'
                dvn = _dot_tn(cast(p), cast(do), None) + _dot(kf, ds1)
                ds_out.append(_dot_tn(cast(qe), cast(do), None) + ch.decay(ds1)
                              - _dot_tn(ch.uw[:, dv:], dvn))
                dp = _dot_nt(cast(do), cast(vn), None)
                dqe = _dot_nt(cast(do), cast(s), None)
                dkf = _dot_nt(vn, ds1)
                ddl = jnp.sum(jnp.sum(s * ds1, axis=1, keepdims=True), axis=0,
                              keepdims=True)
                # V' = U - W S; [U | W] = T [beta v | beta e k]
                dvw = jnp.concatenate([dvn, -_dot_nt(dvn, s)], axis=1)
                dbvk = _dot_tn(ch.t, dvw)
                dt = _dot_nt(dvw, ch.bvk)
                # T = (I + A)^-1: dA = -T^T dT T^T, A strictly lower
                da = jnp.where(rows > cols, -_dot_tn(ch.t, _dot_nt(dt, ch.t)), 0.0)
                # A = beta (k k^T * strict), P = q k^T * causal
                dkk = dkk + b_h * da * ch.strict
                dqk = dqk + dp * causal
                grads = da * ch.a + dp * p  # d(exp(G_i - G_j)) * exp(G_i - G_j)
                dbv, dbk = dbvk[:, :dv], dbvk[:, dv:]
                dbk_k = jnp.sum(dbk * k, axis=1, keepdims=True)
                dkf_k = jnp.sum(dkf * k, axis=1, keepdims=True)
                d_beta = (jnp.sum(da * ch.kks, axis=1, keepdims=True)
                          + jnp.sum(dbv * v, axis=1, keepdims=True)
                          + ch.e * dbk_k)
                d_gam = (jnp.sum(grads, axis=1, keepdims=True)
                         + ch.e * jnp.sum(dqe * q, axis=1, keepdims=True)
                         + b_h * ch.e * dbk_k - ch.f * dkf_k
                         + jnp.where(at_last, jnp.sum(ch.f * dkf_k, axis=0,
                                                      keepdims=True)
                                     + jnp.exp(ch.last) * ddl, 0.0))
                db_col = jnp.where(lane == h, d_beta, db_col)
                dg_col = jnp.where(lane == h, d_gam, dg_col)
                dg_row = jnp.where(sub == h, -jnp.sum(grads, axis=0, keepdims=True),
                                   dg_row)
                dq = dq + dqe * ch.e
                dk_acc = dk_acc + dbk * (b_h * ch.e) + dkf * ch.f
                dvs.append(dbv * b_h)
            dqs.append(dq + _dot(cast(dqk), cast(k), None))
            dks.append(dk_acc + _dot_tn(cast(dqk), cast(q), None)
                       + _dot(dkk, k) + _dot(dkk.T, k))
        ds_ref[...] = jnp.stack(ds_out)
        dv_ref[...] = jnp.concatenate(dvs, axis=1)
        dq_ref[...] = jnp.concatenate(dqs, axis=1)
        dk_ref[...] = jnp.concatenate(dks, axis=1)
        eye = _eye(n)
        db_ref[...] = _dot_tn(db_col, eye)
        dg_ref[...] = _dot_tn(dg_col, eye) + dg_row


# -- the calls -----------------------------------------------------------------


def _vmem(shape, index):
    return pl.BlockSpec(shape, index, memory_space=pltpu.VMEM)


def _layout(q, k, v, beta, g, per):
    """Pad ``T`` to whole segments of chunks (positions that change nothing
    before them: ``beta = g = 0``) and lay the operands out as the kernels
    read them: ``q, k [B, T', K d_k]``, ``v [B, T', H d_v]``, ``beta`` and
    the chunk-wise cumulative gates ``[B, chunks, C, H]``."""
    B, T, H, dv = v.shape
    tp = -(-T // (CHUNK * per)) * CHUNK * per

    def pad(x):
        return jnp.pad(x, ((0, 0), (0, tp - T)) + ((0, 0),) * (x.ndim - 2))

    chunks = (B, tp // CHUNK, CHUNK, H)
    gam = jnp.cumsum(pad(g).reshape(chunks), axis=2)
    return (pad(q).reshape(B, tp, -1), pad(k).reshape(B, tp, -1),
            pad(v).reshape(B, tp, -1), pad(beta).reshape(chunks), gam)


def _fwd_call(q, k, v, beta, gam, heads, dk, dv, segment, cdt):
    B, tp, keys = q.shape
    hb = delta_block(tp, heads, keys // dk, dk, dv, segment)
    kb, chunks = hb * keys // (heads * dk), tp // CHUNK
    col = _vmem((None, CHUNK, kb * dk), lambda b, j, c: (b, c, j))
    val = _vmem((None, CHUNK, hb * dv), lambda b, j, c: (b, c, j))
    gates = _vmem((None, None, CHUNK, heads), lambda b, j, c: (b, c, 0, 0))
    edge = _vmem((None, hb, None, dk, dv), lambda b, j, c: (b, j, c // segment, 0, 0))
    call = pl.pallas_call(
        functools.partial(_fwd_kernel, cdt, segment),
        grid=(B, heads // hb, chunks),
        in_specs=[col, col, val, gates, gates],
        out_specs=[val, edge],
        out_shape=[jax.ShapeDtypeStruct((B, tp, heads * dv), jnp.float32),
                   jax.ShapeDtypeStruct((B, heads, chunks // segment, dk, dv),
                                        jnp.float32)],
        scratch_shapes=[pltpu.VMEM((hb, dk, dv), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=_interpret(),
        name=DELTA_FWD,
    )
    return call(q, k, v, beta, gam)


def _bwd_call(q, k, v, beta, gam, edges, do, heads, segment, cdt):
    B, tp, keys = q.shape
    dk, dv = edges.shape[-2:]
    hb = delta_block(tp, heads, keys // dk, dk, dv, segment)
    kb, segments = hb * keys // (heads * dk), tp // (CHUNK * segment)
    span = 2 * segment

    def chunk(s, back_only=False):  # the chunk step s reads
        within = s % span
        first = (segments - 1 - s // span) * segment
        if back_only:  # the rebuilding steps hold the first chunk walked back
            return first + segment - 1 - jnp.maximum(within - segment, 0)
        return first + jnp.where(within < segment, within, span - 1 - within)

    col = _vmem((None, CHUNK, kb * dk), lambda b, j, s: (b, chunk(s), j))
    q_col = _vmem((None, CHUNK, kb * dk), lambda b, j, s: (b, chunk(s, True), j))
    val = _vmem((None, CHUNK, hb * dv), lambda b, j, s: (b, chunk(s), j))
    back_val = _vmem((None, CHUNK, hb * dv), lambda b, j, s: (b, chunk(s, True), j))
    gates = _vmem((None, None, CHUNK, heads), lambda b, j, s: (b, chunk(s), 0, 0))
    edge = _vmem((None, hb, None, dk, dv),
                 lambda b, j, s: (b, j, segments - 1 - s // span, 0, 0))
    rows = _vmem((None, None, None, hb, CHUNK),
                 lambda b, j, s: (b, j, chunk(s, True), 0, 0))
    grads_of_gates = jax.ShapeDtypeStruct(
        (B, heads // hb, tp // CHUNK, hb, CHUNK), jnp.float32)
    call = pl.pallas_call(
        functools.partial(_bwd_kernel, cdt, segment),
        grid=(B, heads // hb, segments * span),
        in_specs=[q_col, col, val, gates, gates, edge, back_val],
        out_specs=[q_col, q_col, back_val, rows, rows],
        out_shape=[jax.ShapeDtypeStruct(q.shape, jnp.float32)] * 2
        + [jax.ShapeDtypeStruct((B, tp, heads * dv), jnp.float32),
           grads_of_gates, grads_of_gates],
        scratch_shapes=[pltpu.VMEM((segment, hb, dk, dv), jnp.float32),
                        pltpu.VMEM((hb, dk, dv), jnp.float32),
                        pltpu.VMEM((segment, hb, CHUNK, CHUNK), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=_interpret(),
        name=DELTA_BWD,
    )
    return call(q, k, v, beta, gam, edges, do)


def _forward(q, k, v, beta, g, segment, cdt, name):
    B, T, H, dv = v.shape
    per = _segment(T, segment)
    ops = _layout(q, k, v, beta, g, per)
    o, edges = _fwd_call(*ops, H, k.shape[-1], dv, per, cdt)
    o = o[:, :T].reshape(v.shape)
    if name is not None:
        o, edges = checkpoint_name(o, name), checkpoint_name(edges, name)
    return o, (ops, edges)


@functools.partial(jax.custom_vjp, nondiff_argnums=(5, 6, 7))
def delta_rule(q, k, v, beta, g, segment: int, cdt=None,
               residual_checkpoint_name: str | None = None):
    """``models/afmoe.py chunked_delta_rule`` in chunks of CHUNK as the two
    kernels: ``q, k [B, T, K, d_k]``, ``v [B, T, H, d_v]``, ``beta, g [B, T,
    H]`` float32 -> ``o [B, T, H, d_v]`` float32, the state starting at
    zero; the backward pass keeps the state at the edges of segments of
    ``segment`` chunks. The output and those states carry
    ``residual_checkpoint_name``, as the splash kernels' residuals do: a
    checkpoint whose policy saves the name keeps them and does not run the
    forward kernel again. Leading axes are ``vmap``s. The call must satisfy
    :func:`delta_block`."""
    return _forward(q, k, v, beta, g, segment, cdt, residual_checkpoint_name)[0]


def _delta_rule_fwd(q, k, v, beta, g, segment, cdt, residual_checkpoint_name):
    return _forward(q, k, v, beta, g, segment, cdt, residual_checkpoint_name)


def _delta_rule_bwd(segment, cdt, residual_checkpoint_name, res, do):
    (q, k, v, beta, gam), edges = res  # as the kernels read them, padded
    B, T, H, dv = do.shape
    tp, dk = q.shape[1], edges.shape[-2]
    do = jnp.pad(do, ((0, 0), (0, tp - T), (0, 0), (0, 0)))
    dq, dk_, dv_, db, dgam = _bwd_call(q, k, v, beta, gam, edges,
                                       do.reshape(B, tp, H * dv), H,
                                       _segment(T, segment), cdt)

    def heads_last(x):  # [B, H / hb, chunks, hb, C] -> [B, chunks, C, H]
        return jnp.transpose(x, (0, 2, 4, 1, 3)).reshape(beta.shape)

    # G is the cumulative sum of g within a chunk
    dg = jax.lax.cumsum(heads_last(dgam), axis=2, reverse=True)
    heads = (B, tp, q.shape[-1] // dk, dk)
    return (dq.reshape(heads)[:, :T], dk_.reshape(heads)[:, :T],
            dv_.reshape(B, tp, H, dv)[:, :T],
            heads_last(db).reshape(B, tp, H)[:, :T], dg.reshape(B, tp, H)[:, :T])


delta_rule.defvjp(_delta_rule_fwd, _delta_rule_bwd)
