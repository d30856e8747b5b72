"""Fused Pallas TPU kernel for the LSTM recurrence (forward + BPTT backward).

The ICA-LSTM's hot loop (SURVEY.md §3.4) is the time recurrence: per step a
small ``h @ W_hh`` matmul plus gate math. The XLA scan path (models/icalstm.py)
already hoists the input projection; this kernel goes further and keeps the
carry (h, c) and all four recurrence matrices resident in VMEM across the
whole sequence, streaming per-step inputs/outputs HBM↔VMEM via the grid
pipeline — no per-step HBM round trip for the carry, no per-step kernel
launches.

Layout choice: gates live in four separate ``[T, B, H]`` arrays (not one
``[T, B, 4H]``) so every block's lane dimension is H and no slice ever crosses
a lane boundary (Mosaic-friendly; see pallas_guide.md pitfall #2).

Grid: ``(batch_tiles, T)`` — TPU grids execute sequentially, so VMEM scratch
carries (h, c) across the T dimension; time-reversed index maps drive the
backward kernel.

Four measured design points (flagship shape, 32 vmapped sites, v5e):

- **The i2h projection is fused into the forward kernel** (round 3): W_ih
  lives in VMEM beside W_hh and the kernel streams the raw ``x [T, B, D]``
  once — D=256 inbound values per step-row instead of the 4H=696 of a
  pre-projected gate layout, and no ``[T, B, 4H]`` XLA materialization at
  all. dx/dW_ih/db remain XLA einsums over the streamed dpreact cotangents.
- **dW lives OUTSIDE the kernel.** The weight gradient is the only cross-row
  reduction in BPTT; accumulating it in-kernel forced 4 extra outer-product
  dots per backward step AND made the kernel's outputs non-row-wise. Instead
  the backward kernel streams out the gate pre-activation cotangents, which
  concatenate on the FEATURE axis ([T, B, 4H]) so dx/dW_ih/dW_hh are plain
  696-wide MXU matmuls — the k-batched einsum forms canonicalize into dots
  XLA lowered through a ~3× slower convolution emitter (round 3 profiling;
  einsum spelling alone cannot dodge it, only the concat's different
  structure does).
- **The backward takes PRE-transposed recurrent weights.** ``w[k].T`` inside
  the kernel re-ran a lane/sublane transpose on every one of the T grid
  steps and made the backward ~20× slower than the forward; transposing once
  in XLA and keeping W_hhᵀ resident removed the entire gap (round 3 — this
  was the single largest perf bug in the build).
- **vmap folds into kernel rows, not grid steps.** jax's default vmap rule
  for ``pallas_call`` prepends a grid dimension, which executes
  SEQUENTIALLY on a TPU core — 32 vmapped sites ran as 32 serial passes of
  [16, H] matmuls. Both kernel entry points carry a ``custom_vmap`` rule that
  folds the mapped axis into the batch-row dimension instead ([512, H]
  matmuls, full MXU rows), padding rows to the kernel tile as needed. The
  fold is valid because every kernel output is row-wise (see previous point).

The terminal carry (hT, cT) is emitted from the f32 VMEM scratch — never
quantized to the bf16 streams — because the ring LSTM (parallel/sequence.py)
relays it across sequence chunks.

Semantics: standard LSTM gates (single sigmoid). The reference's
double-sigmoid quirk mode stays on the XLA scan path (models/icalstm.py) —
the kernel is the fast path for the default configuration.
``compute_dtype=bfloat16`` runs the matmuls in bf16 with f32 accumulation;
``None`` (default) is full f32, bit-comparable with the scan path.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.custom_batching import custom_vmap
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

B_TILE = 128

# Stable kernel names (``pl.pallas_call(name=)``): the name becomes the Mosaic
# custom call's ``kernel_name`` and a named scope around it, and the TPU
# compiler names the instruction after it (``%lstm_fwd.22``; without a name,
# after the enclosing flax module: ``%fwd.22``) — what a device trace shows
# and what benchmarks/layer_metrics/lstm_{fwd,bwd}_kernel_ms_per_round.json
# match. A rename here changes the compile-cache key and must be made there
# too (benchmarks/tests/test_scope_metrics.py fails otherwise).
LSTM_FWD = "lstm_fwd"
LSTM_BWD = "lstm_bwd"
BILSTM_FWD = "bilstm_fwd"
BILSTM_BWD = "bilstm_bwd"
BILSTM_POOL_FWD = "bilstm_pool_fwd"
BILSTM_POOL_BWD = "bilstm_pool_bwd"
KERNEL_NAMES = (LSTM_FWD, LSTM_BWD, BILSTM_FWD, BILSTM_BWD, BILSTM_POOL_FWD,
                BILSTM_POOL_BWD)


def _interpret() -> bool:
    # Pallas TPU kernels run in interpreter mode on CPU (tests / simulators)
    return jax.default_backend() == "cpu"


def _cdt_name(compute_dtype) -> str | None:
    return jnp.dtype(compute_dtype).name if compute_dtype is not None else None


# ---------------------------------------------------------------------------
# fused forward: the i2h projection runs IN-kernel (W_ih resident in VMEM),
# so the kernel streams the raw input x [T, B, D] once instead of four
# pre-projected [T, B, H] gate arrays — D=256 vs 4H=696 inbound values per
# step-row on the flagship shape, ~2.7× less inbound HBM traffic, and the
# [B*T, D] @ [D, 4H] XLA matmul plus its [T, B, 4H] HBM materialization
# disappear entirely (VERDICT r2 #2).
# ---------------------------------------------------------------------------


def _fwd_fused_kernel(
    x, wih, b, whh, h0, c0, hs, cs, ai, af, ao, ag, hT, cT, h_s, c_s
):
    t = pl.program_id(1)

    @pl.when(t == 0)
    def _():
        h_s[:] = h0[:]
        c_s[:] = c0[:]

    f32 = jnp.float32
    xt = x[0]  # [bt, D] this step's input block, at stream dtype
    h = h_s[:].astype(whh.dtype)
    # preact_k = x_t @ Wih_k + b_k + h @ Whh_k  (both W stacks VMEM-resident)
    pre = [
        jnp.dot(xt, wih[k], preferred_element_type=f32)
        + jnp.dot(h, whh[k], preferred_element_type=f32)
        + b[k].astype(f32)
        for k in range(4)
    ]
    i = jax.nn.sigmoid(pre[0])
    f = jax.nn.sigmoid(pre[1])
    o = jax.nn.sigmoid(pre[2])
    g = jnp.tanh(pre[3])
    c = f * c_s[:] + i * g
    h = o * jnp.tanh(c)
    h_s[:] = h
    c_s[:] = c
    hs[0] = h.astype(hs.dtype)
    cs[0] = c.astype(cs.dtype)
    ai[0] = i.astype(ai.dtype)
    af[0] = f.astype(af.dtype)
    ao[0] = o.astype(ao.dtype)
    ag[0] = g.astype(ag.dtype)

    # terminal carry at FULL f32 (straight from VMEM scratch, not the possibly
    # bf16 hs/cs streams): the ring-LSTM relays this carry between sequence
    # chunks, and quantizing it at each chunk boundary would silently diverge
    # the sharded run from the dense one (review finding, round 3)
    @pl.when(t == pl.num_programs(1) - 1)
    def _():
        hT[:] = h_s[:]
        cT[:] = c_s[:]


def _fwd_fused_call(x, wih4, b4, whh4, h0, c0, compute_dtype=None):
    T, B, D = x.shape
    H = wih4.shape[-1]
    bt = min(B_TILE, B)
    assert B % bt == 0, (
        f"batch {B} must be a multiple of the kernel tile {bt}; "
        "use lstm_forward_fused(), which pads"
    )
    if compute_dtype is not None:
        x = x.astype(compute_dtype)
        wih4 = wih4.astype(compute_dtype)
        whh4 = whh4.astype(compute_dtype)
    grid = (B // bt, T)
    spec_x = pl.BlockSpec((1, bt, D), lambda b, t: (t, b, 0), memory_space=pltpu.VMEM)
    spec_t = pl.BlockSpec((1, bt, H), lambda b, t: (t, b, 0), memory_space=pltpu.VMEM)
    spec_b = pl.BlockSpec((bt, H), lambda b, t: (b, 0), memory_space=pltpu.VMEM)
    spec_wih = pl.BlockSpec((4, D, H), lambda b, t: (0, 0, 0), memory_space=pltpu.VMEM)
    spec_whh = pl.BlockSpec((4, H, H), lambda b, t: (0, 0, 0), memory_space=pltpu.VMEM)
    spec_bias = pl.BlockSpec((4, H), lambda b, t: (0, 0), memory_space=pltpu.VMEM)
    stream_dtype = jnp.dtype(compute_dtype) if compute_dtype is not None else jnp.float32
    out_shape = jax.ShapeDtypeStruct((T, B, H), stream_dtype)
    carry_shape = jax.ShapeDtypeStruct((B, H), jnp.float32)
    return pl.pallas_call(
        _fwd_fused_kernel,
        grid=grid,
        in_specs=[spec_x, spec_wih, spec_bias, spec_whh, spec_b, spec_b],
        out_specs=[spec_t] * 6 + [spec_b] * 2,
        out_shape=[out_shape] * 6 + [carry_shape] * 2,
        scratch_shapes=[pltpu.VMEM((bt, H), jnp.float32)] * 2,
        interpret=_interpret(),
        name=LSTM_FWD,
    )(x, wih4, b4, whh4, h0, c0)


# ---------------------------------------------------------------------------
# backward (dW is computed OUTSIDE the kernel — see module docstring)
# ---------------------------------------------------------------------------


def _bwd_kernel(
    T_total,
    ai, af, ao, ag, cs, cs_prev, wT, c0, dhs, dhT, dcT,
    dxi_i, dxi_f, dxi_o, dxi_g, dh0, dc0,
    dh_s, dc_s,
):
    t = pl.program_id(1)  # 0..T-1, walking time backwards: time = T-1-t
    first_time = t == 0  # time T-1
    last_time = t == T_total - 1  # time 0

    @pl.when(first_time)
    def _():
        # seed the carries with the terminal-state cotangents (exact dcT/dhT);
        # re-seeded at the start of every batch tile (per-tile state)
        dh_s[:] = dhT[:].astype(jnp.float32)
        dc_s[:] = dcT[:].astype(jnp.float32)

    f32 = jnp.float32
    i, f, o, g = (ai[0].astype(f32), af[0].astype(f32),
                  ao[0].astype(f32), ag[0].astype(f32))
    c = cs[0].astype(f32)
    c_prev = jnp.where(last_time, c0[:].astype(f32), cs_prev[0].astype(f32))

    tanh_c = jnp.tanh(c)
    dh = dhs[0].astype(f32) + dh_s[:]
    do = dh * tanh_c
    dc = dh * o * (1.0 - tanh_c * tanh_c) + dc_s[:]
    di = dc * g
    df = dc * c_prev
    dg = dc * i

    dpi = di * i * (1.0 - i)
    dpf = df * f * (1.0 - f)
    dpo = do * o * (1.0 - o)
    dpg = dg * (1.0 - g * g)

    dxi_i[0] = dpi.astype(dxi_i.dtype)
    dxi_f[0] = dpf.astype(dxi_f.dtype)
    dxi_o[0] = dpo.astype(dxi_o.dtype)
    dxi_g[0] = dpg.astype(dxi_g.dtype)

    # dh_{t-1} = Σ_k dp_k @ W_kᵀ (matmuls in w's dtype, f32 accumulation).
    # wT holds the PRE-transposed weights: transposing inside the kernel
    # (w[k].T) re-ran a lane/sublane transpose on every one of the T grid
    # steps and dominated the whole backward pass — measured ~20× slower
    # than this resident-transpose layout on v5e.
    cdt = wT.dtype
    dh_prev = (
        jnp.dot(dpi.astype(cdt), wT[0], preferred_element_type=jnp.float32)
        + jnp.dot(dpf.astype(cdt), wT[1], preferred_element_type=jnp.float32)
        + jnp.dot(dpo.astype(cdt), wT[2], preferred_element_type=jnp.float32)
        + jnp.dot(dpg.astype(cdt), wT[3], preferred_element_type=jnp.float32)
    )

    dh_s[:] = dh_prev
    dc_s[:] = dc * f

    @pl.when(last_time)
    def _():
        dh0[:] = dh_s[:].astype(dh0.dtype)
        dc0[:] = dc_s[:].astype(dc0.dtype)


def _bwd_call(acts, cs, w4, c0, dhs, dhT, dcT, compute_dtype=None):
    T, B, H = cs.shape
    bt = min(B_TILE, B)
    assert B % bt == 0, f"batch {B} must be a multiple of the kernel tile {bt}"
    if compute_dtype is not None:
        w4 = w4.astype(compute_dtype)
    w4T = jnp.swapaxes(w4, 1, 2)  # transpose ONCE in XLA, resident in VMEM
    grid = (B // bt, T)

    rev = lambda b, t: (T - 1 - t, b, 0)
    b_block = lambda b, t: (b, 0)
    spec_rev = pl.BlockSpec((1, bt, H), rev, memory_space=pltpu.VMEM)
    spec_prev = pl.BlockSpec(
        (1, bt, H), lambda b, t: (jnp.maximum(T - 2 - t, 0), b, 0),
        memory_space=pltpu.VMEM,
    )
    spec_b = pl.BlockSpec((bt, H), b_block, memory_space=pltpu.VMEM)
    spec_w = pl.BlockSpec((4, H, H), lambda b, t: (0, 0, 0), memory_space=pltpu.VMEM)
    # dxi dtype must match the xi primal dtype (= the streamed act dtype);
    # dh0/dc0 match the f32 h0/c0 primals
    t_shape = jax.ShapeDtypeStruct((T, B, H), acts[0].dtype)
    b_shape = jax.ShapeDtypeStruct((B, H), jnp.float32)

    outs = pl.pallas_call(
        functools.partial(_bwd_kernel, T),
        grid=grid,
        in_specs=[spec_rev] * 4  # i, f, o, g
        + [spec_rev, spec_prev, spec_w, spec_b, spec_rev, spec_b, spec_b],
        out_specs=[spec_rev] * 4 + [spec_b, spec_b],
        out_shape=[t_shape] * 4 + [b_shape, b_shape],
        scratch_shapes=[pltpu.VMEM((bt, H), jnp.float32)] * 2,
        interpret=_interpret(),
        name=LSTM_BWD,
    )(*acts, cs, cs, w4T, c0, dhs, dhT, dcT)
    return outs  # dxi_i, dxi_f, dxi_o, dxi_g, dh0, dc0


# ---------------------------------------------------------------------------
# vmap folding: mapped axes become kernel batch rows, not serial grid steps
# ---------------------------------------------------------------------------


def _broadcast_unbatched(args, in_batched, axis_size):
    return [
        a if b else jnp.broadcast_to(a[None], (axis_size,) + a.shape)
        for a, b in zip(args, in_batched)
    ]


def _fold_rows(a):
    """[S, T, B, H] → [T, S*B, H]"""
    S, T, B, H = a.shape
    return jnp.moveaxis(a, 0, 1).reshape(T, S * B, H)


def _unfold_rows(a, S, B):
    """[T, S*B, H] → [S, T, B, H]"""
    T, SB, H = a.shape
    return jnp.moveaxis(a.reshape(T, S, B, H), 1, 0)


def _pad_rows(arrs, rows, axis):
    """Pad the row dim of each array up to a kernel-tile multiple."""
    bt = min(B_TILE, rows)
    pad = (-rows) % bt
    if pad == 0:
        return arrs, rows
    padded = []
    for a in arrs:
        widths = [(0, 0)] * a.ndim
        widths[axis] = (0, pad)
        padded.append(jnp.pad(a, widths))
    return padded, rows + pad


@functools.lru_cache(maxsize=None)
def _fwd_fused_callable(cdt_name: str | None):
    cdt = jnp.dtype(cdt_name) if cdt_name else None

    @custom_vmap
    def f(x, wih4, b4, whh4, h0, c0):
        return tuple(_fwd_fused_call(x, wih4, b4, whh4, h0, c0, cdt))

    @f.def_vmap
    def _rule(axis_size, in_batched, *args):
        if any(in_batched[k] for k in (1, 2, 3)):  # per-element weights
            batched = _broadcast_unbatched(args, in_batched, axis_size)
            outs = jax.lax.map(lambda a: f(*a), tuple(batched))
            return tuple(outs), (True,) * 8
        S = axis_size
        batched = _broadcast_unbatched(
            args, [b or i in (1, 2, 3) for i, b in enumerate(in_batched)], S
        )
        x = _fold_rows(batched[0])  # [S, T, B, D] → [T, S*B, D]
        B = batched[4].shape[1]
        h0 = batched[4].reshape(S * B, -1)
        c0 = batched[5].reshape(S * B, -1)
        (x, h0, c0), _ = _pad_rows([x, h0, c0], S * B, axis=-2)
        outs = f(x, args[1], args[2], args[3], h0, c0)
        t_outs = [_unfold_rows(o[:, : S * B], S, B) for o in outs[:6]]
        b_outs = [o[: S * B].reshape(S, B, -1) for o in outs[6:]]
        return tuple(t_outs + b_outs), (True,) * 8

    return f


@functools.lru_cache(maxsize=None)
def _bwd_callable(cdt_name: str | None):
    cdt = jnp.dtype(cdt_name) if cdt_name else None

    @custom_vmap
    def f(ai, af, ao, ag, cs, w4, c0, dhs, dhT, dcT):
        return tuple(_bwd_call((ai, af, ao, ag), cs, w4, c0, dhs, dhT, dcT, cdt))

    @f.def_vmap
    def _rule(axis_size, in_batched, *args):
        if in_batched[5]:  # per-element weights
            batched = _broadcast_unbatched(args, in_batched, axis_size)
            outs = jax.lax.map(lambda a: f(*a), tuple(batched))
            return tuple(outs), (True,) * 6
        S = axis_size
        batched = _broadcast_unbatched(
            args, [b or i == 5 for i, b in enumerate(in_batched)], S
        )
        t_arrs = [_fold_rows(batched[i]) for i in (0, 1, 2, 3, 4, 7)]
        w4 = args[5]
        B = batched[6].shape[1]
        b_arrs = [batched[i].reshape(S * B, -1) for i in (6, 8, 9)]
        rows = S * B
        (ai, af, ao, ag, cs, dhs), _ = _pad_rows(t_arrs, rows, axis=-2)
        (c0, dhT, dcT), _ = _pad_rows(b_arrs, rows, axis=-2)
        outs = f(ai, af, ao, ag, cs, w4, c0, dhs, dhT, dcT)
        dxi = [_unfold_rows(o[:, :rows], S, B) for o in outs[:4]]
        db = [o[:rows].reshape(S, B, -1) for o in outs[4:]]
        return tuple(dxi + db), (True,) * 6

    return f


# ---------------------------------------------------------------------------
# public op with custom VJP
# ---------------------------------------------------------------------------


@functools.partial(jax.custom_vjp, nondiff_argnums=(6,))
def lstm_recurrence_fused(x, wih4, b4, whh4, h0, c0, compute_dtype=None):
    """Fused LSTM: i2h projection + recurrence in ONE kernel pass.

    Args:
      x: ``[T, B, D]`` raw per-step inputs (at compute_dtype or f32).
      wih4: ``[4, D, H]`` f32 input-projection weights (i, f, o, g).
      b4: ``[4, H]`` f32 combined bias (``b_ih + b_hh`` per gate).
      whh4: ``[4, H, H]`` f32 recurrent weights.
      h0, c0: ``[B, H]`` f32 initial carry.

    Returns ``(hs [T, B, H], (hT, cT))`` — the terminal carry is always f32
    (written straight from the kernel's f32 VMEM scratch, never quantized to
    the stream dtype; the ring LSTM relays it between chunks). The backward
    runs the BPTT kernel (dxi ≡ dpreact); dx / dW_ih / db / dW_hh are
    MXU-shaped XLA einsums over the streamed cotangents.
    """
    hs, cs, i, f, o, g, hT, cT = _fwd_fused_callable(_cdt_name(compute_dtype))(
        x, wih4, b4, whh4, h0, c0
    )
    return hs, (hT, cT)


def _vjp_fused_fwd(x, wih4, b4, whh4, h0, c0, compute_dtype):
    hs, cs, i, f, o, g, hT, cT = _fwd_fused_callable(_cdt_name(compute_dtype))(
        x, wih4, b4, whh4, h0, c0
    )
    # b4 rides along only for its dtype: custom_vjp cotangent avals must
    # match the primal avals even when a caller passes non-f32 weights
    return (hs, (hT, cT)), (x, wih4, b4, whh4, h0, c0, hs, cs, (i, f, o, g))


def _vjp_fused_bwd(compute_dtype, res, grads):
    x, wih4, b4, whh4, h0, c0, hs, cs, acts = res
    dhs, (dhT, dcT) = grads
    cdt_name = _cdt_name(compute_dtype)
    dp_i, dp_f, dp_o, dp_g, dh0, dc0 = _bwd_callable(cdt_name)(
        *acts, cs, whh4, c0, dhs, dhT, dcT
    )
    cdt = jnp.dtype(cdt_name) if cdt_name else x.dtype
    # Concatenate the four gate cotangents on the FEATURE axis ([T, B, 4H])
    # so dx / dW_ih / dW_hh are plain 696-wide matmuls. The k-batched einsum
    # forms ('tbh,ktbg->khg' etc.) canonicalize to [4,·,·]-batched dots that
    # XLA's cost model lowers through a convolution emitter measured ~3x
    # slower in-context on v5e; the stack-axis spelling is canonicalized
    # away, only a genuine concat changes the structure.
    dpc = jnp.concatenate([dp_i, dp_f, dp_o, dp_g], axis=-1).astype(cdt)
    H = dp_i.shape[-1]
    wih_cat = jnp.swapaxes(wih4, 0, 1).reshape(wih4.shape[1], -1)  # [D, 4H]
    dx = jnp.einsum(
        "tbg,dg->tbd", dpc, wih_cat.astype(cdt),
        preferred_element_type=jnp.float32,
    ).astype(x.dtype)
    dwih = jnp.einsum(
        "tbd,tbg->dg", x.astype(cdt), dpc, preferred_element_type=jnp.float32,
    ).reshape(-1, 4, H).swapaxes(0, 1).astype(wih4.dtype)
    db = dpc.astype(jnp.float32).sum(axis=(0, 1)).reshape(4, H).astype(b4.dtype)
    h_prev = jnp.concatenate([h0[None].astype(hs.dtype), hs[:-1]], 0)
    dwhh = jnp.einsum(
        "tbh,tbg->hg", h_prev.astype(cdt), dpc, preferred_element_type=jnp.float32,
    ).reshape(H, 4, H).swapaxes(0, 1).astype(whh4.dtype)
    return dx, dwih, db, dwhh, dh0, dc0


lstm_recurrence_fused.defvjp(_vjp_fused_fwd, _vjp_fused_bwd)


# ---------------------------------------------------------------------------
# fused BIDIRECTIONAL kernels (VERDICT r3 #3): both directions advance in ONE
# grid sweep — the fwd direction consumes x block t while the rev direction
# consumes x block T-1-t (the time flip lives in the index map; no flipped
# copy of x is ever materialized). Each direction's recurrence is a serial
# dependency chain on its own carry; interleaving two independent chains in
# one kernel gives the MXU a second stream of ready matmuls while the other
# chain's h@W_hh waits on its carry — the single-direction kernel ran the
# directions as two back-to-back passes with that latency exposed twice.
# Both weight stacks stay VMEM-resident ([2, 4, D, H] + [2, 4, H, H]).
#
# EVERY rev-direction stream is stored in X-TIME convention (the rev state
# computed while consuming x[t] lands at block t, via the same flipped index
# map that reads x): the VJP then pairs dpc_rev with x/W by plain identity
# index — no jnp.flip of any [T, B, ·] array anywhere (the first cut kept
# rev streams in flipped-s order and paid ~0.7 ms/step of pure reverse-copy
# traffic in the epoch, measured on v5e). It also lets dx/dW_ih consume the
# two directions' cotangents as ONE [T, B, 8H]-wide concat matmul.
# The backward walks fwd time descending (blocks T-1-t) while the rev chain
# drains through identity maps (block t) — one kernel, both chains.
# ---------------------------------------------------------------------------


def _fwd_bidir_kernel(
    xf, xr, wih, b, whh, h0, c0,
    hsf, csf, aif, aff, aof, agf, hsr, csr, air, afr, aor, agr, hT, cT,
    hf_s, cf_s, hr_s, cr_s,
):
    t = pl.program_id(1)

    @pl.when(t == 0)
    def _():
        hf_s[:] = h0[0]
        cf_s[:] = c0[0]
        hr_s[:] = h0[1]
        cr_s[:] = c0[1]

    f32 = jnp.float32

    def advance(xt, h_s, c_s, d):
        h = h_s[:].astype(whh.dtype)
        pre = [
            jnp.dot(xt, wih[d, k], preferred_element_type=f32)
            + jnp.dot(h, whh[d, k], preferred_element_type=f32)
            + b[d, k].astype(f32)
            for k in range(4)
        ]
        i = jax.nn.sigmoid(pre[0])
        f = jax.nn.sigmoid(pre[1])
        o = jax.nn.sigmoid(pre[2])
        g = jnp.tanh(pre[3])
        c = f * c_s[:] + i * g
        h = o * jnp.tanh(c)
        h_s[:] = h
        c_s[:] = c
        return h, c, i, f, o, g

    h, c, i, f, o, g = advance(xf[0], hf_s, cf_s, 0)
    hsf[0] = h.astype(hsf.dtype)
    csf[0] = c.astype(csf.dtype)
    aif[0] = i.astype(aif.dtype)
    aff[0] = f.astype(aff.dtype)
    aof[0] = o.astype(aof.dtype)
    agf[0] = g.astype(agf.dtype)

    h, c, i, f, o, g = advance(xr[0], hr_s, cr_s, 1)
    hsr[0] = h.astype(hsr.dtype)
    csr[0] = c.astype(csr.dtype)
    air[0] = i.astype(air.dtype)
    afr[0] = f.astype(afr.dtype)
    aor[0] = o.astype(aor.dtype)
    agr[0] = g.astype(agr.dtype)

    # terminal carries at full f32 (same contract as the single-direction
    # kernel: straight from VMEM scratch, never the possibly-bf16 streams)
    @pl.when(t == pl.num_programs(1) - 1)
    def _():
        hT[0] = hf_s[:]
        cT[0] = cf_s[:]
        hT[1] = hr_s[:]
        cT[1] = cr_s[:]


def _fwd_bidir_call(x, wih2, b2, whh2, h02, c02, compute_dtype=None):
    T, B, D = x.shape
    H = wih2.shape[-1]
    bt = min(B_TILE, B)
    assert B % bt == 0, (
        f"batch {B} must be a multiple of the kernel tile {bt}; "
        "use bilstm_forward_fused(), which pads"
    )
    if compute_dtype is not None:
        x = x.astype(compute_dtype)
        wih2 = wih2.astype(compute_dtype)
        whh2 = whh2.astype(compute_dtype)
    grid = (B // bt, T)
    spec_xf = pl.BlockSpec((1, bt, D), lambda b, t: (t, b, 0), memory_space=pltpu.VMEM)
    spec_xr = pl.BlockSpec(
        (1, bt, D), lambda b, t: (T - 1 - t, b, 0), memory_space=pltpu.VMEM
    )
    spec_t = pl.BlockSpec((1, bt, H), lambda b, t: (t, b, 0), memory_space=pltpu.VMEM)
    # rev streams land at the SAME time index their x block came from
    # (x-time convention; see the section comment)
    spec_tr = pl.BlockSpec(
        (1, bt, H), lambda b, t: (T - 1 - t, b, 0), memory_space=pltpu.VMEM
    )
    spec_b2 = pl.BlockSpec((2, bt, H), lambda b, t: (0, b, 0), memory_space=pltpu.VMEM)
    spec_wih = pl.BlockSpec(
        (2, 4, D, H), lambda b, t: (0, 0, 0, 0), memory_space=pltpu.VMEM
    )
    spec_whh = pl.BlockSpec(
        (2, 4, H, H), lambda b, t: (0, 0, 0, 0), memory_space=pltpu.VMEM
    )
    spec_bias = pl.BlockSpec((2, 4, H), lambda b, t: (0, 0, 0), memory_space=pltpu.VMEM)
    stream = jnp.dtype(compute_dtype) if compute_dtype is not None else jnp.float32
    t_shape = jax.ShapeDtypeStruct((T, B, H), stream)
    carry_shape = jax.ShapeDtypeStruct((2, B, H), jnp.float32)
    return pl.pallas_call(
        _fwd_bidir_kernel,
        grid=grid,
        in_specs=[spec_xf, spec_xr, spec_wih, spec_bias, spec_whh, spec_b2, spec_b2],
        out_specs=[spec_t] * 6 + [spec_tr] * 6 + [spec_b2] * 2,
        out_shape=[t_shape] * 12 + [carry_shape] * 2,
        scratch_shapes=[pltpu.VMEM((bt, H), jnp.float32)] * 4,
        interpret=_interpret(),
        name=BILSTM_FWD,
    )(x, x, wih2, b2, whh2, h02, c02)


def _bwd_bidir_kernel(
    T_total,
    aif, aff, aof, agf, air, afr, aor, agr,
    csf, csf_prev, csr, csr_prev, wT, c0, dhsf, dhsr, dhT, dcT,
    dxf_i, dxf_f, dxf_o, dxf_g, dxr_i, dxr_f, dxr_o, dxr_g, dh0, dc0,
    dhf_s, dcf_s, dhr_s, dcr_s,
):
    t = pl.program_id(1)  # both directions walk their own time backwards
    first_time = t == 0
    last_time = t == T_total - 1

    @pl.when(first_time)
    def _():
        dhf_s[:] = dhT[0].astype(jnp.float32)
        dcf_s[:] = dcT[0].astype(jnp.float32)
        dhr_s[:] = dhT[1].astype(jnp.float32)
        dcr_s[:] = dcT[1].astype(jnp.float32)

    f32 = jnp.float32
    cdt = wT.dtype

    def drain(acts, c, c_prev, dhs_blk, dh_s, dc_s, d, outs):
        i, f, o, g = (a[0].astype(f32) for a in acts)
        c = c[0].astype(f32)
        c_prev = jnp.where(last_time, c0[d].astype(f32), c_prev[0].astype(f32))
        tanh_c = jnp.tanh(c)
        dh = dhs_blk[0].astype(f32) + dh_s[:]
        do = dh * tanh_c
        dc = dh * o * (1.0 - tanh_c * tanh_c) + dc_s[:]
        di = dc * g
        df = dc * c_prev
        dg = dc * i
        dpi = di * i * (1.0 - i)
        dpf = df * f * (1.0 - f)
        dpo = do * o * (1.0 - o)
        dpg = dg * (1.0 - g * g)
        outs[0][0] = dpi.astype(outs[0].dtype)
        outs[1][0] = dpf.astype(outs[1].dtype)
        outs[2][0] = dpo.astype(outs[2].dtype)
        outs[3][0] = dpg.astype(outs[3].dtype)
        dh_s[:] = (
            jnp.dot(dpi.astype(cdt), wT[d, 0], preferred_element_type=f32)
            + jnp.dot(dpf.astype(cdt), wT[d, 1], preferred_element_type=f32)
            + jnp.dot(dpo.astype(cdt), wT[d, 2], preferred_element_type=f32)
            + jnp.dot(dpg.astype(cdt), wT[d, 3], preferred_element_type=f32)
        )
        dc_s[:] = dc * f

    drain((aif, aff, aof, agf), csf, csf_prev, dhsf, dhf_s, dcf_s, 0,
          (dxf_i, dxf_f, dxf_o, dxf_g))
    drain((air, afr, aor, agr), csr, csr_prev, dhsr, dhr_s, dcr_s, 1,
          (dxr_i, dxr_f, dxr_o, dxr_g))

    @pl.when(last_time)
    def _():
        dh0[0] = dhf_s[:].astype(dh0.dtype)
        dc0[0] = dcf_s[:].astype(dc0.dtype)
        dh0[1] = dhr_s[:].astype(dh0.dtype)
        dc0[1] = dcr_s[:].astype(dc0.dtype)


def _bwd_bidir_call(actsf, actsr, csf, csr, whh2, c02, dhsf, dhsr, dhT2, dcT2,
                    compute_dtype=None):
    """``dhsf``/``dhsr`` may be full ``[T, B, H]`` cotangent streams or
    ``[1, B, H]`` per-row constants (the mean-pool backward: every step gets
    the same ``dpool/T`` block through a constant index map — no broadcast
    materialization, no stream traffic)."""
    T, B, H = csf.shape
    bt = min(B_TILE, B)
    assert B % bt == 0, f"batch {B} must be a multiple of the kernel tile {bt}"
    if compute_dtype is not None:
        whh2 = whh2.astype(compute_dtype)
    w2T = jnp.swapaxes(whh2, 2, 3)  # transpose ONCE in XLA, VMEM-resident
    grid = (B // bt, T)

    # fwd streams walk time descending; rev streams are stored in x-time
    # convention, so the rev chain (its own time also descending) walks
    # x-time ASCENDING — identity maps. rev's c_prev (one step earlier in
    # its own time) sits one x-time block LATER.
    rev = lambda b, t: (T - 1 - t, b, 0)
    fwd = lambda b, t: (t, b, 0)
    spec_rev = pl.BlockSpec((1, bt, H), rev, memory_space=pltpu.VMEM)
    spec_fwd = pl.BlockSpec((1, bt, H), fwd, memory_space=pltpu.VMEM)
    spec_prev_f = pl.BlockSpec(
        (1, bt, H), lambda b, t: (jnp.maximum(T - 2 - t, 0), b, 0),
        memory_space=pltpu.VMEM,
    )
    spec_prev_r = pl.BlockSpec(
        (1, bt, H), lambda b, t: (jnp.minimum(t + 1, T - 1), b, 0),
        memory_space=pltpu.VMEM,
    )
    spec_b2 = pl.BlockSpec((2, bt, H), lambda b, t: (0, b, 0), memory_space=pltpu.VMEM)
    spec_w = pl.BlockSpec(
        (2, 4, H, H), lambda b, t: (0, 0, 0, 0), memory_space=pltpu.VMEM
    )
    t_shape = jax.ShapeDtypeStruct((T, B, H), actsf[0].dtype)
    b2_shape = jax.ShapeDtypeStruct((2, B, H), jnp.float32)
    spec_const = pl.BlockSpec(
        (1, bt, H), lambda b, t: (0, b, 0), memory_space=pltpu.VMEM
    )
    spec_dhf = spec_const if dhsf.shape[0] == 1 else spec_rev
    spec_dhr = spec_const if dhsr.shape[0] == 1 else spec_fwd

    return pl.pallas_call(
        functools.partial(_bwd_bidir_kernel, T),
        grid=grid,
        in_specs=[spec_rev] * 4 + [spec_fwd] * 4
        + [spec_rev, spec_prev_f, spec_fwd, spec_prev_r, spec_w, spec_b2,
           spec_dhf, spec_dhr, spec_b2, spec_b2],
        out_specs=[spec_rev] * 4 + [spec_fwd] * 4 + [spec_b2, spec_b2],
        out_shape=[t_shape] * 8 + [b2_shape, b2_shape],
        scratch_shapes=[pltpu.VMEM((bt, H), jnp.float32)] * 4,
        interpret=_interpret(),
        name=BILSTM_BWD,
    )(*actsf, *actsr, csf, csf, csr, csr, w2T, c02, dhsf, dhsr, dhT2, dcT2)


@functools.lru_cache(maxsize=None)
def _fwd_bidir_callable(cdt_name: str | None):
    cdt = jnp.dtype(cdt_name) if cdt_name else None

    @custom_vmap
    def f(x, wih2, b2, whh2, h02, c02):
        return tuple(_fwd_bidir_call(x, wih2, b2, whh2, h02, c02, cdt))

    @f.def_vmap
    def _rule(axis_size, in_batched, *args):
        if any(in_batched[k] for k in (1, 2, 3)):  # per-element weights
            batched = _broadcast_unbatched(args, in_batched, axis_size)
            outs = jax.lax.map(lambda a: f(*a), tuple(batched))
            return tuple(outs), (True,) * 14
        S = axis_size
        batched = _broadcast_unbatched(
            args, [b or i in (1, 2, 3) for i, b in enumerate(in_batched)], S
        )
        x = _fold_rows(batched[0])  # [S, T, B, D] → [T, S*B, D]
        B = batched[4].shape[2]  # [S, 2, B, H]
        h02 = jnp.moveaxis(batched[4], 0, 1).reshape(2, S * B, -1)
        c02 = jnp.moveaxis(batched[5], 0, 1).reshape(2, S * B, -1)
        (x,), _ = _pad_rows([x], S * B, axis=-2)
        (h02, c02), _ = _pad_rows([h02, c02], S * B, axis=-2)
        outs = f(x, args[1], args[2], args[3], h02, c02)
        t_outs = [_unfold_rows(o[:, : S * B], S, B) for o in outs[:12]]
        b_outs = [
            jnp.moveaxis(o[:, : S * B].reshape(2, S, B, -1), 1, 0)
            for o in outs[12:]
        ]
        return tuple(t_outs + b_outs), (True,) * 14

    return f


@functools.lru_cache(maxsize=None)
def _bwd_bidir_callable(cdt_name: str | None):
    cdt = jnp.dtype(cdt_name) if cdt_name else None

    @custom_vmap
    def f(aif, aff, aof, agf, air, afr, aor, agr, csf, csr, whh2, c02,
          dhsf, dhsr, dhT2, dcT2):
        return tuple(_bwd_bidir_call(
            (aif, aff, aof, agf), (air, afr, aor, agr), csf, csr, whh2, c02,
            dhsf, dhsr, dhT2, dcT2, cdt,
        ))

    @f.def_vmap
    def _rule(axis_size, in_batched, *args):
        if in_batched[10]:  # per-element weights
            batched = _broadcast_unbatched(args, in_batched, axis_size)
            outs = jax.lax.map(lambda a: f(*a), tuple(batched))
            return tuple(outs), (True,) * 10
        S = axis_size
        batched = _broadcast_unbatched(
            args, [b or i == 10 for i, b in enumerate(in_batched)], S
        )
        t_arrs = [_fold_rows(batched[i]) for i in (0, 1, 2, 3, 4, 5, 6, 7, 8, 9, 12, 13)]
        B = batched[11].shape[2]  # [S, 2, B, H]
        b_arrs = [
            jnp.moveaxis(batched[i], 0, 1).reshape(2, S * B, -1)
            for i in (11, 14, 15)
        ]
        rows = S * B
        t_arrs, _ = _pad_rows(t_arrs, rows, axis=-2)
        b_arrs, _ = _pad_rows(b_arrs, rows, axis=-2)
        outs = f(*t_arrs[:10], args[10], b_arrs[0], t_arrs[10], t_arrs[11],
                 b_arrs[1], b_arrs[2])
        dxi = [_unfold_rows(o[:, :rows], S, B) for o in outs[:8]]
        db = [
            jnp.moveaxis(o[:, :rows].reshape(2, S, B, -1), 1, 0)
            for o in outs[8:]
        ]
        return tuple(dxi + db), (True,) * 10

    return f


@functools.partial(jax.custom_vjp, nondiff_argnums=(6,))
def bilstm_recurrence_fused(x, wih2, b2, whh2, h02, c02, compute_dtype=None):
    """Fused BIDIRECTIONAL LSTM: both directions in ONE kernel sweep.

    Args:
      x: ``[T, B, D]`` raw per-step inputs. The reverse direction reads x
        through a time-flipped index map — callers never materialize a
        flipped copy (the reference flips in torch, ``models.py:60-65``).
      wih2: ``[2, 4, D, H]`` per-direction input projections (fwd, rev).
      b2: ``[2, 4, H]`` combined biases; whh2: ``[2, 4, H, H]``.
      h02, c02: ``[2, B, H]`` initial carries.

    Returns ``(hs_f [T, B, H], hs_r [T, B, H], (hT2, cT2) [2, B, H] f32)``.
    ``hs_r`` is in X-TIME convention: ``hs_r[t]`` is the rev state computed
    while consuming ``x[t]`` (i.e. after seeing ``x[T-1..t]``) — the
    cuDNN-style bidirectional alignment, equal to ``flip(rev_cell(flip(x)))``.
    Time-order-invariant consumers (the model's mean-pool) use it directly;
    a caller needing the reference's no-flip-back concat order must flip.
    This convention is what lets the VJP run entirely flip-free (see the
    section comment above).
    """
    outs = _fwd_bidir_callable(_cdt_name(compute_dtype))(
        x, wih2, b2, whh2, h02, c02
    )
    hsf, hsr, hT2, cT2 = outs[0], outs[6], outs[12], outs[13]
    return hsf, hsr, (hT2, cT2)


def _vjp_bidir_fwd(x, wih2, b2, whh2, h02, c02, compute_dtype):
    outs = _fwd_bidir_callable(_cdt_name(compute_dtype))(
        x, wih2, b2, whh2, h02, c02
    )
    (hsf, csf, aif, aff, aof, agf,
     hsr, csr, air, afr, aor, agr, hT2, cT2) = outs
    res = (x, wih2, b2, whh2, h02, c02, hsf, csf, (aif, aff, aof, agf),
           hsr, csr, (air, afr, aor, agr))
    return (hsf, hsr, (hT2, cT2)), res


def _bidir_weight_grads(cdt_name, x, wih2, b2, whh2, h02, hsf, hsr, outs):
    """The XLA-side einsums shared by both bidir VJPs: turn the backward
    kernel's pre-activation cotangents into (dx, dwih2, db2, dwhh2, dh02,
    dc02). All inputs are in folded/x-time layout."""
    dpf = outs[0:4]
    dpr = outs[4:8]
    dh02, dc02 = outs[8], outs[9]
    cdt = jnp.dtype(cdt_name) if cdt_name else x.dtype
    H = dpf[0].shape[-1]

    # Same concat-on-feature-axis trick as the single-direction VJP (see
    # _vjp_fused_bwd), doubled: BOTH directions' cotangents are already in
    # x-time convention (the kernels' flipped index maps paid for this), so
    # they concat into ONE [T, B, 8H] array and dx / dW_ih are single
    # 1392-wide MXU matmuls — no jnp.flip of any time array.
    dpc = jnp.concatenate([*dpf, *dpr], axis=-1).astype(cdt)

    def cat_w(w4):  # [4, D, H] → [D, 4H]
        return jnp.swapaxes(w4, 0, 1).reshape(w4.shape[1], -1)

    w_cat8 = jnp.concatenate(
        [cat_w(wih2[0]), cat_w(wih2[1])], axis=-1
    ).astype(cdt)  # [D, 8H]
    xc = x.astype(cdt)
    dx = jnp.einsum(
        "tbg,dg->tbd", dpc, w_cat8, preferred_element_type=jnp.float32,
    ).astype(x.dtype)
    dwih_cat = jnp.einsum(
        "tbd,tbg->dg", xc, dpc, preferred_element_type=jnp.float32,
    )  # [D, 8H]
    dwih2 = jnp.stack([
        dwih_cat[:, : 4 * H].reshape(-1, 4, H).swapaxes(0, 1),
        dwih_cat[:, 4 * H:].reshape(-1, 4, H).swapaxes(0, 1),
    ]).astype(wih2.dtype)
    db_cat = dpc.astype(jnp.float32).sum(axis=(0, 1))
    db2 = jnp.stack([
        db_cat[: 4 * H].reshape(4, H), db_cat[4 * H:].reshape(4, H),
    ]).astype(b2.dtype)

    # h_prev in x-time convention: fwd is the usual shift-right with h0 in
    # front; rev state one step earlier in ITS time sits one x-time step
    # LATER (hs_r[t+1]), with h0 at the tail.
    h_prevf = jnp.concatenate([h02[0][None].astype(hsf.dtype), hsf[:-1]], 0)
    h_prevr = jnp.concatenate([hsr[1:], h02[1][None].astype(hsr.dtype)], 0)
    dpcf, dpcr = dpc[..., : 4 * H], dpc[..., 4 * H:]

    def dwhh_of(h_prev, dpc_dir):
        return jnp.einsum(
            "tbh,tbg->hg", h_prev.astype(cdt), dpc_dir,
            preferred_element_type=jnp.float32,
        ).reshape(H, 4, H).swapaxes(0, 1)

    dwhh2 = jnp.stack([
        dwhh_of(h_prevf, dpcf), dwhh_of(h_prevr, dpcr),
    ]).astype(whh2.dtype)
    return dx, dwih2, db2, dwhh2, dh02, dc02


def _vjp_bidir_bwd(compute_dtype, res, grads):
    (x, wih2, b2, whh2, h02, c02, hsf, csf, actsf, hsr, csr, actsr) = res
    dhsf, dhsr, (dhT2, dcT2) = grads
    cdt_name = _cdt_name(compute_dtype)
    outs = _bwd_bidir_callable(cdt_name)(
        *actsf, *actsr, csf, csr, whh2, c02, dhsf, dhsr, dhT2, dcT2
    )
    return _bidir_weight_grads(cdt_name, x, wih2, b2, whh2, h02, hsf, hsr, outs)


bilstm_recurrence_fused.defvjp(_vjp_bidir_fwd, _vjp_bidir_bwd)


# ---------------------------------------------------------------------------
# pooled bidirectional op — ICALstm's opt-in fused path (mean-pool of the
# hidden sequence, reference ``models.py:109``). NOTE (r5): the flagship A/B
# measured this fused path 27% SLOWER than two single-direction sweeps
# (80,531 vs 110,009 samples/sec/chip, docs/bench_ab_bidir_r5.jsonl), so the
# per-direction path is the model default and this op is reached only via
# ``ICALstm(fused_bidir=True)``. Two structural ideas on top of the
# bidirectional kernels above (kept for the record and for shapes where the
# trade may flip):
#
# 1. The mean-pool lives INSIDE the op: the forward kernel accumulates the
#    time-sum in VMEM scratch and emits [B, H] per direction (the hidden
#    sequences are still written — they are BPTT residuals — but nothing
#    re-reads them to pool), and the backward kernel consumes the pool
#    cotangent as a per-row CONSTANT block (``dpool/T`` through a constant
#    index map) instead of a broadcast [T, B, H] stream.
# 2. Residual layout is SITE-NATIVE under the trainer's vmap. The plain ops
#    above fold the vmapped site axis into kernel rows with moveaxis+reshape
#    copies — and because vmap applies that rule per op, every ~17 MB
#    residual stream was unfolded after the forward and refolded before the
#    backward (~400 MB of relayout copies per flagship training step; this,
#    not kernel time, dominated the round-3 epoch profile). Here the
#    custom_vmap rules dispatch to 4D kernels over ``[S, T, B, ·]`` arrays
#    whose BLOCKS gather ``s_tile × B`` rows per (site-tile, time) grid
#    step — every residual is WRITTEN by the forward kernel and READ by the
#    backward kernel in that one layout; only x/dx pay one transpose each
#    ([S, B, T, D] ↔ [S, T, B, D]). Mosaic constrains the last two block
#    dims to (8·, 128·) or the full array dims, which (B, H) satisfies —
#    this is why the site axis tiles the FIRST block dim, time sits second,
#    and rows are (s_tile · B). (A packed [.., 4, H] gate layout was tried
#    and rejected: Mosaic cannot shape-cast stores that insert singleton
#    dims mid-vector; the separate-array gate streams keep every store a
#    plain leading-dim split, and the VJP's feature-axis concat is cheap.)
#
# Logical layouts (what the custom_vjp-level code sees): x [B, T, D] in,
# residual streams [T, Bp, H] (Bp = row-padded batch; under vmap these
# batch to [S, T, B, H] with NO row padding — site padding is handled
# privately inside each rule), carries [2, B, H]. The dW/dx einsums are
# _bidir_weight_grads, shared with the sequence-returning op.
# ---------------------------------------------------------------------------


def _pool_s_tile(S: int, B: int) -> int:
    """Sites per kernel block: fill ~B_TILE rows (padding covers any
    non-dividing remainder of S)."""
    return max(1, min(S, B_TILE // max(B, 1) or 1))


def _fwd_pool_kernel4(
    xf, xr, wih, b, whh, h0, c0,
    hsf, csf, aif, aff, aof, agf, hsr, csr, air, afr, aor, agr,
    hT, cT, poolf, poolr,
    hf_s, cf_s, hr_s, cr_s, pf_s, pr_s,
):
    t = pl.program_id(1)
    st, _, B, H = hsf.shape
    rows = st * B
    f32 = jnp.float32

    @pl.when(t == 0)
    def _():
        hf_s[:] = h0[0].reshape(rows, H)
        cf_s[:] = c0[0].reshape(rows, H)
        hr_s[:] = h0[1].reshape(rows, H)
        cr_s[:] = c0[1].reshape(rows, H)
        pf_s[:] = jnp.zeros_like(pf_s)
        pr_s[:] = jnp.zeros_like(pr_s)

    def advance(xblk, h_s, c_s, p_s, d):
        xt = xblk[:, 0].reshape(rows, xblk.shape[-1])
        h = h_s[:].astype(whh.dtype)
        pre = [
            jnp.dot(xt, wih[d, k], preferred_element_type=f32)
            + jnp.dot(h, whh[d, k], preferred_element_type=f32)
            + b[d, k].astype(f32)
            for k in range(4)
        ]
        i = jax.nn.sigmoid(pre[0])
        f = jax.nn.sigmoid(pre[1])
        o = jax.nn.sigmoid(pre[2])
        g = jnp.tanh(pre[3])
        c = f * c_s[:] + i * g
        h = o * jnp.tanh(c)
        h_s[:] = h
        c_s[:] = c
        p_s[:] = p_s[:] + h
        return h, c, i, f, o, g

    def put(ref, v):
        ref[:, 0] = v.reshape(st, B, H).astype(ref.dtype)

    h, c, i, f, o, g = advance(xf, hf_s, cf_s, pf_s, 0)
    put(hsf, h), put(csf, c), put(aif, i), put(aff, f), put(aof, o), put(agf, g)
    h, c, i, f, o, g = advance(xr, hr_s, cr_s, pr_s, 1)
    put(hsr, h), put(csr, c), put(air, i), put(afr, f), put(aor, o), put(agr, g)

    @pl.when(t == pl.num_programs(1) - 1)
    def _():
        inv_T = 1.0 / pl.num_programs(1)
        hT[0] = hf_s[:].reshape(st, B, H)
        cT[0] = cf_s[:].reshape(st, B, H)
        hT[1] = hr_s[:].reshape(st, B, H)
        cT[1] = cr_s[:].reshape(st, B, H)
        poolf[:] = (pf_s[:] * inv_T).reshape(st, B, H)
        poolr[:] = (pr_s[:] * inv_T).reshape(st, B, H)


def _fwd_pool_call4(x, wih2, b2, whh2, h02, c02, compute_dtype=None):
    # x [S, T, B, D]; h02/c02 [2, S, B, H] — S pre-padded to an s_tile multiple
    S, T, B, D = x.shape
    H = wih2.shape[-1]
    st = _pool_s_tile(S, B)
    assert S % st == 0
    rows = st * B
    if compute_dtype is not None:
        x = x.astype(compute_dtype)
        wih2 = wih2.astype(compute_dtype)
        whh2 = whh2.astype(compute_dtype)
    grid = (S // st, T)
    V = pltpu.VMEM
    spec_xf = pl.BlockSpec((st, 1, B, D), lambda r, t: (r, t, 0, 0), memory_space=V)
    spec_xr = pl.BlockSpec(
        (st, 1, B, D), lambda r, t: (r, T - 1 - t, 0, 0), memory_space=V
    )
    spec_tf = pl.BlockSpec((st, 1, B, H), lambda r, t: (r, t, 0, 0), memory_space=V)
    spec_tr = pl.BlockSpec(
        (st, 1, B, H), lambda r, t: (r, T - 1 - t, 0, 0), memory_space=V
    )
    spec_c2 = pl.BlockSpec((2, st, B, H), lambda r, t: (0, r, 0, 0), memory_space=V)
    spec_p = pl.BlockSpec((st, B, H), lambda r, t: (r, 0, 0), memory_space=V)
    spec_wih = pl.BlockSpec(
        (2, 4, D, H), lambda r, t: (0, 0, 0, 0), memory_space=V
    )
    spec_whh = pl.BlockSpec(
        (2, 4, H, H), lambda r, t: (0, 0, 0, 0), memory_space=V
    )
    spec_bias = pl.BlockSpec((2, 4, H), lambda r, t: (0, 0, 0), memory_space=V)
    stream = jnp.dtype(compute_dtype) if compute_dtype is not None else jnp.float32
    t_shape = jax.ShapeDtypeStruct((S, T, B, H), stream)
    c2_shape = jax.ShapeDtypeStruct((2, S, B, H), jnp.float32)
    p_shape = jax.ShapeDtypeStruct((S, B, H), jnp.float32)
    return pl.pallas_call(
        _fwd_pool_kernel4,
        grid=grid,
        in_specs=[spec_xf, spec_xr, spec_wih, spec_bias, spec_whh,
                  spec_c2, spec_c2],
        out_specs=[spec_tf] * 6 + [spec_tr] * 6
        + [spec_c2, spec_c2, spec_p, spec_p],
        out_shape=[t_shape] * 12 + [c2_shape, c2_shape, p_shape, p_shape],
        scratch_shapes=[pltpu.VMEM((rows, H), jnp.float32)] * 6,
        interpret=_interpret(),
        name=BILSTM_POOL_FWD,
    )(x, x, wih2, b2, whh2, h02, c02)


def _bwd_pool_kernel4(
    T_total,
    aif, aff, aof, agf, air, afr, aor, agr,
    csf, csf_prev, csr, csr_prev, wT, c0, dpoolf, dpoolr, dhT, dcT,
    dxf_i, dxf_f, dxf_o, dxf_g, dxr_i, dxr_f, dxr_o, dxr_g, dh0, dc0,
    dhf_s, dcf_s, dhr_s, dcr_s,
):
    t = pl.program_id(1)
    st, _, B, H = dxf_i.shape
    rows = st * B
    first_time = t == 0
    last_time = t == T_total - 1
    f32 = jnp.float32
    cdt = wT.dtype

    @pl.when(first_time)
    def _():
        dhf_s[:] = dhT[0].reshape(rows, H).astype(f32)
        dcf_s[:] = dcT[0].reshape(rows, H).astype(f32)
        dhr_s[:] = dhT[1].reshape(rows, H).astype(f32)
        dcr_s[:] = dcT[1].reshape(rows, H).astype(f32)

    def drain(acts, c_blk, c_prev_blk, dpool, dh_s, dc_s, d, outs):
        i, f, o, g = (a[:, 0].reshape(rows, H).astype(f32) for a in acts)
        c = c_blk[:, 0].reshape(rows, H).astype(f32)
        c_prev = jnp.where(
            last_time,
            c0[d].reshape(rows, H).astype(f32),
            c_prev_blk[:, 0].reshape(rows, H).astype(f32),
        )
        tanh_c = jnp.tanh(c)
        dh = dpool[:].reshape(rows, H) + dh_s[:]
        do = dh * tanh_c
        dc = dh * o * (1.0 - tanh_c * tanh_c) + dc_s[:]
        di = dc * g
        df = dc * c_prev
        dg = dc * i
        dpi = di * i * (1.0 - i)
        dpf = df * f * (1.0 - f)
        dpo = do * o * (1.0 - o)
        dpg = dg * (1.0 - g * g)
        for ref, v in zip(outs, (dpi, dpf, dpo, dpg)):
            ref[:, 0] = v.reshape(st, B, H).astype(ref.dtype)
        dh_s[:] = (
            jnp.dot(dpi.astype(cdt), wT[d, 0], preferred_element_type=f32)
            + jnp.dot(dpf.astype(cdt), wT[d, 1], preferred_element_type=f32)
            + jnp.dot(dpo.astype(cdt), wT[d, 2], preferred_element_type=f32)
            + jnp.dot(dpg.astype(cdt), wT[d, 3], preferred_element_type=f32)
        )
        dc_s[:] = dc * f

    drain((aif, aff, aof, agf), csf, csf_prev, dpoolf, dhf_s, dcf_s, 0,
          (dxf_i, dxf_f, dxf_o, dxf_g))
    drain((air, afr, aor, agr), csr, csr_prev, dpoolr, dhr_s, dcr_s, 1,
          (dxr_i, dxr_f, dxr_o, dxr_g))

    @pl.when(last_time)
    def _():
        dh0[0] = dhf_s[:].reshape(st, B, H)
        dc0[0] = dcf_s[:].reshape(st, B, H)
        dh0[1] = dhr_s[:].reshape(st, B, H)
        dc0[1] = dcr_s[:].reshape(st, B, H)


def _bwd_pool_call4(actsf, actsr, csf, csr, whh2, c02, dpoolf, dpoolr,
                    dhT2, dcT2, compute_dtype=None):
    # all [S, T, B, H] site-native; dpool* [S, B, H] f32 (pre-divided by T)
    S, T, B, H = csf.shape
    st = _pool_s_tile(S, B)
    assert S % st == 0
    rows = st * B
    if compute_dtype is not None:
        whh2 = whh2.astype(compute_dtype)
    w2T = jnp.swapaxes(whh2, 2, 3)
    grid = (S // st, T)
    V = pltpu.VMEM
    # fwd-direction streams walk their time DESCENDING (block T-1-t); rev
    # streams are x-time stored, so the rev chain walks blocks ASCENDING
    spec_f = pl.BlockSpec(
        (st, 1, B, H), lambda r, t: (r, T - 1 - t, 0, 0), memory_space=V
    )
    spec_r = pl.BlockSpec((st, 1, B, H), lambda r, t: (r, t, 0, 0), memory_space=V)
    spec_f_prev = pl.BlockSpec(
        (st, 1, B, H), lambda r, t: (r, jnp.maximum(T - 2 - t, 0), 0, 0),
        memory_space=V,
    )
    spec_r_prev = pl.BlockSpec(
        (st, 1, B, H), lambda r, t: (r, jnp.minimum(t + 1, T - 1), 0, 0),
        memory_space=V,
    )
    spec_c2 = pl.BlockSpec((2, st, B, H), lambda r, t: (0, r, 0, 0), memory_space=V)
    spec_p = pl.BlockSpec((st, B, H), lambda r, t: (r, 0, 0), memory_space=V)
    spec_w = pl.BlockSpec(
        (2, 4, H, H), lambda r, t: (0, 0, 0, 0), memory_space=V
    )
    t_shape = jax.ShapeDtypeStruct((S, T, B, H), actsf[0].dtype)
    c2_shape = jax.ShapeDtypeStruct((2, S, B, H), jnp.float32)
    return pl.pallas_call(
        functools.partial(_bwd_pool_kernel4, T),
        grid=grid,
        in_specs=[spec_f] * 4 + [spec_r] * 4
        + [spec_f, spec_f_prev, spec_r, spec_r_prev, spec_w, spec_c2,
           spec_p, spec_p, spec_c2, spec_c2],
        out_specs=[spec_f] * 4 + [spec_r] * 4 + [spec_c2, spec_c2],
        out_shape=[t_shape] * 8 + [c2_shape, c2_shape],
        scratch_shapes=[pltpu.VMEM((rows, H), jnp.float32)] * 4,
        interpret=_interpret(),
        name=BILSTM_POOL_BWD,
    )(*actsf, *actsr, csf, csf, csr, csr, w2T, c02, dpoolf, dpoolr,
      dhT2, dcT2)


def _pad_sites(arrs, S, st, axis=0):
    pad = (-S) % st
    if pad == 0:
        return arrs
    out = []
    for a in arrs:
        widths = [(0, 0)] * a.ndim
        widths[axis] = (0, pad)
        out.append(jnp.pad(a, widths))
    return out


@functools.lru_cache(maxsize=None)
def _pool_fwd_kcall(cdt_name: str | None):
    """custom_vmap forward. Unbatched → the 3D kernels above (row padding,
    one x transpose — the single-site debug path); vmapped → site-native 4D
    kernels (one x transpose, zero residual copies)."""
    cdt = jnp.dtype(cdt_name) if cdt_name else None

    @custom_vmap
    def f(x, wih2, b2, whh2, h02, c02):
        B, T, D = x.shape
        H = wih2.shape[-1]
        bt = min(B_TILE, B)
        pad = (-B) % bt
        xp = x.astype(cdt if cdt is not None else jnp.float32)
        h02p, c02p = h02.astype(jnp.float32), c02.astype(jnp.float32)
        if pad:
            xp = jnp.concatenate([xp, jnp.zeros((pad, T, D), xp.dtype)], 0)
            zb = jnp.zeros((2, pad, H), jnp.float32)
            h02p = jnp.concatenate([h02p, zb], 1)
            c02p = jnp.concatenate([c02p, zb], 1)
        xT = jnp.swapaxes(xp, 0, 1)  # [T, Bp, D]
        outs = _fwd_bidir_call(xT, wih2, b2, whh2, h02p, c02p, cdt)
        hsf, hsr, hT2, cT2 = outs[0], outs[6], outs[12], outs[13]
        poolf = hsf[:, :B].mean(axis=0, dtype=jnp.float32)
        poolr = hsr[:, :B].mean(axis=0, dtype=jnp.float32)
        return (poolf, poolr, hT2[:, :B], cT2[:, :B], xT) + tuple(outs[:12])

    @f.def_vmap
    def _rule(axis_size, in_batched, *args):
        if any(in_batched[k] for k in (1, 2, 3)):  # per-element weights
            batched = _broadcast_unbatched(args, in_batched, axis_size)
            outs = jax.lax.map(lambda a: f(*a), tuple(batched))
            return tuple(outs), (True,) * 17
        S = axis_size
        batched = _broadcast_unbatched(
            args, [b or i in (1, 2, 3) for i, b in enumerate(in_batched)], S
        )
        B = batched[0].shape[1]
        st = _pool_s_tile(S, B)
        # THE one x relayout: [S, B, T, D] → [S, T, B, D] (XLA can often
        # fuse it into the producing matmul's epilogue)
        xT = jnp.swapaxes(batched[0], 1, 2)
        xT = xT.astype(cdt if cdt is not None else jnp.float32)
        h02 = jnp.moveaxis(batched[4], 0, 1)  # [2, S, B, H] (small)
        c02 = jnp.moveaxis(batched[5], 0, 1)
        (xTp,) = _pad_sites([xT], S, st)
        h02, c02 = _pad_sites([h02, c02], S, st, axis=1)
        outs = _fwd_pool_call4(xTp, args[1], args[2], args[3], h02, c02, cdt)
        streams = [a[:S] for a in outs[:12]]
        hT2, cT2, poolf, poolr = outs[12], outs[13], outs[14], outs[15]
        mv = lambda a: jnp.moveaxis(a[:, :S], 0, 1)  # [2,S,·]→[S,2,·] (small)
        return (
            (poolf[:S], poolr[:S], mv(hT2), mv(cT2), xT) + tuple(streams),
            (True,) * 17,
        )

    return f


@functools.lru_cache(maxsize=None)
def _pool_bwd_kcall(cdt_name: str | None):
    """custom_vmap backward: kernel-only (row-wise outputs). dW einsums live
    OUTSIDE in the custom_vjp bwd (_bidir_weight_grads) — they batch
    per-site under vmap and JAX sums the cotangent for the shared
    (unbatched) weights."""
    cdt = jnp.dtype(cdt_name) if cdt_name else None

    @custom_vmap
    def f(aif, aff, aof, agf, air, afr, aor, agr, csf, csr, whh2, c02,
          dpoolf, dpoolr, dhT2, dcT2):
        Bp = csf.shape[1]
        B = dpoolf.shape[0]
        pad = Bp - B
        stream = csf.dtype

        def padb(a, axis=1):  # pad the row axis of [2, B, H] / [1, B, H]
            if not pad:
                return a
            widths = [(0, 0)] * a.ndim
            widths[axis] = (0, pad)
            return jnp.pad(a, widths)

        return _bwd_bidir_call(
            (aif, aff, aof, agf), (air, afr, aor, agr), csf, csr, whh2,
            padb(c02.astype(jnp.float32)),
            padb(dpoolf.astype(stream)[None]), padb(dpoolr.astype(stream)[None]),
            padb(dhT2.astype(jnp.float32)), padb(dcT2.astype(jnp.float32)),
            cdt,
        )

    @f.def_vmap
    def _rule(axis_size, in_batched, *args):
        if in_batched[10]:  # per-element weights
            batched = _broadcast_unbatched(args, in_batched, axis_size)
            outs = jax.lax.map(lambda a: f(*a), tuple(batched))
            return tuple(outs), (True,) * 10
        S = axis_size
        batched = _broadcast_unbatched(
            args, [b or i == 10 for i, b in enumerate(in_batched)], S
        )
        B = batched[8].shape[2]  # csf [S, T, B, H]
        st = _pool_s_tile(S, B)
        c02 = jnp.moveaxis(batched[11], 0, 1).astype(jnp.float32)  # [2,S,B,H]
        dhT2 = jnp.moveaxis(batched[14], 0, 1).astype(jnp.float32)
        dcT2 = jnp.moveaxis(batched[15], 0, 1).astype(jnp.float32)
        dpoolf = batched[12].astype(jnp.float32)
        dpoolr = batched[13].astype(jnp.float32)
        streams = _pad_sites(list(batched[:10]) + [dpoolf, dpoolr], S, st)
        c02, dhT2, dcT2 = _pad_sites([c02, dhT2, dcT2], S, st, axis=1)
        outs = _bwd_pool_call4(
            tuple(streams[0:4]), tuple(streams[4:8]), streams[8], streams[9],
            args[10], c02, streams[10], streams[11], dhT2, dcT2, cdt,
        )
        mv = lambda a: jnp.moveaxis(a[:, :S], 0, 1)
        return (
            tuple(a[:S] for a in outs[:8]) + (mv(outs[8]), mv(outs[9])),
            (True,) * 10,
        )

    return f


@functools.partial(jax.custom_vjp, nondiff_argnums=(6,))
def bilstm_pool_fused_op(x, wih2, b2, whh2, h02, c02, compute_dtype=None):
    """Fused bidirectional LSTM + time mean-pool (stacked-weight layout).

    x [B, T, D]; wih2 [2, 4, D, H]; b2 [2, 4, H]; whh2 [2, 4, H, H];
    h02/c02 [2, B, H]. Returns ``(pooled [B, 2H] f32, (hT2, cT2) f32)``
    where ``pooled = concat([hs_f.mean(time), hs_r.mean(time)], -1)``.
    See the section comment for the layout/batching design.
    """
    outs = _pool_fwd_kcall(_cdt_name(compute_dtype))(
        x, wih2, b2, whh2, h02, c02
    )
    poolf, poolr, hT2, cT2 = outs[:4]
    return jnp.concatenate([poolf, poolr], axis=-1), (hT2, cT2)


def _vjp_pool_fwd(x, wih2, b2, whh2, h02, c02, compute_dtype):
    outs = _pool_fwd_kcall(_cdt_name(compute_dtype))(
        x, wih2, b2, whh2, h02, c02
    )
    (poolf, poolr, hT2, cT2, xT,
     hsf, csf, aif, aff, aof, agf, hsr, csr, air, afr, aor, agr) = outs
    # xT (the transposed/padded input actually fed to the kernel) is the
    # residual — the dW einsums need x in stream layout, and saving the
    # transposed copy avoids a second transpose in the backward. x_wit is a
    # zero-size dtype witness so dx can be cast back to the primal dtype.
    x_wit = jnp.zeros((0,), x.dtype)
    res = (xT, x_wit, wih2, b2, whh2, h02, c02, hsf, csf,
           (aif, aff, aof, agf), hsr, csr, (air, afr, aor, agr))
    return (jnp.concatenate([poolf, poolr], axis=-1), (hT2, cT2)), res


def _vjp_pool_bwd(compute_dtype, res, grads):
    (xT, x_wit, wih2, b2, whh2, h02, c02,
     hsf, csf, actsf, hsr, csr, actsr) = res
    dpooled, (dhT2, dcT2) = grads
    B = dpooled.shape[0]
    T = xT.shape[0]
    H = hsf.shape[-1]
    cdt_name = _cdt_name(compute_dtype)
    dpoolf = dpooled[:, :H] / T
    dpoolr = dpooled[:, H:] / T
    outs = _pool_bwd_kcall(cdt_name)(
        *actsf, *actsr, csf, csr, whh2, c02, dpoolf, dpoolr, dhT2, dcT2
    )
    # row-pad h0 to the streams' padded width for the h_prev shift (no-op
    # under vmap, where rows are never padded)
    pad = hsf.shape[1] - h02.shape[1]
    h02p = jnp.pad(h02, ((0, 0), (0, pad), (0, 0))) if pad else h02
    dxT, dwih2, db2, dwhh2, dh02, dc02 = _bidir_weight_grads(
        cdt_name, xT, wih2, b2, whh2, h02p, hsf, hsr, outs
    )
    dx = jnp.swapaxes(dxT, 0, 1)[:B].astype(x_wit.dtype)
    # The kernel streams are row-padded to the batch tile; dx is sliced back
    # above, and the carry cotangents need the same trim (pad rows carry
    # exactly-zero gradient, so slicing is exact).
    dh02 = dh02[:, :B]
    dc02 = dc02[:, :B]
    return dx, dwih2, db2, dwhh2, dh02, dc02


bilstm_pool_fused_op.defvjp(_vjp_pool_fwd, _vjp_pool_bwd)


def bilstm_pool_forward_fused(x, params_fwd, params_rev, h02=None, c02=None,
                              compute_dtype=None):
    """Model-layout wrapper over :func:`bilstm_pool_fused_op`.

    Args:
      x: ``[B, T, D]`` raw inputs (shared by both directions).
      params_fwd / params_rev: ``(w_ih [D, 4H], b [4H], w_hh [H, 4H])`` in
        LSTMCell blocked layout (b = b_ih + b_hh).
      h02, c02: optional ``[2, B, H]`` initial carries (zeros by default).

    Returns ``(pooled [B, 2H] f32, (hT2, cT2) [2, B, H] f32)``.
    """
    B = x.shape[0]
    H = params_fwd[2].shape[0]

    def stack_dir(p):
        w_ih, b, w_hh = (a.astype(jnp.float32) for a in p)
        wih4 = jnp.stack([w_ih[:, k * H: (k + 1) * H] for k in range(4)])
        b4 = jnp.stack([b[k * H: (k + 1) * H] for k in range(4)])
        whh4 = jnp.stack([w_hh[:, k * H: (k + 1) * H] for k in range(4)])
        return wih4, b4, whh4

    wf, bf, whf = stack_dir(params_fwd)
    wr, br, whr = stack_dir(params_rev)
    if h02 is None:
        h02 = jnp.zeros((2, B, H), jnp.float32)
    if c02 is None:
        c02 = jnp.zeros((2, B, H), jnp.float32)
    return bilstm_pool_fused_op(
        x, jnp.stack([wf, wr]), jnp.stack([bf, br]), jnp.stack([whf, whr]),
        h02.astype(jnp.float32), c02.astype(jnp.float32), compute_dtype,
    )




def bilstm_forward_fused(x, params_fwd, params_rev, h02=None, c02=None,
                         compute_dtype=None):
    """Model-layout convenience wrapper over :func:`bilstm_recurrence_fused`.

    Args:
      x: ``[B, T, D]`` raw inputs (shared by both directions).
      params_fwd / params_rev: ``(w_ih [D, 4H], b [4H], w_hh [H, 4H])`` in
        LSTMCell blocked layout (b = b_ih + b_hh).
      h02, c02: optional ``[2, B, H]`` initial carries (zeros by default).

    Returns ``(hs_f [B, T, H], hs_r [B, T, H], (hT2, cT2) [2, B, H] f32)``
    with ``hs_r`` in x-time convention (see the op docstring). Pads the
    batch to the kernel tile.
    """
    B, T, D = x.shape
    H = params_fwd[2].shape[0]
    in_dtype = x.dtype
    x = x.astype(compute_dtype if compute_dtype is not None else jnp.float32)

    def stack_dir(p):
        w_ih, b, w_hh = (a.astype(jnp.float32) for a in p)
        wih4 = jnp.stack([w_ih[:, k * H: (k + 1) * H] for k in range(4)])
        b4 = jnp.stack([b[k * H: (k + 1) * H] for k in range(4)])
        whh4 = jnp.stack([w_hh[:, k * H: (k + 1) * H] for k in range(4)])
        return wih4, b4, whh4

    wf, bf, whf = stack_dir(params_fwd)
    wr, br, whr = stack_dir(params_rev)
    wih2 = jnp.stack([wf, wr])
    b2 = jnp.stack([bf, br])
    whh2 = jnp.stack([whf, whr])
    if h02 is None:
        h02 = jnp.zeros((2, B, H), jnp.float32)
    if c02 is None:
        c02 = jnp.zeros((2, B, H), jnp.float32)
    h02 = h02.astype(jnp.float32)
    c02 = c02.astype(jnp.float32)

    bt = min(B_TILE, B)
    pad = (-B) % bt
    if pad:
        x = jnp.concatenate([x, jnp.zeros((pad, T, D), x.dtype)], 0)
        zb = jnp.zeros((2, pad, H), jnp.float32)
        h02 = jnp.concatenate([h02, zb], 1)
        c02 = jnp.concatenate([c02, zb], 1)
    x_t = jnp.swapaxes(x, 0, 1)  # [T, B, D]
    hsf, hsr, (hT2, cT2) = bilstm_recurrence_fused(
        x_t, wih2, b2, whh2, h02, c02, compute_dtype
    )
    hsf = jnp.swapaxes(hsf, 0, 1)
    hsr = jnp.swapaxes(hsr, 0, 1)
    if pad:
        hsf, hsr = hsf[:B], hsr[:B]
        hT2, cT2 = hT2[:, :B], cT2[:, :B]
    return hsf.astype(in_dtype), hsr.astype(in_dtype), (hT2, cT2)


def lstm_forward_fused(x, w_ih, b, w_hh, h0, c0, compute_dtype=None):
    """Model-layout convenience wrapper over :func:`lstm_recurrence_fused`.

    Args:
      x: ``[B, T, D]`` raw inputs (the encoder output — no pre-projection).
      w_ih: ``[D, 4H]`` blocked input projection, b: ``[4H]`` combined bias,
      w_hh: ``[H, 4H]`` blocked recurrent weight (LSTMCell layout).
      h0, c0: ``[B, H]``.

    Returns ``(hs [B, T, H] at x's dtype, (hT, cT) at f32)`` — the carry
    contract is "always f32" (matches the scan path; the ring LSTM relays it
    between chunks). Pads the batch to the kernel tile.
    """
    B, T, D = x.shape
    H = w_hh.shape[0]
    in_dtype = x.dtype
    x = x.astype(compute_dtype if compute_dtype is not None else jnp.float32)
    w_ih = w_ih.astype(jnp.float32)
    w_hh = w_hh.astype(jnp.float32)
    b = b.astype(jnp.float32)
    h0 = h0.astype(jnp.float32)
    c0 = c0.astype(jnp.float32)
    bt = min(B_TILE, B)
    pad = (-B) % bt
    if pad:
        x = jnp.concatenate([x, jnp.zeros((pad, T, D), x.dtype)], 0)
        h0 = jnp.concatenate([h0, jnp.zeros((pad, H), h0.dtype)], 0)
        c0 = jnp.concatenate([c0, jnp.zeros((pad, H), c0.dtype)], 0)
    x_t = jnp.swapaxes(x, 0, 1)  # [T, B, D]
    wih4 = jnp.stack([w_ih[:, k * H : (k + 1) * H] for k in range(4)])
    b4 = jnp.stack([b[k * H : (k + 1) * H] for k in range(4)])
    whh4 = jnp.stack([w_hh[:, k * H : (k + 1) * H] for k in range(4)])
    hs, (hT, cT) = lstm_recurrence_fused(x_t, wih4, b4, whh4, h0, c0, compute_dtype)
    hs = jnp.swapaxes(hs, 0, 1)
    if pad:
        hs, hT, cT = hs[:B], hT[:B], cT[:B]
    return hs.astype(in_dtype), (hT, cT)
