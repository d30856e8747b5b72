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

Grid: ``(row_tiles, time_blocks)`` — TPU grids execute sequentially, so VMEM
scratch carries (h, c) across the time dimension, and one grid step walks the
``Tb`` timesteps of its ``(Tb, R, ·)`` blocks itself (``lstm_block`` chooses
``(Tb, R)``); time-reversed index maps drive the backward kernel.

Five measured design points (flagship shape, 32 vmapped sites, v5e):

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

- **A grid step carries a block of timesteps, not one** (PR 31). A grid
  step costs 0.35 µs whatever it holds, and at ``(1, 128, ·)`` blocks a call
  paid it 392 times, a third of its time. The forward (bound by the MXU at
  the padded widths, 0.68 µs a 128-row timestep) now walks 2 timesteps of
  all 512 rows a step, the backward (bound by HBM) 7 timesteps of 128 rows,
  and takes ``c_{t-1}`` from the row above in the same ``cs`` block, fetching
  only a one-timestep halo a block where it fetched ``cs`` twice: 1.449 →
  1.085 ms a round, 51 → 69 % of the kernels' roofline. Two things that
  looked right lost on the chip: computing the block's ``x @ W_ih`` ahead of
  the serial chain into VMEM scratch (forward +10 %: the MXU was the wall,
  not the chain, and the detour adds VMEM traffic and VPU adds), and asking
  for more scoped VMEM for larger blocks (backward +29 %, and the rest of
  the round slower too). A rolled ``fori_loop`` walk was 6 % slower than
  the unrolled one.

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

# Row granule: callers pad the row count to a multiple of ``min(B_TILE, rows)``
# and a grid step's row tile R is a whole number of granules.
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
KERNEL_NAMES = (LSTM_FWD, LSTM_BWD)

# What one grid step may hold in VMEM, as ``block_vmem_bytes`` counts it: 14 of
# the 16 MiB to which the TPU compiler scopes a kernel by default (the rest is
# for the kernel body's own temporaries). NO larger scope is asked for
# (``vmem_limit_bytes``): with 64 MiB the backward call ran 29 % slower and the
# rest of the round 0.2 ms slower on a v5e (PERF.md §6, PR 31) — XLA keeps
# small operands and its fusions' data in the VMEM that kernels leave free.
VMEM_BUDGET = 14 << 20
# Longest walk of timesteps inside one grid step (the walk is unrolled).
MAX_BLOCK_STEPS = 16
# Most rows of one grid step of the forward call.
MAX_BLOCK_ROWS = 512


def _interpret() -> bool:
    # Pallas TPU kernels run in interpreter mode on CPU (tests / simulators)
    return jax.default_backend() == "cpu"


def _cdt_name(compute_dtype) -> str | None:
    return jnp.dtype(compute_dtype).name if compute_dtype is not None else None


# ---------------------------------------------------------------------------
# the block a grid step carries
# ---------------------------------------------------------------------------


def _round_up(n: int, m: int) -> int:
    return pl.cdiv(n, m) * m


def block_vmem_bytes(Tb: int, R: int, D: int | None, H: int, stream_dtype) -> int:
    """VMEM one grid step of ``(Tb, R)`` holds: every block twice (the
    pipeline's double buffer) plus scratch, as stored (lanes padded to 128,
    rows to the dtype's sublane tile). An upper bound: XLA may hand a small
    operand over in VMEM, outside the kernel's scope.

    With ``D``, the forward call: the ``x`` block and six output blocks at the
    stream dtype, both weight stacks, the bias, ``h0``/``c0``/``hT``/``cT`` and
    the carry scratch. With ``D`` None, the backward call, which sees no ``x``:
    six input and four output stream blocks, the one-timestep halo, one weight
    stack, five ``[R, H]`` carries in and out, the carry scratch.
    """
    item = jnp.dtype(stream_dtype).itemsize
    sub = 32 // item  # rows of one (sublane, 128) tile: 8 float32, 16 bfloat16
    Hp = _round_up(H, 128)
    step = _round_up(R, sub) * Hp * item  # one timestep of one stream
    carry = _round_up(R, 8) * Hp * 4
    whh = 4 * _round_up(H, sub) * Hp * item
    if D is None:
        return 2 * (10 * Tb * step + step + whh + 5 * carry) + 2 * carry
    x_step = _round_up(R, sub) * _round_up(D, 128) * item
    wih = 4 * _round_up(D, sub) * Hp * item
    bias = 8 * Hp * 4
    return 2 * (Tb * (x_step + 6 * step) + wih + whh + bias + 4 * carry) + 2 * carry


def lstm_block(T: int, rows: int, D: int | None, H: int, stream_dtype,
               override: tuple[int, int] | None = None) -> tuple[int, int]:
    """``(Tb, R)``: the timesteps and rows of the block one grid step carries.

    A pure function of what a call can see: ``T``, the padded row count
    ``rows`` (a multiple of the granule ``min(B_TILE, rows)``), ``H``, the
    stream dtype, and ``D`` for the forward call (None for the backward,
    which sees no ``x``). What was measured on a v5e (PERF.md §6, PR 31): a
    grid step costs 0.35 µs whatever it carries, so both calls want few of
    them; the forward is bound by the MXU at the padded widths and gains from
    rows (a weight tile meets more of them), the backward by HBM and gains
    from timesteps (the halo is one stream block in ``10 * Tb``).

    - ``R``: the backward takes one granule. The forward takes the largest
      whole number of granules that divides ``rows``, up to MAX_BLOCK_ROWS,
      for which two timesteps still fit VMEM_BUDGET.
    - ``Tb``: among 1..MAX_BLOCK_STEPS under VMEM_BUDGET, the one with the
      fewest grid steps, a step walked past ``T`` counting as three of them
      (the last time block may hold such steps; they leave the carry alone).
      A divisor of ``T`` where a good one fits (98 → 14, or 7 where only ten
      fit), else a tail (97 → 14, one step past); ``T`` 1 gives 1, the kernel
      as it was before the block.

    ``override`` is for tests and ``scripts/kernel_tune.py``: a ``(Tb, R)`` to
    use as given, checked only for what the kernels cannot run.
    """
    if override is not None:
        Tb, R = override
        if not (1 <= Tb <= T and R >= 1 and rows % R == 0):
            raise ValueError(
                f"block {override} does not fit T={T}, rows={rows}: "
                "need 1 <= Tb <= T and R dividing rows"
            )
        return Tb, R
    gran = min(B_TILE, rows)
    assert rows % gran == 0, (
        f"batch {rows} must be a multiple of the kernel tile {gran}; "
        "use lstm_forward_fused(), which pads"
    )

    def fits(Tb, R):
        return block_vmem_bytes(Tb, R, D, H, stream_dtype) <= VMEM_BUDGET

    R = gran
    if D is not None:
        tiles = rows // gran
        R = max(
            (gran * d for d in range(1, tiles + 1)
             if tiles % d == 0 and gran * d <= MAX_BLOCK_ROWS and fits(2, gran * d)),
            default=gran,
        )

    def cost(Tb):
        blocks = pl.cdiv(T, Tb)
        past = blocks * Tb - T
        return blocks + 3 * past, past, -Tb

    steps = [Tb for Tb in range(1, min(T, MAX_BLOCK_STEPS) + 1) if fits(Tb, R)]
    return min(steps or [1], key=cost), R


def _block_start_prev(tb, Tb):
    """Time of the cell state BEFORE time block ``tb``'s earliest step (the
    backward's halo; at ``tb`` 0 there is none and ``c0`` stands in)."""
    return jnp.maximum(tb * Tb - 1, 0)


def _c_before_block(tb, c0, halo):
    return jnp.where(tb == 0, c0, halo)


def _hold(live, new, old):
    """A step past ``T`` (the last time block's tail) leaves the carry alone."""
    return jnp.where(live, new, old)


# ---------------------------------------------------------------------------
# fused forward: the i2h projection runs IN-kernel (W_ih resident in VMEM),
# so the kernel streams the raw input x [T, B, D] once instead of four
# pre-projected [T, B, H] gate arrays — D=256 vs 4H=696 inbound values per
# step-row on the flagship shape, ~2.7× less inbound HBM traffic, and the
# [B*T, D] @ [D, 4H] XLA matmul plus its [T, B, 4H] HBM materialization
# disappear entirely (VERDICT r2 #2).
# ---------------------------------------------------------------------------


def _fwd_fused_kernel(
    T, x, wih, b, whh, h0, c0, hs, cs, ai, af, ao, ag, hT, cT, h_s, c_s
):
    Tb = x.shape[0]
    j = pl.program_id(1)  # time block: steps j*Tb .. j*Tb + Tb - 1
    last = pl.num_programs(1) - 1

    @pl.when(j == 0)
    def _():
        h_s[:] = h0[:]
        c_s[:] = c0[:]

    f32 = jnp.float32
    bias = [b[k].astype(f32) for k in range(4)]
    tail = T % Tb or Tb  # steps from here on are past T in the last time block
    for s in range(Tb):
        xt = x[s]  # [R, D] this step's input rows, at stream dtype
        h = h_s[:].astype(whh.dtype)
        # preact_k = x_t @ Wih_k + h @ Whh_k + b_k  (both W stacks VMEM-resident)
        pre = [
            jnp.dot(xt, wih[k], preferred_element_type=f32)
            + jnp.dot(h, whh[k], preferred_element_type=f32)
            + bias[k]
            for k in range(4)
        ]
        i = jax.nn.sigmoid(pre[0])
        f = jax.nn.sigmoid(pre[1])
        o = jax.nn.sigmoid(pre[2])
        g = jnp.tanh(pre[3])
        c = f * c_s[:] + i * g
        h = o * jnp.tanh(c)
        hs[s] = h.astype(hs.dtype)
        cs[s] = c.astype(cs.dtype)
        ai[s] = i.astype(ai.dtype)
        af[s] = f.astype(af.dtype)
        ao[s] = o.astype(ao.dtype)
        ag[s] = g.astype(ag.dtype)
        if s >= tail:
            h, c = _hold(j < last, h, h_s[:]), _hold(j < last, c, c_s[:])
        h_s[:] = h
        c_s[:] = c

    # terminal carry at FULL f32 (straight from VMEM scratch, not the possibly
    # bf16 hs/cs streams): the ring-LSTM relays this carry between sequence
    # chunks, and quantizing it at each chunk boundary would silently diverge
    # the sharded run from the dense one (review finding, round 3)
    @pl.when(j == last)
    def _():
        hT[:] = h_s[:]
        cT[:] = c_s[:]


def _fwd_fused_call(x, wih4, b4, whh4, h0, c0, compute_dtype=None, block=None):
    T, B, D = x.shape
    H = wih4.shape[-1]
    if compute_dtype is not None:
        x = x.astype(compute_dtype)
        wih4 = wih4.astype(compute_dtype)
        whh4 = whh4.astype(compute_dtype)
    stream_dtype = jnp.dtype(compute_dtype) if compute_dtype is not None else jnp.float32
    Tb, R = lstm_block(T, B, D, H, stream_dtype, override=block)
    grid = (B // R, pl.cdiv(T, Tb))
    spec_x = pl.BlockSpec((Tb, R, D), lambda b, j: (j, b, 0), memory_space=pltpu.VMEM)
    spec_t = pl.BlockSpec((Tb, R, H), lambda b, j: (j, b, 0), memory_space=pltpu.VMEM)
    spec_b = pl.BlockSpec((R, H), lambda b, j: (b, 0), memory_space=pltpu.VMEM)
    spec_wih = pl.BlockSpec((4, D, H), lambda b, j: (0, 0, 0), memory_space=pltpu.VMEM)
    spec_whh = pl.BlockSpec((4, H, H), lambda b, j: (0, 0, 0), memory_space=pltpu.VMEM)
    spec_bias = pl.BlockSpec((4, H), lambda b, j: (0, 0), memory_space=pltpu.VMEM)
    out_shape = jax.ShapeDtypeStruct((T, B, H), stream_dtype)
    carry_shape = jax.ShapeDtypeStruct((B, H), jnp.float32)
    return pl.pallas_call(
        functools.partial(_fwd_fused_kernel, T),
        grid=grid,
        in_specs=[spec_x, spec_wih, spec_bias, spec_whh, spec_b, spec_b],
        out_specs=[spec_t] * 6 + [spec_b] * 2,
        out_shape=[out_shape] * 6 + [carry_shape] * 2,
        scratch_shapes=[pltpu.VMEM((R, H), jnp.float32)] * 2,
        interpret=_interpret(),
        name=LSTM_FWD,
    )(x, wih4, b4, whh4, h0, c0)


# ---------------------------------------------------------------------------
# backward (dW is computed OUTSIDE the kernel — see module docstring)
# ---------------------------------------------------------------------------


def _bwd_kernel(
    T,
    ai, af, ao, ag, cs, halo, wT, c0, dhs, dhT, dcT,
    dxi_i, dxi_f, dxi_o, dxi_g, dh0, dc0,
    dh_s, dc_s,
):
    Tb = cs.shape[0]
    j = pl.program_id(1)  # walking time backwards, block by block
    last = pl.num_programs(1) - 1
    tb = last - j  # time block: steps tb*Tb + Tb - 1 down to tb*Tb

    @pl.when(j == 0)
    def _():
        # seed the carries with the terminal-state cotangents (exact dcT/dhT);
        # re-seeded at the start of every batch tile (per-tile state)
        dh_s[:] = dhT[:].astype(jnp.float32)
        dc_s[:] = dcT[:].astype(jnp.float32)

    f32 = jnp.float32
    cdt = wT.dtype
    tail = T % Tb or Tb  # steps from here on are past T in time block `last`
    for s in reversed(range(Tb)):
        i, f, o, g = (ai[s].astype(f32), af[s].astype(f32),
                      ao[s].astype(f32), ag[s].astype(f32))
        c = cs[s].astype(f32)
        # c_{t-1} is the row above in the SAME block; only the block's
        # earliest step reaches outside it, into the one-timestep halo
        if s:
            c_prev = cs[s - 1].astype(f32)
        else:
            c_prev = _c_before_block(tb, c0[:].astype(f32), halo[0].astype(f32))

        tanh_c = jnp.tanh(c)
        dh = dhs[s].astype(f32) + dh_s[:]
        do = dh * tanh_c
        dc = dh * o * (1.0 - tanh_c * tanh_c) + dc_s[:]
        di = dc * g
        df = dc * c_prev
        dg = dc * i

        dpi = di * i * (1.0 - i)
        dpf = df * f * (1.0 - f)
        dpo = do * o * (1.0 - o)
        dpg = dg * (1.0 - g * g)

        dxi_i[s] = dpi.astype(dxi_i.dtype)
        dxi_f[s] = dpf.astype(dxi_f.dtype)
        dxi_o[s] = dpo.astype(dxi_o.dtype)
        dxi_g[s] = dpg.astype(dxi_g.dtype)

        # dh_{t-1} = Σ_k dp_k @ W_kᵀ (matmuls in w's dtype, f32 accumulation).
        # wT holds the PRE-transposed weights: transposing inside the kernel
        # (w[k].T) re-ran a lane/sublane transpose on every one of the T grid
        # steps and dominated the whole backward pass — measured ~20× slower
        # than this resident-transpose layout on v5e.
        dh_prev = (
            jnp.dot(dpi.astype(cdt), wT[0], preferred_element_type=f32)
            + jnp.dot(dpf.astype(cdt), wT[1], preferred_element_type=f32)
            + jnp.dot(dpo.astype(cdt), wT[2], preferred_element_type=f32)
            + jnp.dot(dpg.astype(cdt), wT[3], preferred_element_type=f32)
        )
        dc_prev = dc * f
        if s >= tail:  # the walk's FIRST steps
            dh_prev = _hold(j > 0, dh_prev, dh_s[:])
            dc_prev = _hold(j > 0, dc_prev, dc_s[:])
        dh_s[:] = dh_prev
        dc_s[:] = dc_prev

    @pl.when(j == last)
    def _():
        dh0[:] = dh_s[:].astype(dh0.dtype)
        dc0[:] = dc_s[:].astype(dc0.dtype)


def _bwd_call(acts, cs, w4, c0, dhs, dhT, dcT, compute_dtype=None, block=None):
    T, B, H = cs.shape
    if compute_dtype is not None:
        w4 = w4.astype(compute_dtype)
    w4T = jnp.swapaxes(w4, 1, 2)  # transpose ONCE in XLA, resident in VMEM
    Tb, R = lstm_block(T, B, None, H, cs.dtype, override=block)
    nT = pl.cdiv(T, Tb)
    grid = (B // R, nT)

    spec_rev = pl.BlockSpec(
        (Tb, R, H), lambda b, j: (nT - 1 - j, b, 0), memory_space=pltpu.VMEM
    )
    # the second operand over cs: one timestep a time block, not one a step
    spec_halo = pl.BlockSpec(
        (1, R, H), lambda b, j: (_block_start_prev(nT - 1 - j, Tb), b, 0),
        memory_space=pltpu.VMEM,
    )
    spec_b = pl.BlockSpec((R, H), lambda b, j: (b, 0), memory_space=pltpu.VMEM)
    spec_w = pl.BlockSpec((4, H, H), lambda b, j: (0, 0, 0), memory_space=pltpu.VMEM)
    # dxi dtype must match the xi primal dtype (= the streamed act dtype);
    # dh0/dc0 match the f32 h0/c0 primals
    t_shape = jax.ShapeDtypeStruct((T, B, H), acts[0].dtype)
    b_shape = jax.ShapeDtypeStruct((B, H), jnp.float32)

    outs = pl.pallas_call(
        functools.partial(_bwd_kernel, T),
        grid=grid,
        in_specs=[spec_rev] * 4  # i, f, o, g
        + [spec_rev, spec_halo, spec_w, spec_b, spec_rev, spec_b, spec_b],
        out_specs=[spec_rev] * 4 + [spec_b, spec_b],
        out_shape=[t_shape] * 4 + [b_shape, b_shape],
        scratch_shapes=[pltpu.VMEM((R, H), jnp.float32)] * 2,
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
