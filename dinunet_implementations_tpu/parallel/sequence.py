"""Sequence / context parallelism over the ``model`` mesh axis.

The reference has no sequence sharding (SURVEY.md §2.2: its longest-sequence
handling is a single-device Python-loop LSTM over ≤98 windows). For the TPU
build, long-context is first-class: sequences too long for one device's HBM
shard their time axis across the ``model`` axis, with collectives carrying the
cross-chunk dependencies:

- :func:`ring_attention` — blockwise attention with online-softmax
  accumulation while K/V blocks rotate around the ring via ``ppermute``
  (the standard ring-attention recipe; memory per device is O(T/n)).
- :func:`ring_lstm` — the LSTM carry relayed around the ring: device d
  computes microbatch j's chunk in wavefront stage ``j + d`` and hands
  (h, c) to device d+1. A recurrence is inherently sequential, so a single
  sequence incurs n-stage latency; splitting the batch into ``m``
  microbatches pipelines the wavefront so devices work on different
  microbatches concurrently. Per-device row-steps are ``(m + n - 1)·B/m``
  vs the dense ``B`` — an overhead factor of ``(m + n - 1)/m`` (→ 1 as m
  grows), NOT the n× of the unpipelined masked wavefront (``m=1``), which
  recomputes every stage on every device. What the ring buys is *memory*
  scaling (n× longer sequences than fit on one device) at modest extra
  FLOPs; the microbatch count trades pipeline overhead against MXU row
  utilization (B/m rows per kernel call).

All functions run inside ``shard_map``/``vmap`` with a bound axis name.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from .mesh import MODEL_AXIS


def _ring_perm(n):
    return [(i, (i + 1) % n) for i in range(n)]


def ring_attention(q, k, v, axis_name: str | None = MODEL_AXIS):
    """Ring attention over a sequence sharded on ``axis_name``.

    q/k/v: ``[B, T_local, N, Hd]`` per device (full heads, local time chunk).
    Returns ``[B, T_local, N, Hd]`` — exact (non-causal) softmax attention
    over the *global* sequence, computed with online-softmax accumulation as
    K/V blocks rotate around the ring.
    """
    if axis_name is None:
        from ..models.transformer import dot_product_attention

        return dot_product_attention(q, k, v)

    n = jax.lax.axis_size(axis_name)
    scale = q.shape[-1] ** -0.5
    B, T, N, Hd = q.shape

    num = jnp.zeros((B, T, N, Hd), jnp.float32)
    den = jnp.zeros((B, N, T), jnp.float32)
    mx = jnp.full((B, N, T), -jnp.inf, jnp.float32)

    def step(carry, _):
        k_blk, v_blk, num, den, mx = carry
        logits = jnp.einsum(
            "btnh,bsnh->bnts", q, k_blk, preferred_element_type=jnp.float32
        ) * scale
        blk_max = logits.max(axis=-1)
        new_mx = jnp.maximum(mx, blk_max)
        corr = jnp.exp(mx - new_mx)
        p = jnp.exp(logits - new_mx[..., None])  # [B, N, T, S]
        num_new = num * jnp.moveaxis(corr, 1, 2)[..., None] + jnp.einsum(
            "bnts,bsnh->btnh", p, v_blk.astype(jnp.float32)
        )
        den_new = den * corr + p.sum(axis=-1)
        k_nxt = jax.lax.ppermute(k_blk, axis_name, _ring_perm(n))
        v_nxt = jax.lax.ppermute(v_blk, axis_name, _ring_perm(n))
        return (k_nxt, v_nxt, num_new, den_new, new_mx), None

    (k_f, v_f, num, den, mx), _ = jax.lax.scan(
        step, (k, v, num, den, mx), None, length=n
    )
    out = num / jnp.moveaxis(den, 1, 2)[..., None]
    return out.astype(q.dtype)


def _auto_microbatches(B: int, n: int) -> int:
    """Pick the microbatch count that minimizes hardware row-tile work:
    ``(m + n - 1)`` stages × ``ceil((B/m)/8)`` sublane tiles per stage (rows
    tile to 8 on the MXU, so a 1-row call costs a full tile). Ties break
    toward smaller ``m`` (fewer ppermute rounds). m=1 — the masked
    wavefront — wins naturally when B is a single tile; capped at 4n (the
    pipeline is full by then)."""
    if n <= 1:
        return 1

    def tile_cost(m):
        return (m + n - 1) * -(-(B // m) // 8)

    return min(
        (m for m in range(1, min(4 * n, B) + 1) if B % m == 0),
        key=lambda m: (tile_cost(m), m),
    )


def ring_lstm(cell_fn, x_local, h0, c0, axis_name: str = MODEL_AXIS,
              microbatches: int | None = None):
    """Run an LSTM over a time-sharded sequence by relaying the carry around
    the ring, pipelined over batch microbatches (wavefront overlap).

    ``cell_fn(x_chunk, (h, c)) -> (hs_chunk, (hT, cT))`` — any full-sequence
    cell (e.g. a bound ``LSTMCell``). ``x_local`` is this device's
    ``[B, T_local, D]`` chunk; ``h0``/``c0`` [B, H] seed the sequence start.

    The batch splits into ``m = microbatches`` slices (``None`` → heuristic,
    :func:`_auto_microbatches`). Microbatch j's chunk-d rows are computed on
    device d at wavefront stage ``j + d`` (``m + n - 1`` stages total), so
    devices work on *different* microbatches concurrently instead of
    recomputing every stage SPMD-uniformly and masking — per-device
    row-steps are ``(m + n - 1)·B/m`` vs the masked wavefront's ``n·B``
    (``m=1`` reproduces exactly that masked behavior). Stages at the
    pipeline fill/drain still execute (SPMD uniformity) on clamped dummy
    slices whose writes are masked out.

    Returns ``(hs_local [B, T_local, H], (hT, cT))`` where the terminal
    carry is valid on every device (broadcast from the last ring position).
    """
    n = jax.lax.axis_size(axis_name)
    idx = jax.lax.axis_index(axis_name)
    B = x_local.shape[0]
    m = _auto_microbatches(B, n) if microbatches is None else microbatches
    if m < 1 or B % m:
        raise ValueError(
            f"microbatches={m} must be >= 1 and divide the batch ({B})"
        )
    mb = B // m

    def fresh(j):  # h0/c0 rows seeding microbatch j (clamped at fill/drain)
        row = jnp.clip(j, 0, m - 1) * mb
        return (
            jax.lax.dynamic_slice_in_dim(h0, row, mb, 0),
            jax.lax.dynamic_slice_in_dim(c0, row, mb, 0),
        )

    # device 0 seeds microbatch 0 at stage 0; everyone else idles until the
    # wavefront arrives (their stage-0 compute is masked garbage)
    carry = jax.tree.map(
        lambda f: jnp.where(idx == 0, f, jnp.zeros_like(f)), fresh(0)
    )
    out = None
    finals = None
    # Python loop over stages (static: m + n - 1 is mesh/config-determined):
    # cell_fn is typically a bound flax submodule, which cannot be called
    # inside a lax.scan body from a compact parent.
    for s in range(m + n - 1):
        j = s - idx  # the microbatch this device advances at stage s
        valid = (j >= 0) & (j < m)
        row = jnp.clip(j, 0, m - 1) * mb
        x_mb = jax.lax.dynamic_slice_in_dim(x_local, row, mb, 0)
        hs, (hT, cT) = cell_fn(x_mb, carry)
        if out is None:
            out = jnp.zeros((B,) + hs.shape[1:], hs.dtype)
            finals = (
                jnp.zeros((B,) + hT.shape[1:], hT.dtype),
                jnp.zeros((B,) + cT.shape[1:], cT.dtype),
            )
        out = jnp.where(
            valid,
            jax.lax.dynamic_update_slice_in_dim(out, hs.astype(out.dtype), row, 0),
            out,
        )
        # the last ring position finishes microbatch j: record its terminal
        done = valid & (idx == n - 1)
        finals = jax.tree.map(
            lambda f, t: jnp.where(
                done,
                jax.lax.dynamic_update_slice_in_dim(f, t.astype(f.dtype), row, 0),
                f,
            ),
            finals, (hT, cT),
        )
        # relay microbatch j's carry to device d+1 (stage s+1); device 0
        # instead seeds the NEXT microbatch fresh
        send = jax.tree.map(
            lambda t: jnp.where(valid, t, jnp.zeros_like(t)), (hT, cT)
        )
        recv = jax.tree.map(
            lambda t: jax.lax.ppermute(t, axis_name, _ring_perm(n)), send
        )
        carry = jax.tree.map(
            lambda f, r: jnp.where(idx == 0, f, r), fresh(s + 1), recv
        )
    # only device n-1 wrote finals; a psum broadcasts them everywhere
    final = jax.tree.map(
        lambda t: jax.lax.psum(t, axis_name) if n > 1 else t, finals
    )
    return out, final


def reverse_sequence(x_local, axis_name: str = MODEL_AXIS, axis: int = 1):
    """Time-reverse a sequence that is sharded on ``axis_name``.

    If device i holds chunk i of the global sequence, after this call device i
    holds chunk i of the *reversed* global sequence: one ``ppermute`` swaps
    chunk i ↔ chunk n-1-i, and a local flip reverses within the chunk. Used by
    the ring bidirectional LSTM (the reference's reverse direction runs the
    cell over ``torch.flip(x, (1,))``, ``comps/icalstm/models.py:60-65``).
    Self-inverse, and its AD transpose is itself (ppermute + flip are both
    linear and self-inverse here), so gradients route back to the owning chunk.
    """
    n = jax.lax.axis_size(axis_name)
    swapped = jax.lax.ppermute(
        x_local, axis_name, [(i, n - 1 - i) for i in range(n)]
    )
    return jnp.flip(swapped, axis=axis)


def shard_sequence(x, axis_name: str = MODEL_AXIS, axis: int = 1):
    """Split a gathered [B, T, ...] array into this device's chunk."""
    n = jax.lax.axis_size(axis_name)
    idx = jax.lax.axis_index(axis_name)
    T = x.shape[axis]
    chunk = T // n
    return jax.lax.dynamic_slice_in_dim(x, idx * chunk, chunk, axis=axis)


def gather_sequence(x_local, axis_name: str = MODEL_AXIS, axis: int = 1):
    """Inverse of :func:`shard_sequence` — all-gather chunks back to [B, T, ...]."""
    return jax.lax.all_gather(x_local, axis_name, axis=axis, tiled=True)
