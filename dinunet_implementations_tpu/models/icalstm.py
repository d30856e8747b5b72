"""ICALstm — the ICA-timecourse bidirectional LSTM classifier.

Capability parity with reference ``comps/icalstm/models.py:5-110``:

- per-window encoder ``Linear(num_comps*window → input_size) + ReLU``
  (the reference applies it in a Python loop over the batch,
  ``models.py:107``; here it is one batched matmul over ``[B*S]`` rows);
- hand-rolled (bi)LSTM: per direction a cell with ``i2h: (D → 4H)``,
  ``h2h: (H → 4H)``; ``hidden_size`` is split across directions
  (``models.py:55-57``); the reverse direction runs over the time-flipped
  input and hidden sequences concat on the feature dim (``models.py:60-65``);
- mean-pool over time, then the classifier head
  ``Dropout(0.25) → Linear(H→256) → BatchNorm1d(256) → ReLU → Linear(256→64)
  → ReLU → Linear(64→num_cls)`` (``models.py:96-104``).

**Gate math.** The reference cell has a numerical quirk
(``models.py:31-38``): it applies ``sigmoid`` to the i/f/o pre-activations
*twice* (``gates = preact[:, :3H].sigmoid()`` then ``sigmoid(gates[...])``),
while ``g`` uses ``tanh`` of the raw pre-activation. ``double_sigmoid_gates``
reproduces that bit-for-bit for parity runs; the default is standard LSTM
gates (single sigmoid), which trains strictly better.

TPU-first shape of the recurrence: the input projection for *all* timesteps is
hoisted out of the loop into one ``[B*T, D] @ [D, 4H]`` MXU matmul; only the
``h @ W_hh`` recurrence stays inside ``lax.scan`` (sequential by nature).
"""

from __future__ import annotations

import flax.linen as nn
import jax
import jax.numpy as jnp

from .layers import BatchNorm, TorchLinearInit, compute_dtype_of, dense


def _lstm_gates(preact, H, double_sigmoid: bool):
    if double_sigmoid:
        gates = jax.nn.sigmoid(preact[..., : 3 * H])
        i = jax.nn.sigmoid(gates[..., :H])
        f = jax.nn.sigmoid(gates[..., H : 2 * H])
        o = jax.nn.sigmoid(gates[..., 2 * H : 3 * H])
    else:
        i = jax.nn.sigmoid(preact[..., :H])
        f = jax.nn.sigmoid(preact[..., H : 2 * H])
        o = jax.nn.sigmoid(preact[..., 2 * H : 3 * H])
    g = jnp.tanh(preact[..., 3 * H :])
    return i, f, o, g


def _auto_pallas() -> bool:
    # The fused kernel uses TPU-only pltpu.VMEM specs; any other accelerator
    # (e.g. GPU) must fall back to the lax.scan path rather than crash.
    return jax.default_backend() == "tpu"


class LSTMCell(nn.Module):
    """One direction over a full sequence: x [B, T, D] → hidden seq [B, T, H].

    Reference ``comps/icalstm/models.py:5-45`` — but the Python
    loop-over-timesteps becomes ``lax.scan`` (or the fused Pallas recurrence
    kernel, ops/lstm_pallas.py) and the i2h projection one batched matmul.

    ``use_pallas``: None = auto (fused kernel on accelerators, scan on CPU);
    the double-sigmoid compat mode always uses the scan path.
    """

    hidden_size: int
    double_sigmoid_gates: bool = False
    use_pallas: bool | None = None
    compute_dtype: str | None = None  # e.g. "bfloat16"; None = f32 (parity)

    @nn.compact
    def __call__(self, x, h0=None):
        B, T, D = x.shape
        H = self.hidden_size
        w_ih = self.param("w_ih", TorchLinearInit.kernel, (D, 4 * H))
        b_ih = self.param("b_ih", TorchLinearInit.bias_for(D), (4 * H,))
        w_hh = self.param("w_hh", TorchLinearInit.kernel, (H, 4 * H))
        b_hh = self.param("b_hh", TorchLinearInit.bias_for(H), (4 * H,))

        cdt = compute_dtype_of(self.compute_dtype)
        if h0 is None:
            # carry is always f32: the scan body computes an f32 carry (scan
            # requires carry-type invariance) and the kernel keeps f32 carries
            h0 = (jnp.zeros((B, H), jnp.float32), jnp.zeros((B, H), jnp.float32))

        use_pallas = (
            self.use_pallas if self.use_pallas is not None else _auto_pallas()
        ) and not self.double_sigmoid_gates
        if use_pallas:
            # fused kernel: i2h projection runs in-kernel with W_ih resident
            # in VMEM — streams x [T, B, D] once instead of a pre-projected
            # [T, B, 4H] (no XLA-side xi materialization at all)
            from ..ops.lstm_pallas import lstm_forward_fused

            return lstm_forward_fused(
                x, w_ih, b_ih + b_hh, w_hh, h0[0], h0[1], compute_dtype=cdt
            )

        if cdt is not None:
            # scan path: hoist the i2h projection for all timesteps into one
            # bf16 MXU matmul (f32 accum); XLA fuses the downcast epilogue
            xi = (jnp.dot(
                x.astype(cdt), w_ih.astype(cdt),
                preferred_element_type=jnp.float32,
            ) + (b_ih + b_hh)).astype(cdt)
        else:
            xi = x @ w_ih + (b_ih + b_hh)  # [B, T, 4H] — one matmul

        def step(carry, xt):
            h, c = carry
            if cdt is not None:
                preact = xt + jnp.dot(
                    h.astype(cdt), w_hh.astype(cdt),
                    preferred_element_type=jnp.float32,
                )
            else:
                preact = xt + h @ w_hh
            i, f, o, g = _lstm_gates(preact, H, self.double_sigmoid_gates)
            c = f * c + i * g
            h = o * jnp.tanh(c)
            return (h, c), h

        (hT, cT), hs = jax.lax.scan(step, h0, jnp.swapaxes(xi, 0, 1))
        return jnp.swapaxes(hs, 0, 1), (hT, cT)


class _LSTMCellParams(nn.Module):
    """Parameter-only twin of :class:`LSTMCell` — declares the exact same
    param tree (names, shapes, inits) without running the recurrence, so the
    streaming step (:class:`_StreamLSTM`) can own the compute while
    checkpoints/params remain interchangeable with the cell modules."""

    in_dim: int
    hidden: int

    @nn.compact
    def __call__(self):
        D, H = self.in_dim, self.hidden
        w_ih = self.param("w_ih", TorchLinearInit.kernel, (D, 4 * H))
        b_ih = self.param("b_ih", TorchLinearInit.bias_for(D), (4 * H,))
        w_hh = self.param("w_hh", TorchLinearInit.kernel, (H, 4 * H))
        b_hh = self.param("b_hh", TorchLinearInit.bias_for(H), (4 * H,))
        return w_ih, b_ih + b_hh, w_hh


class BiLSTM(nn.Module):
    """Bidirectional wrapper (reference ``comps/icalstm/models.py:48-66``):
    ``hidden_size`` is the *total* width, split across directions.

    ``sequence_axis``: when set (a bound mesh axis name, normally
    ``parallel.mesh.MODEL_AXIS``), ``x`` is this device's time chunk of a
    sequence sharded over that axis; each direction runs as a ring LSTM
    (parallel/sequence.py) with the carry relayed around the ring. Submodule
    names match the dense path, so params are interchangeable.
    """

    hidden_size: int
    bidirectional: bool = True
    double_sigmoid_gates: bool = False
    use_pallas: bool | None = None
    compute_dtype: str | None = None
    sequence_axis: str | None = None
    # ring-LSTM wavefront microbatches (parallel/sequence.py): 0 = auto
    sequence_microbatches: int = 0
    # time_pool="mean": return the time-mean [B, H_total] instead of the
    # hidden sequence. Numerically identical to mean-pooling the concat
    # (column blocks reduce independently), but the [B, T, 2*per_dir] concat
    # never materializes — its per-direction boundary sits at a non-lane-
    # aligned feature offset (e.g. 174), and profiling the 32-site bench
    # showed XLA spending ~0.5 ms/round on relayout copies plus a slowed
    # reverse-direction backward kernel whose dhs cotangent arrived
    # lane-rotated. Dense path only (the ring path pools in ICALstm). Each
    # direction is its own kernel call: one sweep advancing both measured
    # 27 % slower on the chip (docs/bench_ab_bidir_r5.jsonl).
    time_pool: str | None = None

    @nn.compact
    def __call__(self, x, h0=None):
        if self.time_pool not in (None, "mean"):
            raise ValueError(f"unknown time_pool {self.time_pool!r}")
        if self.time_pool is not None and self.sequence_axis is not None:
            # a local-chunk mean would silently violate the global-mean
            # contract on a sequence-sharded input; pooling across chunks is
            # the caller's job (ICALstm's all_gather reduction)
            raise ValueError("time_pool requires sequence_axis=None")
        pool = (lambda s: jnp.mean(s, axis=1)) if self.time_pool == "mean" else (lambda s: s)
        per_dir = self.hidden_size // (2 if self.bidirectional else 1)

        fwd_cell = LSTMCell(
            per_dir, self.double_sigmoid_gates, self.use_pallas,
            self.compute_dtype, name="fwd"
        )
        if self.sequence_axis is None:
            fwd, (h, c) = fwd_cell(x, h0)
        else:
            from ..parallel.sequence import reverse_sequence, ring_lstm

            if h0 is None:
                z = jnp.zeros((x.shape[0], per_dir), jnp.float32)
                h0 = (z, z)
            fwd, (h, c) = ring_lstm(
                lambda xc, carry: fwd_cell(xc, carry), x, h0[0], h0[1],
                axis_name=self.sequence_axis,
                microbatches=self.sequence_microbatches or None,
            )
        if not self.bidirectional:
            return pool(fwd), (h, c)
        rev_cell = LSTMCell(
            per_dir, self.double_sigmoid_gates, self.use_pallas,
            self.compute_dtype, name="rev"
        )
        if self.sequence_axis is None:
            rev, (hr, cr) = rev_cell(jnp.flip(x, axis=1), h0)
        else:
            # reverse direction = the cell over the time-reversed GLOBAL
            # sequence; reverse_sequence re-shards it so device i holds
            # reversed-chunk i, making the local concat line up with the dense
            # path's (no flip-back, as the reference) hidden concat
            rev, (hr, cr) = ring_lstm(
                lambda xc, carry: rev_cell(xc, carry),
                reverse_sequence(x, self.sequence_axis, axis=1),
                h0[0], h0[1], axis_name=self.sequence_axis,
                microbatches=self.sequence_microbatches or None,
            )
        return (
            jnp.concatenate([pool(fwd), pool(rev)], axis=-1),
            (jnp.concatenate([h, hr], 1), jnp.concatenate([c, cr], 1)),
        )


class _StreamLSTM(nn.Module):
    """Streaming (single-direction) LSTM step over a CHUNK of new windows,
    with the mean-pool accumulator folded into the recurrence carry — the
    O(1) autoregressive state of the serving path (serving/session.py).

    Declares the exact ``fwd`` cell param tree of the dense path
    (:class:`_LSTMCellParams`), so a trained unidirectional :class:`ICALstm`
    checkpoint drives this module unchanged. The carry is ``(h, c, pooled,
    count)``: hidden/cell state plus the running hidden-state SUM and valid
    timestep count — everything the classifier head needs, at a size
    independent of how many windows the session has already consumed.

    Bit-exact chunk composition: the pooled sum accumulates INSIDE the
    ``lax.scan`` (a strict left fold in time order), so feeding windows
    ``[0..t1)`` then ``[t1..T)`` performs literally the same sequence of
    additions as feeding ``[0..T)`` in one chunk — streaming in chunks is
    bitwise identical to full-sequence replay through this module
    (tests/test_serving.py). ``step_valid`` gates padded chunk slots: an
    invalid step is an exact identity on all four carry parts, so
    time-padding a short chunk up to its shape bucket cannot perturb the
    session."""

    hidden: int
    compute_dtype: str | None = None

    @nn.compact
    def __call__(self, enc, h, c, pooled, count, step_valid):
        D = enc.shape[-1]
        w_ih, b, w_hh = _LSTMCellParams(D, self.hidden, name="fwd")()
        cdt = compute_dtype_of(self.compute_dtype)
        if cdt is not None:
            # mirror LSTMCell's mixed-precision scan path op-for-op: bf16
            # MXU matmuls with f32 accumulation, bf16 xi stream
            xi = (jnp.dot(
                enc.astype(cdt), w_ih.astype(cdt),
                preferred_element_type=jnp.float32,
            ) + b).astype(cdt)
        else:
            xi = enc @ w_ih + b  # [B, t, 4H] — one hoisted matmul

        H = self.hidden

        def step(carry, inp):
            h, c, pooled, count = carry
            xt, sv = inp  # [B, 4H] pre-projected window, [B] valid gate
            if cdt is not None:
                preact = xt + jnp.dot(
                    h.astype(cdt), w_hh.astype(cdt),
                    preferred_element_type=jnp.float32,
                )
            else:
                preact = xt + h @ w_hh
            i, f, o, g = _lstm_gates(preact, H, False)
            c_new = f * c + i * g
            h_new = o * jnp.tanh(c_new)
            live = sv[:, None] > 0
            # invalid steps are exact identities: h/c/pooled hold, count
            # adds sv == 0 — a padded slot can never move the session
            return (
                jnp.where(live, h_new, h),
                jnp.where(live, c_new, c),
                jnp.where(live, pooled + h_new, pooled),
                count + sv,
            ), None

        (h, c, pooled, count), _ = jax.lax.scan(
            step,
            (h, c, pooled, count),
            (jnp.swapaxes(xi, 0, 1), jnp.swapaxes(step_valid, 0, 1)),
        )
        return h, c, pooled, count


class ICALstmStream(nn.Module):
    """Streaming twin of :class:`ICALstm` — the serving path's O(1) per-chunk
    step (serving/engine.py).

    Same parameter tree as the dense model (submodule names ``encoder`` /
    ``lstm/fwd`` / ``cls_fc1`` / ``cls_bn`` / ``cls_fc2`` / ``cls_fc3``), so
    one trained checkpoint serves both the batched full-sequence path and
    this incremental one. Processes only the chunk's NEW windows (encoder +
    recurrence from the carried ``(h, c)``), updates the scan-accumulated
    mean-pool state, and re-runs the tiny classifier head on the updated
    pool — cost per chunk is independent of the session's history length.

    Unidirectional only (``ICALstm(bidirectional=False)`` checkpoints): the
    reverse direction of a biLSTM reads the future, so no O(1) carry can
    reproduce it — the serving engine refuses streaming for bidirectional
    checkpoints rather than approximate them (docs/ARCHITECTURE.md
    "Serving"). Dropout is eval-mode (identity) by construction; the head
    BatchNorm runs on the checkpoint's running stats, so co-batched sessions
    never perturb each other."""

    input_size: int = 256
    hidden_size: int = 256
    num_cls: int = 2
    num_comps: int = 53
    window_size: int = 20
    compute_dtype: str | None = None

    @nn.compact
    def __call__(self, x, h, c, pooled, count, step_valid):
        # x: [B, t, C, W] new windows; h/c/pooled: [B, H]; count: [B];
        # step_valid: [B, t] (1.0 = real window, 0.0 = chunk padding)
        B, t = x.shape[0], x.shape[1]
        flat = x.reshape(B, t, -1)
        cdt = compute_dtype_of(self.compute_dtype)
        enc = nn.relu(
            dense(self.input_size, fan_in=self.num_comps * self.window_size,
                  name="encoder", dtype=cdt)(flat)
        )
        h, c, pooled, count = _StreamLSTM(
            self.hidden_size, self.compute_dtype, name="lstm"
        )(enc, h, c, pooled, count, step_valid)
        # classifier head on the running mean — identical layer stack (and
        # eval semantics) to ICALstm's; Dropout is a train-only no-op there
        o = (pooled / jnp.maximum(count, 1.0)[:, None]).astype(jnp.float32)
        o = dense(256, fan_in=o.shape[-1], name="cls_fc1")(o)
        o = BatchNorm(256, track_running_stats=True, name="cls_bn")(
            o, train=False
        )
        o = nn.relu(o)
        o = nn.relu(dense(64, fan_in=256, name="cls_fc2")(o))
        logits = dense(self.num_cls, fan_in=64, name="cls_fc3")(o)
        return logits, (h, c, pooled, count)


class ICALstm(nn.Module):
    input_size: int = 256
    hidden_size: int = 256
    bidirectional: bool = True
    num_cls: int = 2
    num_comps: int = 53
    window_size: int = 20
    num_layers: int = 1  # parity field; reference builds 1 layer regardless
    double_sigmoid_gates: bool = False
    dropout_rate: float = 0.25
    use_pallas: bool | None = None  # None = auto (kernel on accelerators)
    compute_dtype: str | None = None  # "bfloat16" = mixed precision (f32 accum)
    sequence_microbatches: int = 0  # ring wavefront microbatches; 0 = auto
    # Sequence parallelism (TPU extension, SURVEY.md §2.2): a bound mesh axis
    # name (parallel.mesh.MODEL_AXIS) shards the window axis S across that
    # axis — the encoder runs on the local chunk, the BiLSTM relays its carry
    # ring-style, and the time mean-pool finishes with an all_gather. Callers
    # pass the FULL [B, S, C, W] batch (replicated over the axis); the model
    # takes its own chunk. Init outside the mesh with sequence_axis=None —
    # param shapes/names are identical (FederatedTask.init_variables does this).
    sequence_axis: str | None = None

    @nn.compact
    def __call__(self, x, train: bool = True, mask=None):
        # x: [B, S, C, W] (windows, components, timepoints-per-window)
        B, S = x.shape[0], x.shape[1]
        flat = x.reshape(B, S, -1)  # [B, S, C*W]
        if self.sequence_axis is not None:
            from ..parallel.sequence import shard_sequence

            n = jax.lax.axis_size(self.sequence_axis)
            if S % n:
                raise ValueError(
                    f"sequence parallelism needs windows ({S}) divisible by "
                    f"the {self.sequence_axis!r} axis size ({n})"
                )
            flat = shard_sequence(flat, self.sequence_axis, axis=1)
        cdt = compute_dtype_of(self.compute_dtype)
        # under compute_dtype the encoder output stays bf16 — it feeds the
        # per-direction i2h projections, which consume bf16 directly
        enc = nn.relu(
            dense(self.input_size, fan_in=self.num_comp_window, name="encoder",
                  dtype=cdt)(flat)
        )
        o, h = BiLSTM(
            self.hidden_size,
            self.bidirectional,
            self.double_sigmoid_gates,
            self.use_pallas,
            self.compute_dtype,
            self.sequence_axis,
            sequence_microbatches=self.sequence_microbatches,
            # dense path: pool inside BiLSTM per direction — same values as
            # mean-pooling the concat (models.py:109) without materializing
            # the lane-misaligned [B, T, H_total] sequence concat
            time_pool=None if self.sequence_axis is not None else "mean",
            name="lstm",
        )(enc)
        if self.sequence_axis is not None:
            # mean over the GLOBAL window axis: local sum, then all_gather
            # (transpose = reduce-scatter, so chunk cotangents route back to
            # the owning device — sound under AD, unlike a bare psum here)
            o = jax.lax.all_gather(
                o.sum(axis=1), self.sequence_axis
            ).sum(axis=0) / S
        o = o.astype(jnp.float32)  # classifier head + BN stay full precision

        # classifier head (models.py:96-104); per-direction width totals
        # hidden_size when bidirectional splits evenly, else 2*(H//2).
        o = nn.Dropout(self.dropout_rate, deterministic=not train)(o)
        o = dense(256, fan_in=o.shape[-1], name="cls_fc1")(o)
        o = BatchNorm(256, track_running_stats=True, name="cls_bn")(
            o, train=train, mask=mask
        )
        o = nn.relu(o)
        o = nn.relu(dense(64, fan_in=256, name="cls_fc2")(o))
        return dense(self.num_cls, fan_in=64, name="cls_fc3")(o)

    @property
    def num_comp_window(self):
        return self.num_comps * self.window_size
