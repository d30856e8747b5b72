"""Mixture-of-experts decoders as a federated next-token task: the Trinity
family's ``model_type: afmoe``, ``model_type: glm4_moe_lite`` (latent
attention on every layer, one multi-token-prediction module),
``model_type: smallthinker`` (the router before attention, ReGLU experts),
``model_type: lfm2_moe`` (a gated short convolution for attention in three
layers of four, a tied head) and ``model_type: qwen3_next`` (Gated DeltaNet
linear attention in three layers of four). The five share the expert layer's
grouped products, the norms, the rotary term, the attention kernels, the
blocked head and the loss; they differ in the token mixer and its
projections, in the routing rule and what it reads, and in the block around
them, which ``Dims.model_type`` selects.

One chip's SHARE of the model: the expert layer is told which routed experts
it holds (``experts_held`` of ``num_experts``, from ``first_expert``), routes
every token over all ``num_experts``, and computes its own experts' part of
the result; the embedding and the head hold ``vocab_rows`` rows of the
vocabulary. What the absent experts would add is left out (the exchange that
would bring it lives on other chips); nothing here stands in for them.

TRINITY'S block, ``afmoe`` (x: ``[T, hidden]`` float32 residual stream;
matmuls in ``compute_dtype`` with float32 accumulation; norms, softmax,
router, rotary angles, the residual and the loss in float32):

- ``h0 = E[tok] * sqrt(hidden)`` (``mup_enabled``);
- attention, every layer: ``a = RMSNorm(h)``; ``q, k, v = a Wq, a Wk, a Wv``;
  ``q, k <- RMSNorm_head(q), RMSNorm_head(k)``; rotary positions on
  ``sliding_attention`` layers only (a ``full_attention`` layer has no
  positional term); grouped-query scores ``q k^T / sqrt(head_dim)``, query head
  ``n`` reading key-value head ``n // (heads / kv_heads)``, masked to ``j <=
  i`` and, on sliding layers, ``j > i - window``; softmax; ``o = P v``; ``o <-
  o * sigmoid(a Wg)``; ``h <- h + RMSNorm(o Wo)``;
- MLP, dense layers: ``m = RMSNorm(h)``; ``h <- h + RMSNorm(swiglu(m))``;
- MLP, MoE layers: ``s = sigmoid(m Wr)``; ``sel = top_k(s + b)`` (``b`` the
  ``expert_bias`` buffer: selection only, no gradient); ``w = s[sel]``,
  normalized (``route_norm``) and scaled (``route_scale``); ``y = shared(m) +
  sum over sel that are held of w_e expert_e(m)``; ``h <- h + RMSNorm(y)``;
- ``logits = RMSNorm(h) W_head``; the loss is the mean over the sequence's
  positions of the cross-entropy against the next token.

THE OTHER TYPE'S block, ``glm4_moe_lite`` (DeepSeek-V2/V3's equations, which
this ``model_type`` reuses; same precisions). Of the above it keeps the MoE
layer's equations, the dense MLP, the head and the loss, and replaces the rest:

- ``h0 = E[tok]`` (no scale); two pre-norms a block and no post-norm:
  ``h <- h + Attn(RMSNorm(h))``, ``h <- h + FFN(RMSNorm(h))``;
- latent attention (MLA), every layer, ``a = RMSNorm(h)``: ``c_q = RMSNorm(a
  W_qa)`` (``q_lora_rank``); ``q = c_q W_qb`` as heads of ``qk_nope_head_dim
  + qk_rope_head_dim``, split ``q_nope | q_rope``; ``[c_kv | k_r] = a W_kva``
  (``kv_lora_rank | qk_rope_head_dim``); ``c_kv <- RMSNorm(c_kv)``; ``[k_nope
  | v] = c_kv W_kvb`` as heads of ``qk_nope_head_dim | v_head_dim``; rotary
  positions on ``q_rope`` of every head and on ``k_r``, which all heads
  SHARE; ``k = [k_nope | k_r]``; scores ``q k^T / sqrt(qk_nope_head_dim +
  qk_rope_head_dim)``, causal, softmax; ``o = P v``; ``out = o W_o``. No gate,
  no QK-norm, no window: every layer is full causal attention WITH a
  positional term. The up-projected form only (training): the absorbed form
  and a latent cache are a serving path's, which this task has not;
- multi-token prediction, depth 1 (DeepSeek-V3 section 2.2;
  ``num_nextn_predict_layers`` 1): ``h'_i = [RMSNorm_e(E[t_{i+1}]) ;
  RMSNorm_h(h_i)] W_eh`` with ``h`` the last block's output before the final
  norm, one more MoE block, then the main model's head form with the module's
  own norm and the SHARED ``W_head`` and ``E``, predicting ``t_{i+2}``; the
  training loss is ``L_main + mtp_loss_weight * L_mtp``, ``L_mtp`` the mean
  over the ``T - 1`` positions that have such a target.

THE THIRD TYPE'S block, ``smallthinker`` (SmallThinker-21BA3B-Instruct; same
precisions; every layer an expert layer, no dense layer, no shared expert, no
bias). With ``h`` the block's input:

- ``h0 = E[tok]`` (no scale); two pre-norms a block and no post-norm, as the
  type above;
- the router, BEFORE attention and on the un-normed input: ``r = h W_r``;
  ``sel = top_k(r)``; ``p = softmax(r[sel])`` over the chosen alone (no
  selection bias, no scale: no ``expert_bias`` in the tree). The experts a
  token goes to are a function of the block's INPUT, and the router's
  gradient reaches ``h`` past the attention;
- attention: ``a = RMSNorm(h)``; ``q, k, v = a Wq, a Wk, a Wv`` (grouped
  queries as Trinity's; no QK-norm, no output gate: neither ``q_norm``,
  ``k_norm`` nor ``wg`` in the tree); rotary positions on sliding layers
  only, a full layer has NO positional term (Trinity's rule; the full layer
  is FIRST in the period of four); ``h' = h + o Wo``;
- experts: ``m = RMSNorm(h')``; ``y = sum over sel that are held of p_e
  (relu(m W1_e) * (m W3_e)) W2_e`` (ReGLU for SwiGLU; ``p`` is not
  renormalised over the held ones); ``h_next = h' + y``.

THE FOURTH TYPE'S block, ``lfm2_moe`` (LFM2-8B-A1B; same precisions; two
pre-norms a block and no post-norm, no embedding scale, no bias, no shared
expert). ``layer_types`` names each layer's token mixer, which sits where
attention sits (under the module name ``attn``, whichever it is):

- ``conv``, the gated short convolution (:class:`ShortConv`), ``a =
  RMSNorm(h)``: ``[B | C | x] = a W_in`` (hidden -> 3 x hidden); ``u = B *
  x``; ``c_t = sum_{j < L} f_j * u_{t - (L - 1) + j}`` with ``u`` zero before
  position 0: a causal depth-wise convolution over ``conv_L_cache`` = ``L``
  positions, one ``L``-tap filter a channel; ``o = (C * c) W_out``. No
  positions, no softmax, no state beyond ``L - 1`` rows;
- ``full_attention``: grouped queries; RMSNorm over each head on ``q`` and
  ``k``, THEN rotary over the whole head; causal, no window, no gate: a
  full layer WITH a positional term, at head width 64 (half a lane tile: the
  kernels take it natively, the rotary hand-over kernel does not and XLA's
  ``rotary`` stays);
- experts: Trinity's rule (``sigmoid``, top-k of ``s + expert_bias``, the
  chosen over their sum, ``route_scale``) with SwiGLU experts; the leading
  ``num_dense_layers`` layers a dense SwiGLU MLP;
- the head is the embedding's own matrix (``tie_word_embeddings``): the tree
  has no ``lm_head``, ``logits = RMSNorm(h) E^T``, and the embedding's
  gradient is the sum of its two uses.

THE FIFTH TYPE'S block, ``qwen3_next`` (Qwen3-Next; same precisions; two
pre-norms a block and no post-norm, no embedding scale, no bias, an untied
head). ``layer_types`` names each layer's token mixer, a full layer every
``global_attn_every_n_layers``-th (the published ``full_attention_interval``):

- ``linear_attention``, Gated DeltaNet (:class:`GatedDeltaNet`, under the
  module name ``linear_attn``), ``a = RMSNorm(h)``: ``[q | k | v | z] = a
  W_qkvz``, ``[b | a'] = a W_ba``; ``q, k, v = silu(conv(q | k | v))``, the
  causal depth-wise convolution of :func:`causal_conv` over
  ``linear_conv_kernel_dim`` positions; value head ``h`` reads key head ``h //
  (value heads / key heads)``; ``q <- l2norm(q) / sqrt(d_k)``, ``k <-
  l2norm(k)``; ``beta = sigmoid(b)``, ``g = -exp(A_log) softplus(a' +
  dt_bias)``; the gated delta rule (:func:`chunked_delta_rule`), from ``S = 0``
  a value head: ``S <- exp(g_t) S``; ``S <- S + k_t beta_t (v_t - S^T k_t)^T``;
  ``o_t = S^T q_t``; ``o <- RMSNorm(o) * silu(z)`` over each value head;
  ``out = o W_out``;
- ``full_attention``: ``q``, a gate and ``k``, ``v`` from four projections;
  RMSNorm over each head on ``q`` and ``k``, THEN rotary over the first
  ``head_dim * partial_rotary_factor`` dimensions of each head only; causal,
  no window; ``o <- o * sigmoid(gate)``; ``out = o W_o``;
- experts: a softmax over all ``num_experts`` router logits, top-k,
  renormalised over the chosen (the same as a softmax over the chosen logits
  alone: :func:`route_chosen`), SwiGLU experts, and a shared expert whose
  output is scaled by ``sigmoid(m w_sg)`` (``shared_expert_gate``).

Attention never builds a ``[T, T]`` tensor. On a TPU, at sequences of whole
kernel blocks, it is jax's splash-attention Pallas kernels (block-sparse flash
attention: a masked block is never visited); elsewhere it is XLA query blocks:
a sliding layer reads, for each block, only the ``window + block`` keys its
window touches, a full layer the causal prefix in ``kv_chunk`` steps. The
routed experts run as grouped matrix products (``jax.lax.ragged_dot``) over
the token assignments sorted by expert, walked in row chunks: dropless (the
index arrays hold the worst case, every token on held experts), memory and
work follow the real counts; the rows go back to token order in one gather,
or a token's ``k`` slots one at a time where ``[tokens, k, hidden]`` would be
past COMBINE_BYTES.
One ``jax.checkpoint`` a block, which keeps what ``BLOCK_KEEPS`` names and
recomputes the rest: the routed experts' output (their backward pass runs
their forward itself, chunk by chunk); where attention is the kernels, the
forward kernel's output and log-sum-exp, which are all of its result that the
backward kernels read, so the kernel runs once a layer a round; and the raw
key and value projections (of latent attention: the raw latent ``[c_kv |
k_r]``, a ninth of its keys' and values' width, from which the up-projection
is recomputed). The kernels' own inputs (``q``, ``k``, ``v`` after
QK-norm and rotary) are not kept: the norms' and rotary's backward passes need
the raw projections anyway, and from those the inputs are elementwise work.
The raw query projection, eight times a key's size, is recomputed too: kept,
it did not pay for its memory on the chip (PERF.md, PR 29). The head and the
loss run in sequence blocks so ``[T, vocab]`` is never whole in training.
"""

from __future__ import annotations

import dataclasses
import functools
import math

import flax.linen as nn
import jax
import jax.numpy as jnp
from jax.ad_checkpoint import checkpoint_name

from ..ops import delta_pallas, rope_pallas
from ..telemetry import scopes
from .layers import compute_dtype_of

SLIDING = "sliding_attention"
FULL = "full_attention"
CONV = "conv"  # a layer whose token mixer is the gated short convolution
LINEAR = "linear_attention"  # a layer whose token mixer is Gated DeltaNet
AFMOE = "afmoe"  # Dims.model_type: Trinity's block
GLM4_MOE_LITE = "glm4_moe_lite"  # latent attention, two pre-norms, MTP
SMALLTHINKER = "smallthinker"  # router before attention, ReGLU, two pre-norms
LFM2_MOE = "lfm2_moe"  # short convolutions, QK-norm then rotary, a tied head
QWEN3_NEXT = "qwen3_next"  # Gated DeltaNet, partial rotary, a gated shared expert
MODEL_TYPES = (AFMOE, GLM4_MOE_LITE, SMALLTHINKER, LFM2_MOE, QWEN3_NEXT)


def _init(std: float = 0.02):
    return nn.initializers.normal(std)


def rms_norm(x, scale, eps: float):
    x = x.astype(jnp.float32)
    var = jnp.mean(jnp.square(x), axis=-1, keepdims=True)
    return x * jax.lax.rsqrt(var + eps) * scale.astype(jnp.float32)


def _mm(x, w, cdt):
    """``x @ w`` with operands in the compute dtype, accumulated in float32."""
    if cdt is not None:
        x, w = x.astype(cdt), w.astype(cdt)
    return jnp.matmul(x, w, preferred_element_type=jnp.float32)


def rotary(x, positions, theta: float):
    """Rotate-half rotary embedding. ``x [..., T, heads, d]``, float32."""
    d = x.shape[-1]
    inv = 1.0 / (theta ** (jnp.arange(0, d, 2, dtype=jnp.float32) / d))
    ang = positions.astype(jnp.float32)[:, None] * inv[None, :]  # [T, d/2]
    cos = jnp.concatenate([jnp.cos(ang), jnp.cos(ang)], -1)[:, None, :]
    sin = jnp.concatenate([jnp.sin(ang), jnp.sin(ang)], -1)[:, None, :]
    x1, x2 = x[..., : d // 2], x[..., d // 2:]
    return x * cos + jnp.concatenate([-x2, x1], -1) * sin


def partial_rotary(x, positions, theta: float, dims: int):
    """:func:`rotary` over the first ``dims`` of each head's ``d``
    dimensions, the rest passed through (the whole head where ``dims == d``)."""
    if dims == x.shape[-1]:
        return rotary(x, positions, theta)
    return jnp.concatenate(
        [rotary(x[..., :dims], positions, theta), x[..., dims:]], -1)


def _attend(q, k, v, qpos, kpos, window):
    """One query block against one key span. ``q [B, Q, G, R, D]``, ``k, v
    [B, S, G, D]``; ``qpos [Q]`` / ``kpos [S]`` absolute positions (a negative
    ``kpos`` is padding). Float32 scores and softmax."""
    scale = q.shape[-1] ** -0.5
    s = jnp.einsum("bqgrd,bsgd->bgrqs", q, k,
                   preferred_element_type=jnp.float32) * scale
    ok = (kpos[None, :] <= qpos[:, None]) & (kpos[None, :] >= 0)
    if window is not None:
        ok &= kpos[None, :] > qpos[:, None] - window
    s = jnp.where(ok[None, None, None], s, -1e30)
    p = jax.nn.softmax(s, axis=-1)
    return jnp.einsum("bgrqs,bsgd->bqgrd", p.astype(v.dtype), v,
                      preferred_element_type=jnp.float32)


def blocked_attention(q, k, v, window, q_block: int, kv_chunk: int, cdt=None):
    """Causal (``window=None``) or causal-window grouped-query attention in
    query blocks. ``q [B, T, N, D]``, ``k, v [B, T, G, D]`` -> ``[B, T, N, D]``
    float32. Each block is recomputed in the backward pass (its scores are
    never kept)."""
    B, T, N, D = q.shape
    G = k.shape[2]
    qb = min(q_block, T)
    if T % qb:
        raise ValueError(f"sequence {T} is not a multiple of q_block {qb}")
    if cdt is not None:
        q, k, v = q.astype(cdt), k.astype(cdt), v.astype(cdt)
    q = q.reshape(B, T // qb, qb, G, N // G, D)
    attend = jax.checkpoint(functools.partial(_attend, window=window))

    if window is not None:
        # keys of block i: positions [i*qb - window, (i+1)*qb), read from a
        # front-padded copy so that every block has the same span
        span = window + qb
        pad = ((0, 0), (window, 0), (0, 0), (0, 0))
        kp, vp = jnp.pad(k, pad), jnp.pad(v, pad)

        def one(args):
            i, qi = args
            lo = i * qb
            ks = jax.lax.dynamic_slice_in_dim(kp, lo, span, axis=1)
            vs = jax.lax.dynamic_slice_in_dim(vp, lo, span, axis=1)
            return attend(qi, ks, vs, lo + jnp.arange(qb),
                          lo - window + jnp.arange(span))

        out = jax.lax.map(one, (jnp.arange(T // qb), jnp.moveaxis(q, 1, 0)))
        out = jnp.moveaxis(out, 0, 1)
    else:
        # the causal prefix, read in kv_chunk steps: the blocks of chunk c
        # see keys [0, (c+1)*kv_chunk)
        chunk = max(qb, min(kv_chunk, T) // qb * qb)
        outs = []
        for lo in range(0, T, chunk):
            hi = min(lo + chunk, T)
            ks, vs = k[:, :hi], v[:, :hi]
            kpos = jnp.arange(hi)

            def one(args, ks=ks, vs=vs, kpos=kpos):
                i, qi = args
                return attend(qi, ks, vs, i * qb + jnp.arange(qb), kpos)

            blocks = jnp.arange(lo // qb, hi // qb)
            outs.append(jax.lax.map(
                one, (blocks, jnp.moveaxis(q[:, lo // qb: hi // qb], 1, 0))))
        out = jnp.moveaxis(jnp.concatenate(outs, 0), 0, 1)
    return out.reshape(B, T, N, D)


# -- attention as a kernel ------------------------------------------------------
#
# On a TPU, at sequence lengths that are whole kernel blocks, attention runs as
# the splash-attention Pallas kernels that ship with jax (block-sparse flash
# attention: the causal and the causal-window mask are block maps, a masked
# block is never visited, scores live in VMEM only). Their names, as the
# compiler's instructions carry them (a transform wraps them), are constants
# here so that a metric's pattern has something to hold on to. They take
# their operands head-major (``splash_heads``); a layer with rotary positions
# whose heads are whole lane tiles writes them so from its raw projections in
# one pass (``ops/rope_pallas.py``), every other caller through XLA's
# cast, ``moveaxis`` and scale (``kernel_attention``).

ATTN_FWD = "splash_mqa_fwd"  # forward, keeps the log-sum-exp for the backward
ATTN_DQ = "splash_mqa_dq"  # backward: the queries' cotangent
ATTN_DKV = "splash_mqa_dkv"  # backward: the keys' and values' cotangents
KERNEL_BLOCK = 512  # the granule: a sequence the kernels take is whole such blocks
# the forward kernel's output and log-sum-exp, as a block's checkpoint knows
# them (the XLA path has no such value: its blocks are recomputed)
ATTN_OUT = "attention_kernel_out"
# the delta rule kernels' output and edge states, as a block's checkpoint knows
# them (the XLA path has no such value: its segments are recomputed)
DELTA_OUT = "delta_rule_kernel_out"


def _auto_pallas() -> bool:
    return jax.default_backend() == "tpu"


def _interpret() -> bool:
    return jax.default_backend() == "cpu"


# The kernels' block geometry: what was measured on a v5e (PERF.md §6, PRs 33, 34;
# ``scripts/kernel_tune.py --attention`` is the sweep). A kernel's time is
# within 4 % of ``steps * 0.4..0.75 us + blocks_computed * c(kernel, d)``: a
# grid step has a fixed cost, and a visited block is computed WHOLE however
# much of it the mask leaves. So blocks grow while the mask's band is wide
# beside them, and stay one granule where it is not.
MAX_EDGE = 1024  # 2,048-row blocks never won, and seldom fit the scope
BAND_EDGES = 4  # a block edge is at most this share of the band the mask leaves
COMPUTE_BLOCK = 512  # key columns a kernel works on at a time inside its key block
VMEM_BUDGET = 15 * 2 ** 20  # of the 16 MiB scope; no vmem_limit_bytes (PERF.md §6, PR 31)


ATTN_KERNELS = ("fwd", "dq", "dkv")


def block_sizes(fwd, dq, dkv, **layouts):
    """A splash-attention ``BlockSizes`` from each kernel's ``(block_q,
    block_kv, compute)``; the dq kernel has no compute block."""
    from jax.experimental.pallas.ops.tpu.splash_attention import (
        splash_attention_kernel as sk,
    )

    return sk.BlockSizes(
        block_q=fwd[0], block_kv=fwd[1], block_kv_compute=fwd[2],
        block_q_dkv=dkv[0], block_kv_dkv=dkv[1], block_kv_dkv_compute=dkv[2],
        block_q_dq=dq[0], block_kv_dq=dq[1], **layouts)


def kernel_blocks(sizes) -> dict:
    """``{kernel: (block_q, block_kv, compute)}`` of a ``BlockSizes``: the
    inverse of :func:`block_sizes` (dq's compute block reads its key block)."""
    return {"fwd": (sizes.block_q, sizes.block_kv, sizes.block_kv_compute),
            "dq": (sizes.block_q_dq, sizes.block_kv_dq, sizes.block_kv_dq),
            "dkv": (sizes.block_q_dkv, sizes.block_kv_dkv,
                    sizes.block_kv_dkv_compute)}


def attention_vmem_bytes(kernel: str, block_q: int, block_kv: int,
                         compute: int, head_dim: int, dtype) -> int:
    """VMEM one grid step of the ``"fwd"``, ``"dq"`` or ``"dkv"`` kernel
    holds: the blocks the pipeline moves, twice (its double buffer); the
    accumulators that stay for a whole row of steps, once; and the float32
    score tiles of the body. An upper bound fitted to what the TPU compiler
    allocated or refused over step 0's sweep (bfloat16, widths 128 and 256):
    it refuses every geometry the compiler refused, and a few it took. A
    head narrower than a lane tile (64) is held in whole 128-lane tiles: the
    compiler's verdicts at width 64 were those at 128 (PERF.md §6, PR 38)."""
    item = jnp.dtype(dtype).itemsize
    head_dim = -(-head_dim // 128) * 128
    q_rows = block_q * head_dim  # a [block_q, d] tile, in elements
    kv_rows = block_kv * head_dim
    lanes = block_q * 128 * 4  # a per-row statistic, lane-broadcast
    sublanes = 8 * block_q * 4  # the same, sublane-broadcast
    if kernel == "fwd":  # q, k, v, positions -> o, log-sum-exp; m, l, o_acc
        moved = (2 * q_rows + 2 * kv_rows) * item + 2 * lanes
        kept = 2 * lanes + 4 * q_rows
        body = 4 * block_q * block_kv + 8 * q_rows  # the slices are unrolled
    elif kernel == "dq":  # q, k, v, do, lse, di, positions -> dq; dq_acc
        moved = (3 * q_rows + 2 * kv_rows) * item + 2 * sublanes + lanes
        kept = 4 * q_rows
        body = 6 * block_q * block_kv  # no compute block: the whole tile
    elif kernel == "dkv":  # q, k, v, do, lse, di, positions -> dk, dv; accs
        moved = (2 * q_rows + 4 * kv_rows) * item + 3 * sublanes
        kept = 8 * kv_rows
        body = 12 * block_q * compute
    else:
        raise ValueError(kernel)
    return 2 * moved + kept + body


def attention_blocks(t: int, heads_per_kv: int, head_dim: int,
                     window: int | None, dtype, override=None):
    """The splash-attention ``BlockSizes`` of one call: query block, key block
    and compute block of the forward, the dq and the dkv kernel.

    A pure function of what the call can see. The rules, each from step 0's
    sweeps of the five shapes the language-model cells run (PERF.md §6, PRs 33
    and 34):

    - The granule is ``min(KERNEL_BLOCK, t)``; every block is a whole number
      of granules that divides ``t``, so whatever ``masked_attention`` takes
      runs (1,536 or 2,560 positions at 512, 128 at 128).
    - An edge is at most ``band / BAND_EDGES``, the band being the window, or
      ``t`` under the plain causal mask: a block is computed whole, so on a
      2,048-wide window 1,024-blocks compute a fifth more pairs than
      512-blocks and measured within 2 % of them either way; on a 4,096-wide
      window over 16,384 positions they compute a ninth more and took 6-8 %
      off every kernel; over a causal 8,192 they compute a sixteenth more
      and took 7-19 % off (the steps fall from 136 to 36 a head), over a
      causal 16,384 18-22 %.
    - Each kernel takes the first of (1,024 x 1,024), (512 x 1,024), (512 x
      512) (query x key: the long KEY block was second in every kernel) that
      these allow and whose :func:`attention_vmem_bytes` is within
      VMEM_BUDGET; head width and operand dtype enter here (float32 operands
      at width 256 leave the forward 512 x 1,024).
    - The compute block is COMPUTE_BLOCK: whole key blocks cost the forward
      5-10 %, 256 columns cost it 22 % at width 128 with 512-blocks.
    - A head HALF a lane tile wide (64, four query heads a key-value head,
      causal over 8,192: PR 38's sweep) is taken natively by all three
      kernels, costs what width 128 costs to the percent (the time follows
      the pairs, not the width) and ranks the blocks as width 128 does:
      1,024 x 1,024 took 18, 15 and 16 % off 512 x 512.

    ``heads_per_kv`` decides nothing: with eight, seven and one query heads a
    key-value head the kernels' gains followed the head width and the mask,
    and the compiler's VMEM verdicts were the same at seven as at eight
    (2,048 x 1,024 refused in the dkv kernel under the causal mask, taken
    under a window; 2,048 x 2,048 refused everywhere).

    ``override`` is for tests and ``scripts/kernel_tune.py``: one edge for
    every block, or a ``BlockSizes`` used as given.
    """
    del heads_per_kv
    gran = min(KERNEL_BLOCK, t)
    if isinstance(override, int):
        return block_sizes(*[(min(override, t),) * 3] * 3)
    if override is not None:
        return override
    band = t if window is None else min(window, t)
    compute = min(COMPUTE_BLOCK, gran)
    long = max((e for e in range(gran, MAX_EDGE + 1, gran)
                if t % e == 0 and e * BAND_EDGES <= band), default=gran)

    def blocks(kernel):
        for bq, bkv in ((long, long), (gran, long)):
            if attention_vmem_bytes(kernel, bq, bkv, compute, head_dim,
                                    dtype) <= VMEM_BUDGET:
                return bq, bkv, compute
        return gran, gran, compute

    return block_sizes(*map(blocks, ATTN_KERNELS))


@functools.lru_cache(maxsize=None)
def _splash(t: int, heads_per_kv: int, window: int | None, sizes,
            interpret: bool):
    from jax.experimental.pallas.ops.tpu.splash_attention import (
        splash_attention_kernel as sk,
        splash_attention_mask as sm,
    )

    one = (sm.CausalMask((t, t)) if window is None
           else sm.LocalMask((t, t), window_size=(window - 1, 0), offset=0))
    kernel = sk.make_splash_mqa_single_device(
        sm.MultiHeadMask([one] * heads_per_kv), block_sizes=sizes,
        residual_checkpoint_name=ATTN_OUT, interpret=interpret)
    names = {sk.get_kernel_name(True, phase == "fwd", False, phase)
             for phase in ("fwd", "dq", "dkv")}
    assert all(n.startswith((ATTN_FWD, ATTN_DQ, ATTN_DKV)) for n in names), names
    return kernel


def kernel_attention(q, k, v, window, blocks=None, cdt=None):
    """:func:`blocked_attention`'s result from the splash-attention kernels:
    every key-value head's group of query heads is one multi-query call, in
    the blocks :func:`attention_blocks` chooses (``blocks`` is its
    ``override``)."""
    B, T, N, D = q.shape
    G = k.shape[2]
    if cdt is not None:
        q, k, v = q.astype(cdt), k.astype(cdt), v.astype(cdt)
    qh = jnp.moveaxis(q.reshape(B, T, G, N // G, D), 1, 3) * (D ** -0.5)
    return splash_heads(qh, jnp.moveaxis(k, 1, 2), jnp.moveaxis(v, 1, 2),
                        window, blocks)


def splash_heads(qh, kh, vh, window, blocks=None):
    """The kernels on operands in their own layout: ``qh [B, G, R, T, D]``
    (scaled by ``D ** -0.5``), ``kh, vh [B, G, T, D]`` -> ``[B, T, G * R, D]``
    float32."""
    B, G, R, T, D = qh.shape
    with jax.ensure_compile_time_eval():
        sizes = attention_blocks(T, R, D, window, qh.dtype, override=blocks)
        kernel = _splash(T, R, window, sizes, _interpret())
    out = jax.vmap(jax.vmap(kernel))(qh, kh, vh)  # sequences, key-value heads
    return jnp.moveaxis(out, 3, 1).reshape(B, T, G * R, D).astype(jnp.float32)


def takes_kernels(t: int) -> bool:
    """Whether attention over ``t`` positions runs as the kernels here: on a
    TPU, at sequences of whole kernel blocks."""
    return _auto_pallas() and t % min(KERNEL_BLOCK, t) == 0 and t >= 128


def masked_attention(q, k, v, window, q_block: int, kv_chunk: int, cdt):
    """The kernels on a TPU at sequences of whole kernel blocks, the XLA
    blocks elsewhere."""
    if takes_kernels(q.shape[1]):
        return kernel_attention(q, k, v, window, cdt=cdt)
    return blocked_attention(q, k, v, window, q_block, kv_chunk, cdt)


# -- the routed experts ------------------------------------------------------
#
# ``jax.lax.ragged_dot`` is the grouped product (on a TPU the compiler turns it
# into a kernel that walks the groups' row tiles, so its work follows the
# group sizes). It has no batched form there, and the trainer folds sites by
# ``vmap``: the expert layer therefore carries its own vmap rule, which folds
# the mapped axis INTO the groups (group = (site, expert), rows sorted
# site-major, every site's assignments on experts held elsewhere at the very
# end), and its own backward pass, so that what vmap sees of it are two plain
# forward computations. The weight gradient comes out per fold, as the trainer
# wants it.

_CONTRACT_ROWS = jax.lax.RaggedDotDimensionNumbers(
    dot_dimension_numbers=(([0], [0]), ([], [])),
    lhs_ragged_dimensions=[0], rhs_group_dimensions=[])


def route(scores, bias, top_k: int, route_norm: bool, route_scale: float):
    """``(sel [T, k] int32, w [T, k] float32)``: selection by ``scores +
    bias``, weights from ``scores`` alone."""
    _, sel = jax.lax.top_k(scores + jax.lax.stop_gradient(bias), top_k)
    w = jnp.take_along_axis(scores, sel, axis=-1)
    if route_norm:
        w = w / (w.sum(axis=-1, keepdims=True) + 1e-20)
    return sel, w * route_scale


def route_chosen(logits, top_k: int):
    """The other rule (``smallthinker``, ``qwen3_next``): the ``top_k``
    largest logits, and a softmax over those alone; no bias, no scale. The
    same weights as a softmax over ALL logits renormalised over the chosen."""
    top, sel = jax.lax.top_k(logits, top_k)
    return sel, jax.nn.softmax(top, axis=-1)


def _plan(sel, first_expert: int, held: int):
    """Sort the assignments ``sel [S, T, k]`` of ``S`` folds by (held expert,
    fold): ``(order, inverse, bounds [held * S + 1], is_held [S, T, k])``;
    group ``e * S + s`` owns the sorted rows ``bounds[e * S + s] .. bounds[e *
    S + s + 1] - 1``, so an expert's rows (all folds') are contiguous.
    Assignments on experts held elsewhere sort last, after every fold's."""
    folds = sel.shape[0]
    local = sel - first_expert
    is_held = (local >= 0) & (local < held)
    key = local * folds + jnp.arange(folds)[:, None, None]
    key = jnp.where(is_held, key, folds * held).reshape(-1)
    order = jnp.argsort(key, stable=True).astype(jnp.int32)
    inverse = jnp.argsort(order).astype(jnp.int32)
    bounds = jnp.searchsorted(
        jnp.take(key, order), jnp.arange(folds * held + 1, dtype=key.dtype)
    ).astype(jnp.int32)
    return order, inverse, bounds, is_held


ROW_CHUNK = 8192  # sorted assignment rows the expert layer holds at a time
# The sorted rows go back to token order through ``[tokens, k, hidden]``, which
# the compiler holds in float32: 1 GiB at Trinity's cell, 0.5 at GLM's, and
# 1.875 GiB (three times over in the backward pass) at 2 x 16,384 tokens of
# top-6 and width 2,560, where the epoch program no longer fitted the chip.
# Past this size the sum over a token's k slots walks the slots instead.
COMBINE_BYTES = 2 ** 30
ROUTED_OUT = "routed_experts_out"
KV_PROJ = "attention_kv_proj"  # a layer's raw key and value projections
MLA_LATENT = "attention_mla_latent"  # latent attention's raw [c_kv | k_r]
# what a block's checkpoint keeps; everything else is recomputed
BLOCK_KEEPS = jax.checkpoint_policies.save_only_these_names(
    ROUTED_OUT, ATTN_OUT, KV_PROJ, MLA_LATENT, DELTA_OUT)


def _row_chunk(rows: int) -> int:
    """Largest divisor of ``rows`` that is at most ``ROW_CHUNK``."""
    c = min(ROW_CHUNK, rows)
    while rows % c:
        c -= 1
    return c


# The stacks' cotangents are float32 ``[folds * held, ...]`` and the sorted rows
# of one chunk lie in few of those (expert, fold) groups: a chunk adds its
# products into the narrowest window of the stacks that holds all its rows, a
# chunk that no window holds into the whole stacks (PERF.md section 6, PRs 35
# and 39). The narrowest window is this many experts' groups.
WINDOW_EXPERTS = 2


def window_rungs(folds: int, held: int) -> tuple:
    """The windows' widths in groups, narrowest first: the groups of each
    whole number of experts from ``WINDOW_EXPERTS`` to all held but one."""
    return tuple(e * folds for e in range(WINDOW_EXPERTS, held))


def _chunk_sizes(bounds, lo, chunk: int):
    """Sizes of the groups ``bounds`` delimits, clipped to the rows ``lo ..
    lo + chunk - 1``."""
    return jnp.diff(jnp.clip(bounds, lo, lo + chunk))


def stack_window(sizes, window: int):
    """Where a chunk's rows lie among the groups: ``(g0, inside [window],
    narrow)``: the ``window`` consecutive groups from the chunk's first
    nonempty one, their sizes, and whether every row of the chunk is in them.
    ``sizes`` are clipped to the chunk, so the groups before its first
    nonempty one are empty: a start clamped to the last window only prepends
    empty groups, and the window's rows start at the chunk's first row."""
    g0 = jnp.clip(jnp.argmax(sizes > 0), 0, sizes.shape[0] - window)
    inside = jax.lax.dynamic_slice_in_dim(sizes, g0, window)
    return g0, inside, inside.sum() == sizes.sum()


def rung_of(sizes, rungs: tuple):
    """Which of ``rungs`` a chunk takes: the index of the narrowest whose
    window holds all its rows, ``len(rungs)`` where none does (the whole
    stacks). A wider window holds what a narrower one does, so it is the
    count of those that do not."""
    return sum(((~stack_window(sizes, w)[2]).astype(jnp.int32) for w in rungs),
               jnp.int32(0))


def window_chunks(held_counts, rows: int):
    """``(taken, whole, live)`` of one backward pass over ``rows`` worst-case
    assignment rows (``folds * tokens * k``) whose routing put ``held_counts
    [folds, held]`` assignments on the held experts: ``{a rung's width in
    groups: chunks that take it}``, the chunks that take the whole stacks,
    and the live chunks. A function of the routing alone, on the predicate
    the chunk loop calls."""
    counts = jnp.asarray(held_counts, jnp.int32)
    folds, held = counts.shape
    chunk = _row_chunk(rows)
    rungs = window_rungs(folds, held)
    bounds = jnp.concatenate(  # expert-major, as _plan sorts
        [jnp.zeros(1, jnp.int32), jnp.cumsum(counts.T.reshape(-1))])
    los = jnp.arange(rows // chunk) * chunk
    live = los < bounds[-1]
    rung = jax.vmap(lambda lo: rung_of(
        _chunk_sizes(bounds, lo, chunk), rungs))(los)
    taken = {w: ((rung == i) & live).sum() for i, w in enumerate(rungs)}
    return taken, ((rung == len(rungs)) & live).sum(), live.sum()


class _Experts:
    """The grouped products of one call: the sorted assignment rows are walked
    in chunks of ``chunk`` rows, and a chunk that starts past the held
    assignments is skipped (``lax.cond``), so that memory and work follow the
    real counts while the index arrays hold the worst case (dropless)."""

    def __init__(self, m, sel, w, w1, w3, w2, first_expert, cdt, relu=False):
        self.relu = relu  # ReGLU experts (relu(a) * b) for SwiGLU's silu(a) * b
        self.folds, self.t, self.h = m.shape
        self.k, self.held = sel.shape[-1], w1.shape[0]
        self.rows = self.folds * self.t * self.k
        self.chunk = _row_chunk(self.rows)
        self.cast = (lambda a: a) if cdt is None else (lambda a: a.astype(cdt))
        self.order, self.inverse, self.bounds, self.is_held = _plan(
            sel, first_expert, self.held)
        self.wk = jnp.where(self.is_held, w, 0.0)
        self.tokens = self.cast(m).reshape(self.folds * self.t, self.h)
        # an expert's rows are contiguous over the folds: the products by the
        # (shared) stacks group by expert, only the stacks' own cotangents by
        # (expert, fold)
        self.w1, self.w3, self.w2 = (self.cast(a) for a in (w1, w3, w2))

    def walk(self, body, carry):
        """``carry = body(lo, carry)`` for every chunk that holds a held
        assignment."""
        def step(c, carry):
            lo = c * self.chunk
            return jax.lax.cond(lo < self.bounds[-1],
                                lambda x: body(lo, x), lambda x: x, carry)

        return jax.lax.fori_loop(0, self.rows // self.chunk, step, carry)

    def rows_of(self, lo):
        """``(token of each row, sizes of the (expert, fold) groups inside the
        chunk, live rows)``."""
        rows = jax.lax.dynamic_slice_in_dim(self.order, lo, self.chunk)
        live = (lo + jnp.arange(self.chunk) < self.bounds[-1])[:, None]
        return rows // self.k, _chunk_sizes(self.bounds, lo, self.chunk), live

    def by_expert(self, sizes):
        """Group sizes by expert, the folds merged."""
        return sizes.reshape(self.held, self.folds).sum(axis=1)

    def dot(self, sizes):
        """The grouped product by an expert-major stack, rows by expert."""
        return functools.partial(jax.lax.ragged_dot,
                                 group_sizes=self.by_expert(sizes),
                                 preferred_element_type=jnp.float32)

    def hidden(self, tok, sizes):
        """``(xs, a, b, mid)`` of a chunk: all of the forward that the
        backward pass needs again."""
        dot = self.dot(sizes)
        xs = jnp.take(self.tokens, tok, axis=0)
        a, b = dot(xs, self.w1), dot(xs, self.w3)
        mid = self.cast((jax.nn.relu(a) if self.relu else jax.nn.silu(a)) * b)
        return xs, a, b, mid

    def forward(self, tok, sizes, live):
        """The experts' outputs ``[chunk, H]`` float32 of a chunk's rows."""
        mid = self.hidden(tok, sizes)[-1]
        # rows past the held assignments belong to no group: whatever the
        # grouped product leaves there is not a result
        return jnp.where(live, self.dot(sizes)(mid, self.w2), 0.0)

    def per_token(self, buf):
        """Sorted rows ``[rows, ...]`` back in token-major order ``[S * T, k,
        ...]``: the inverse permutation's gather, no scatter."""
        out = jnp.take(buf, self.inverse, axis=0)
        return out.reshape((self.folds * self.t, self.k) + buf.shape[1:])

    @property
    def by_slot(self) -> bool:
        """Whether ``[S * T, k, H]`` in float32 is past COMBINE_BYTES."""
        return self.rows * self.h * 4 > COMBINE_BYTES

    def slot_sum(self, buf, weights=None):
        """``sum_j weights[:, j] * buf[inverse[:, j]]`` (``weights [S * T, k]``,
        ones if None) as ``[S * T, H]`` float32, one of a token's ``k`` slots
        at a time: :meth:`per_token`'s gather in ``k`` parts, so that
        ``[S * T, k, H]`` is never whole."""
        inv = self.inverse.reshape(-1, self.k).T  # [k, S * T]
        by_slot = None if weights is None else weights.T

        def slot(j, acc):
            rows = jnp.take(buf, inv[j], axis=0).astype(jnp.float32)
            return acc + (rows if weights is None else rows * by_slot[j][:, None])

        return jax.lax.fori_loop(
            0, self.k, slot, jnp.zeros((inv.shape[1], self.h), jnp.float32))


def _experts_forward(m, sel, w, w1, w3, w2, first_expert, cdt, relu=False):
    """``m [S, T, H]``, ``sel, w [S, T, k]`` -> the held experts' part ``[S,
    T, H]`` float32."""
    ex = _Experts(m, sel, w, w1, w3, w2, first_expert, cdt, relu)

    def body(lo, buf):
        ys = ex.forward(*ex.rows_of(lo))
        return jax.lax.dynamic_update_slice_in_dim(
            buf, ys.astype(buf.dtype), lo, axis=0)

    buf = ex.walk(body, jnp.zeros((ex.rows, ex.h), ex.tokens.dtype))
    if ex.by_slot:
        y = ex.slot_sum(buf, ex.wk.reshape(-1, ex.k))
    else:
        y = jnp.einsum("nkh,nk->nh", ex.per_token(buf), ex.wk.reshape(-1, ex.k),
                       preferred_element_type=jnp.float32)
    return y.reshape(m.shape)


def _experts_backward(m, sel, w, w1, w3, w2, dy, first_expert, cdt,
                      relu=False):
    """Cotangents ``(dm [S, T, H], dw [S, T, k], dw1, dw3, dw2 [S, E, ..])``
    of :func:`_experts_forward` for ``dy [S, T, H]``, the forward recomputed
    chunk by chunk (all but its product by ``w2``). A chunk adds its stack
    gradients into the narrowest window of :func:`window_rungs` that holds
    its rows, or into the whole stacks: the same products into the same
    accumulators in the same order."""
    ex = _Experts(m, sel, w, w1, w3, w2, first_expert, cdt, relu)
    wdot = functools.partial(
        jax.lax.ragged_dot_general,
        ragged_dot_dimension_numbers=_CONTRACT_ROWS,
        preferred_element_type=jnp.float32)
    dys_tok = ex.cast(dy).reshape(ex.folds * ex.t, ex.h)
    wk_sorted = jnp.take(ex.wk.reshape(-1), ex.order)
    w1t, w3t, w2t = (jnp.swapaxes(a, 1, 2) for a in (ex.w1, ex.w3, ex.w2))

    groups, rungs = ex.folds * ex.held, window_rungs(ex.folds, ex.held)

    def body(lo, carry):
        dxs_buf, dwk_buf, dw1, dw3, dw2 = carry
        tok, sizes, live = ex.rows_of(lo)
        xs, a, b, mid = ex.hidden(tok, sizes)
        dot = ex.dot(sizes)
        g = jnp.take(dys_tok, tok, axis=0)
        wk = jax.lax.dynamic_slice_in_dim(wk_sorted, lo, ex.chunk)[:, None]
        # a row's weight commutes with the product by w2: u serves dmid and,
        # since sum_h (mid w2)_h g_h = sum_f mid_f (g w2^T)_f, the weight's
        # own cotangent, without the forward's product by w2
        u = dot(g, w2t)
        dmid = u * wk
        dwk = jnp.where(live[:, 0], (mid.astype(jnp.float32) * u).sum(-1), 0.0)
        dys = ex.cast(jnp.where(live, g.astype(jnp.float32) * wk, 0.0))
        if relu:  # d relu(a) b: the gate's cotangent is b where a > 0
            on = live & (a > 0)
            da = ex.cast(jnp.where(on, dmid * b, 0.0))
            db = ex.cast(jnp.where(on, dmid * a, 0.0))
        else:
            sig = jax.nn.sigmoid(a)
            da = ex.cast(jnp.where(
                live, dmid * b * sig * (1.0 + a * (1.0 - sig)), 0.0))
            db = ex.cast(jnp.where(live, dmid * a * sig, 0.0))
        dxs = jnp.where(live, dot(da, w1t) + dot(db, w3t), 0.0)
        pairs = ((xs, da), (xs, db), (mid, dys))

        def whole(stacks):
            return tuple(dw + wdot(x, d, sizes)
                         for dw, (x, d) in zip(stacks, pairs))

        def windowed(window, stacks):
            g0, inside, _ = stack_window(sizes, window)
            cut = jax.lax.dynamic_slice_in_dim
            return tuple(jax.lax.dynamic_update_slice_in_dim(
                dw, cut(dw, g0, window) + wdot(x, d, inside), g0, axis=0)
                for dw, (x, d) in zip(stacks, pairs))

        stacks = jax.lax.switch(rung_of(sizes, rungs), [
            functools.partial(windowed, w) for w in rungs] + [whole],
            (dw1, dw3, dw2))
        put = jax.lax.dynamic_update_slice_in_dim
        return (put(dxs_buf, dxs.astype(dxs_buf.dtype), lo, axis=0),
                put(dwk_buf, dwk, lo, axis=0)) + stacks

    f, h = w1.shape[2], ex.h
    dxs_buf, dwk_buf, dw1, dw3, dw2 = ex.walk(body, (
        jnp.zeros((ex.rows, h), ex.tokens.dtype),
        jnp.zeros((ex.rows,), jnp.float32),
        jnp.zeros((groups, h, f), jnp.float32),
        jnp.zeros((groups, h, f), jnp.float32),
        jnp.zeros((groups, f, h), jnp.float32)))
    if ex.by_slot:
        dm = ex.slot_sum(dxs_buf).reshape(m.shape)
    else:
        dm = ex.per_token(dxs_buf).astype(jnp.float32).sum(axis=1).reshape(m.shape)
    dwk = jnp.where(ex.is_held, ex.per_token(dwk_buf).reshape(w.shape), 0.0)

    def per_fold(x):  # [held * S, ...] expert-major -> [S, held, ...]
        return jnp.swapaxes(x.reshape((ex.held, ex.folds) + x.shape[1:]), 0, 1)

    return dm, dwk, per_fold(dw1), per_fold(dw3), per_fold(dw2)


@functools.lru_cache(maxsize=None)
def _expert_layer(first_expert: int, cdt, relu: bool = False):
    """``experts(m [T, H], sel [T, k], w [T, k], w1, w3, w2) -> [T, H]`` for
    one share of the experts (ReGLU ones if ``relu``), differentiable and
    mappable."""
    from jax.custom_batching import custom_vmap

    def folded(fn, n_tok, n_out):
        """``fn`` over a leading fold axis as a custom_vmap function of
        unfolded arguments: a vmap over the first ``n_tok`` (token) arguments
        becomes the fold axis; the expert stacks after them are shared."""
        @custom_vmap
        def call(*args):
            lead = tuple(a[None] for a in args[:n_tok]) + args[n_tok:]
            return jax.tree.map(lambda o: o[0], fn(*lead))

        @call.def_vmap
        def rule(axis_size, in_batched, *args):
            tok, stacks = in_batched[:n_tok], in_batched[n_tok:]
            if not all(tok) or any(stacks):
                raise NotImplementedError(
                    "the expert layer maps over its token arguments only "
                    "(the trainer's site fold)")
            return fn(*args), (True,) * n_out if n_out > 1 else True

        return call

    def fwd_impl(m, sel, w, w1, w3, w2):
        return _experts_forward(m, sel, w, w1, w3, w2, first_expert, cdt, relu)

    def bwd_impl(m, sel, w, dy, w1, w3, w2):
        return _experts_backward(m, sel, w, w1, w3, w2, dy, first_expert, cdt,
                                 relu)

    fwd_call, bwd_call = folded(fwd_impl, 3, 1), folded(bwd_impl, 4, 5)

    @jax.custom_vjp
    def experts(m, sel, w, w1, w3, w2):
        return fwd_call(m, sel, w, w1, w3, w2)

    def experts_fwd(m, sel, w, w1, w3, w2):
        return fwd_call(m, sel, w, w1, w3, w2), (m, sel, w, w1, w3, w2)

    def experts_bwd(res, dy):
        m, sel, w, w1, w3, w2 = res
        dm, dw, dw1, dw3, dw2 = bwd_call(m, sel, w, dy, w1, w3, w2)
        return (dm.astype(m.dtype), None, dw.astype(w.dtype),
                dw1.astype(w1.dtype), dw3.astype(w3.dtype),
                dw2.astype(w2.dtype))

    experts.defvjp(experts_fwd, experts_bwd)
    return experts


def routed_experts(m, sel, w, w1, w3, w2, first_expert: int, cdt=None,
                   relu: bool = False):
    """The held experts' part of the layer for the tokens ``m [T, H]`` with
    assignments ``sel, w [T, k]`` over ALL experts; stacks ``w1, w3 [E, H,
    F]``, ``w2 [E, F, H]`` of the experts ``first_expert .. + E - 1`` ->
    ``[T, H]`` float32; an expert is ``(silu(m w1) * (m w3)) w2``, with
    ``relu`` for ``silu`` if asked. Dropless: the buffer holds all ``T * k``
    assignments, the grouped products cover ``sum(group_sizes)`` rows."""
    return _expert_layer(first_expert, cdt, relu)(m, sel, w, w1, w3, w2)


# -- modules -----------------------------------------------------------------


class RMSNorm(nn.Module):
    eps: float = 1e-5

    @nn.compact
    def __call__(self, x, weight_only: bool = False):
        scale = self.param("scale", nn.initializers.ones, (x.shape[-1],))
        return scale if weight_only else rms_norm(x, scale, self.eps)


class SwiGLU(nn.Module):
    width: int
    compute_dtype: str | None = None

    @nn.compact
    def __call__(self, m):
        cdt = compute_dtype_of(self.compute_dtype)
        h = m.shape[-1]
        w1 = self.param("w1", _init(), (h, self.width))
        w3 = self.param("w3", _init(), (h, self.width))
        w2 = self.param("w2", _init(), (self.width, h))
        act = jax.nn.silu(_mm(m, w1, cdt)) * _mm(m, w3, cdt)
        return _mm(act, w2, cdt)


class Attention(nn.Module):
    num_heads: int
    num_kv_heads: int
    head_dim: int
    window: int | None  # None = full attention
    rope_theta: float
    eps: float
    q_block: int
    kv_chunk: int
    compute_dtype: str | None = None
    # three facts of a layer, each its own: RMSNorm over every head of q and k
    # (``q_norm``, ``k_norm`` in the tree), the sigmoid output gate (``wg``),
    # rotary positions. Trinity has the first two, smallthinker neither, and
    # in both a layer has positions iff it has a window (``rope`` None);
    # lfm2_moe norms, does not gate, and rotates on its full layers
    qk_norm: bool = True
    gate: bool = True
    rope: bool | None = None
    # positions on the first ``rotary_dim`` dimensions of each head only
    # (qwen3_next: 64 of 256); None = the whole head
    rotary_dim: int | None = None

    @nn.compact
    def __call__(self, a):
        cdt = compute_dtype_of(self.compute_dtype)
        B, T, H = a.shape
        N, G, D = self.num_heads, self.num_kv_heads, self.head_dim
        wq = self.param("wq", _init(), (H, N * D))
        wk = self.param("wk", _init(), (H, G * D))
        wv = self.param("wv", _init(), (H, G * D))
        if self.gate:
            wg = self.param("wg", _init(), (H, N * D))
        wo = self.param("wo", _init(), (N * D, H))
        q = _mm(a, wq, cdt).reshape(B, T, N, D)
        # the raw key and value projections are kept across the block's
        # recomputation (an eighth of the queries' bytes); q is recomputed
        k = checkpoint_name(_mm(a, wk, cdt), KV_PROJ).reshape(B, T, G, D)
        v = checkpoint_name(_mm(a, wv, cdt), KV_PROJ).reshape(B, T, G, D)
        norms = ((RMSNorm(self.eps, name="q_norm"), RMSNorm(self.eps, name="k_norm"))
                 if self.qk_norm else ())
        rope = self.window is not None if self.rope is None else self.rope
        out = q.dtype if cdt is None else cdt
        rotated = D if self.rotary_dim is None else self.rotary_dim
        if (rope and rotated == D and takes_kernels(T)
                and rope_pallas.rope_block(T, N // G, D, out, self.qk_norm)):
            # QK-norm, rotary, the rounding, the scale and the kernels' layout
            # in ONE pass each way (ROPE_FWD / ROPE_BWD): the heads are whole
            # lane tiles and the rotation covers them whole
            qh, kh = rope_pallas.rope_heads(
                q.reshape(B, T, N * D), k.reshape(B, T, G * D),
                tuple(n(x, weight_only=True) for n, x in zip(norms, (q, k))),
                D, self.rope_theta, self.eps if self.qk_norm else None, out)
            o = splash_heads(qh, kh, jnp.moveaxis(v.astype(out), 1, 2),
                             self.window)
        else:
            if self.qk_norm:
                q, k = norms[0](q), norms[1](k)
            if rope:
                pos = jnp.arange(T)
                q, k = (partial_rotary(x, pos, self.rope_theta, rotated)
                        for x in (q, k))
            o = masked_attention(q, k, v, self.window, self.q_block,
                                 self.kv_chunk, cdt)
        o = o.reshape(B, T, N * D)
        if self.gate:
            o = o * jax.nn.sigmoid(_mm(a, wg, cdt))
        return _mm(o, wo, cdt)


def causal_conv(u, filt):
    """The causal depth-wise convolution of ``u [..., T, channels]`` under the
    filter ``filt [channels, taps]``: tap ``j`` reads the position ``taps - 1
    - j`` back, zeros before position 0. The shift is a pad and ``taps``
    static slices along the sequence."""
    t, taps = u.shape[-2], filt.shape[1]
    u = jnp.pad(u, ((0, 0),) * (u.ndim - 2) + ((taps - 1, 0), (0, 0)))
    return sum(filt[:, j] * jax.lax.slice_in_dim(u, j, j + t, axis=-2)
               for j in range(taps))


def short_conv(bcx, filt):
    """The gated short convolution's element-wise half: ``C * conv(B * x)``
    of ``bcx = [B | C | x] [..., T, 3 * hidden]`` float32 under the filter
    ``filt [hidden, taps]`` (:func:`causal_conv`)."""
    b, c, x = jnp.split(bcx, 3, axis=-1)
    return c * causal_conv(b * x, filt)


class ShortConv(nn.Module):
    """The gated short convolution, ``lfm2_moe``'s token mixer on a ``conv``
    layer: ``[B | C | x] = a W_in``; ``o = (C * conv(B * x)) W_out``
    (:func:`short_conv`). The projections in ``compute_dtype`` with float32
    accumulation, the gates and the taps in float32."""

    hidden: int
    taps: int = 3
    compute_dtype: str | None = None

    @nn.compact
    def __call__(self, a):
        cdt = compute_dtype_of(self.compute_dtype)
        w_in = self.param("w_in", _init(), (a.shape[-1], 3 * self.hidden))
        filt = self.param("filter", _init(), (self.hidden, self.taps))
        w_out = self.param("w_out", _init(), (self.hidden, a.shape[-1]))
        mixed = short_conv(_mm(a, w_in, cdt), filt.astype(jnp.float32))
        return _mm(mixed, w_out, cdt)


# -- the gated delta rule ------------------------------------------------------
#
# Gated DeltaNet's recurrence in chunks of DELTA_CHUNK positions: everything
# that does not depend on the state carried in from earlier chunks is computed
# for a segment's chunks at once, as batched products over (sequence, head,
# chunk); a ``lax.scan`` over the chunks carries the float32 state ``[d_k,
# d_v]`` a head and does only the two products the state needs. JAX
# differentiates it, under the block's checkpoint like the rest of the layer.
# On a TPU, where ``delta_pallas.delta_block`` takes the call's shape, the
# chunk loop runs as that module's two kernels instead, the state in VMEM.

DELTA_CHUNK = 64  # positions a chunk of the delta rule holds
DELTA_SEGMENT = 16  # chunks a checkpointed segment of the delta rule holds
SUBSTITUTION_ROWS = 16  # the diagonal blocks the triangular inverse solves row by row
_HIGHEST = jax.lax.Precision.HIGHEST


def l2_normalize(x, eps: float = 1e-6):
    """``x / sqrt(sum x^2 + eps)`` over the last axis, float32."""
    return x * jax.lax.rsqrt(jnp.sum(x * x, axis=-1, keepdims=True) + eps)


def _substitute(a):
    """``(I + a)^-1`` of strictly lower triangular ``a [..., m, m]`` by forward
    substitution: row ``i`` of the inverse is ``e_i - a[i] (I + a)^-1``, whose
    rows at and below ``i`` are still the identity's."""
    m = a.shape[-1]
    eye = jnp.eye(m, dtype=a.dtype)

    def row(i, inv):
        a_i = jax.lax.dynamic_index_in_dim(a, i, axis=-2, keepdims=False)
        new = jax.lax.dynamic_index_in_dim(eye, i, keepdims=False) - jnp.einsum(
            "...j,...jk->...k", a_i, inv, precision=_HIGHEST)
        return jax.lax.dynamic_update_index_in_dim(inv, new, i, axis=-2)

    return jax.lax.fori_loop(1, m, row, jnp.broadcast_to(eye, a.shape))


def unit_lower_inverse(a):
    """``(I + a)^-1`` of strictly lower triangular ``a [..., n, n]`` float32:
    forward substitution on the diagonal blocks of SUBSTITUTION_ROWS rows,
    then pairs of blocks merged, ``[[A, 0], [C, D]]^-1 = [[A^-1, 0], [-D^-1 C
    A^-1, D^-1]]``, until one is left. No power series of ``a``: its terms
    grow where the keys of a chunk are alike, and cancel only in exact
    arithmetic."""
    n = a.shape[-1]
    m = min(SUBSTITUTION_ROWS, n)
    if n % m or (n // m) & (n // m - 1):
        raise ValueError(f"{n} rows are not a power of two of {m}-row blocks")

    def blocks(size, row_off):  # the blocks of ``size`` on a block diagonal
        return jnp.stack([a[..., i + row_off: i + row_off + size, i: i + size]
                          for i in range(0, n - row_off, 2 * size if row_off
                                         else size)], axis=-3)

    inv = _substitute(blocks(m, 0))  # [..., n / m, m, m]
    size = m
    while inv.shape[-3] > 1:
        upper, lower = inv[..., 0::2, :, :], inv[..., 1::2, :, :]
        corner = -jnp.einsum(
            "...ij,...jk,...kl->...il", lower, blocks(size, size), upper,
            precision=_HIGHEST)
        inv = jnp.concatenate([
            jnp.concatenate([upper, jnp.zeros_like(upper)], -1),
            jnp.concatenate([corner, lower], -1)], -2)
        size *= 2
    return inv[..., 0, :, :]


def _delta_segment(s, xs, cdt):
    """One segment of chunks of :func:`chunked_delta_rule` from the state ``s
    [B, H, d_k, d_v]`` carried in: ``xs = (q, k, v, beta, g)``, each ``[B,
    heads, chunks, chunk, ...]`` (``q``, ``k`` of the key heads) -> ``(state
    carried out, o [B, H, chunks, chunk, d_v])``. Everything that does not
    need the state is computed for all the segment's chunks at once; one
    ``lax.scan`` over them does the two products the state needs."""
    q, k, v, beta, g = xs
    chunk, dv = k.shape[-2], v.shape[-1]
    # value head h reads key head h // (value heads / key heads)
    q, k = (jnp.repeat(x, v.shape[1] // x.shape[1], axis=1) for x in (q, k))
    gam = jnp.cumsum(g, axis=-1)  # [B, H, chunks, chunk]
    row, col = jnp.arange(chunk)[:, None], jnp.arange(chunk)[None, :]
    diff = gam[..., :, None] - gam[..., None, :]
    strict = jnp.exp(jnp.where(row > col, diff, -jnp.inf))
    causal = jnp.exp(jnp.where(row >= col, diff, -jnp.inf))
    kk = jnp.einsum("...id,...jd->...ij", k, k, precision=_HIGHEST)
    inv = unit_lower_inverse(beta[..., :, None] * kk * strict)
    decayed = jnp.exp(gam)
    rhs = jnp.concatenate(
        [v * beta[..., None], k * (beta * decayed)[..., None]], axis=-1)
    uw = jnp.einsum("...ij,...jd->...id", inv, rhs, precision=_HIGHEST)
    last = gam[..., -1:]
    k_out = k * jnp.exp(last - gam)[..., None]  # what a position leaves in S

    def step(s, xs):
        uw_c, k_c, decay_c = xs
        vn = uw_c[..., :dv] - jnp.einsum("...ik,...kv->...iv", uw_c[..., dv:], s,
                                         precision=_HIGHEST)
        s_next = decay_c[..., None, None] * s + jnp.einsum(
            "...ik,...iv->...kv", k_c, vn, precision=_HIGHEST)
        return s_next, (s, vn)

    along = lambda x: jnp.moveaxis(x, 2, 0)  # noqa: E731  chunks first
    s, (states, vn) = jax.lax.scan(
        step, s, (along(uw), along(k_out), along(jnp.exp(last[..., 0]))))
    states, vn = jnp.moveaxis(states, 0, 2), jnp.moveaxis(vn, 0, 2)
    cast = (lambda x: x) if cdt is None else (lambda x: x.astype(cdt))
    scores = jnp.einsum("...id,...jd->...ij", cast(q), cast(k),
                        preferred_element_type=jnp.float32) * causal
    o = jnp.einsum("...id,...dv->...iv", cast(q * decayed[..., None]),
                   cast(states), preferred_element_type=jnp.float32)
    return s, o + jnp.einsum("...ij,...jv->...iv", cast(scores), cast(vn),
                             preferred_element_type=jnp.float32)


def chunked_delta_rule(q, k, v, beta, g, chunk: int = DELTA_CHUNK, cdt=None):
    """The gated delta rule over ``T`` positions, float32: ``q, k [B, T, K,
    d_k]`` (``k`` l2-normed, ``q`` l2-normed and scaled), ``v [B, T, H, d_v]``,
    ``beta, g [B, T, H]`` (``g <= 0``, the log of a position's decay) -> ``o
    [B, T, H, d_v]``; value head ``h`` reads key head ``h // (H / K)``, the
    state of every value head starts at zero. A sequence that is not whole
    segments is padded with positions that change nothing before them
    (``beta = g = 0``).

    In a chunk, with ``G`` the cumulative sum of ``g`` and ``S`` the state
    carried in: ``T = (I + strictly_lower(beta_i k_i.k_j exp(G_i - G_j)))^-1``,
    ``U = T (beta v)``, ``W = T (beta k exp(G))``; ``V' = U - W S``; ``o = (q
    exp(G)) S + (q k^T exp(G_i - G_j))_{j <= i} V'``; ``S <- exp(G_C) S + (k
    exp(G_C - G))^T V'``. Every exponent is a difference ``G_i - G_j`` with ``j
    <= i`` (or ``G`` itself), so ``<= 0``: no ratio of two decays, which
    underflow within a few positions at the published initialisation; the
    exponents above the diagonal are masked before ``exp``, not after.

    The chunks run in segments of DELTA_SEGMENT (:func:`_delta_segment`), one
    ``lax.scan`` over the segments carrying the state, each segment under
    ``jax.checkpoint``: the backward pass keeps the state at the segments'
    edges and recomputes one segment at a time, so the chunks' ``[chunk,
    chunk]`` matrices and states are never whole for the sequence (at 2 x
    8,192 positions and 32 heads they are 0.13-0.54 GB apiece).

    Precision: what feeds the state (``k k^T``, the triangular inverse, ``U``,
    ``W`` and the two products of the scan) is float32 at HIGHEST; the products
    that only make the output (``q k^T``, and ``o``'s two) take ``cdt``
    operands with float32 accumulation, as attention's do.

    On a TPU, in chunks of ``delta_pallas.CHUNK`` and where
    ``delta_pallas.delta_block`` takes the shape (heads of whole lane tiles),
    the same arithmetic runs as ``ops/delta_pallas.py``'s two kernels, the
    state in VMEM across the chunks and its own backward kernel; everywhere
    else as below."""
    B, T, H, dv = v.shape
    K, dk = k.shape[-2:]
    if (chunk == delta_pallas.CHUNK and _auto_pallas() and delta_pallas.delta_block(
            T, H, K, dk, dv, DELTA_SEGMENT) is not None):
        # the chunk loop as one kernel each way, the state in VMEM
        return delta_pallas.delta_rule(q, k, v, beta, g, DELTA_SEGMENT, cdt,
                                       DELTA_OUT)
    chunks = -(-T // chunk)
    per = min(DELTA_SEGMENT, chunks)
    chunks = -(-chunks // per) * per

    def segments(x):  # [B, T, H, ...] -> [segments, B, H, per, chunk, ...]
        x = jnp.pad(x, ((0, 0), (0, chunks * chunk - T))
                    + ((0, 0),) * (x.ndim - 2))
        x = x.reshape((B, chunks // per, per, chunk) + x.shape[2:])
        return jnp.moveaxis(x, (1, 4), (0, 2))

    body = jax.checkpoint(functools.partial(_delta_segment, cdt=cdt))
    _, o = jax.lax.scan(body, jnp.zeros((B, H, dk, dv), jnp.float32),
                        tuple(map(segments, (q, k, v, beta, g))))
    o = jnp.moveaxis(o, (0, 2), (1, 4)).reshape(B, chunks * chunk, H, dv)
    return o[:, :T]


def _a_log_init(key, shape, dtype=jnp.float32):
    """``log A`` with ``A ~ U(0, 16)``, the family's initialisation."""
    return jnp.log(jax.random.uniform(key, shape, dtype, maxval=16.0))


class GatedDeltaNet(nn.Module):
    """Gated DeltaNet, ``qwen3_next``'s token mixer on a ``linear_attention``
    layer (the equations are the module docstring's). Columns: ``in_proj_qkvz``
    is ``[q | k | v | z]`` and ``in_proj_ba`` ``[b | a']``, each part head-major;
    the convolution runs over the ``q | k | v`` channels. The projections in
    ``compute_dtype`` with float32 accumulation; the convolution, the gates,
    the norms and the recurrence in float32."""

    num_k_heads: int
    num_v_heads: int
    k_dim: int
    v_dim: int
    taps: int
    eps: float
    compute_dtype: str | None = None

    @nn.compact
    def __call__(self, a):
        cdt = compute_dtype_of(self.compute_dtype)
        B, T, H = a.shape
        nk, nv, dk, dv = self.num_k_heads, self.num_v_heads, self.k_dim, self.v_dim
        keys, values = nk * dk, nv * dv
        w_qkvz = self.param("in_proj_qkvz", _init(), (H, 2 * keys + 2 * values))
        w_ba = self.param("in_proj_ba", _init(), (H, 2 * nv))
        filt = self.param("conv1d", _init(), (2 * keys + values, self.taps))
        a_log = self.param("A_log", _a_log_init, (nv,))
        dt_bias = self.param("dt_bias", nn.initializers.ones, (nv,))
        w_out = self.param("out_proj", _init(), (values, H))
        qkvz = _mm(a, w_qkvz, cdt)
        ba = _mm(a, w_ba, cdt)

        # the convolution, the norms and the gates are recomputed where the
        # backward pass needs them: kept, their float32 [T, 8192] passes
        # would be live beside the rule's own
        @jax.checkpoint
        def rule_inputs(qkv, ba, filt, a_log, dt_bias):
            mixed = jax.nn.silu(causal_conv(qkv, filt.astype(jnp.float32)))
            beta = jax.nn.sigmoid(ba[..., :nv])
            g = -jnp.exp(a_log.astype(jnp.float32)) * jax.nn.softplus(
                ba[..., nv:] + dt_bias.astype(jnp.float32))
            q, k = (l2_normalize(mixed[..., i * keys: (i + 1) * keys]
                                 .reshape(B, T, nk, dk)) for i in (0, 1))
            v = mixed[..., 2 * keys:].reshape(B, T, nv, dv)
            return q * dk ** -0.5, k, v, beta, g

        q, k, v, beta, g = rule_inputs(qkvz[..., : 2 * keys + values], ba,
                                       filt, a_log, dt_bias)
        with jax.named_scope(scopes.DELTA_CHUNKS):
            o = chunked_delta_rule(q, k, v, beta, g, chunk=DELTA_CHUNK, cdt=cdt)
        norm = RMSNorm(self.eps, name="norm")(o, weight_only=True)

        @jax.checkpoint  # the same for the gated norm's float32 passes
        def gated(o, z, norm):
            return rms_norm(o, norm, self.eps) * jax.nn.silu(z)

        o = gated(o, qkvz[..., 2 * keys + values:].reshape(B, T, nv, dv), norm)
        return _mm(o.reshape(B, T, values), w_out, cdt)


class LatentAttention(nn.Module):
    """Multi-head latent attention (MLA) in its up-projected form: queries,
    keys and values come out of low-rank latents, and the keys' rotary slice
    is one vector a position that every head shares. One query head a
    key-value head, all of one width (``qk_nope_head_dim + qk_rope_head_dim
    == v_head_dim``: the attention paths carry one head width)."""

    num_heads: int
    q_lora_rank: int
    kv_lora_rank: int
    qk_nope_head_dim: int
    qk_rope_head_dim: int
    v_head_dim: int
    rope_theta: float
    eps: float
    q_block: int
    kv_chunk: int
    compute_dtype: str | None = None

    @nn.compact
    def __call__(self, a):
        cdt = compute_dtype_of(self.compute_dtype)
        B, T, H = a.shape
        N, rq, rkv = self.num_heads, self.q_lora_rank, self.kv_lora_rank
        dn, dr, dv = (self.qk_nope_head_dim, self.qk_rope_head_dim,
                      self.v_head_dim)
        wq_a = self.param("wq_a", _init(), (H, rq))
        wq_b = self.param("wq_b", _init(), (rq, N * (dn + dr)))
        wkv_a = self.param("wkv_a", _init(), (H, rkv + dr))
        wkv_b = self.param("wkv_b", _init(), (rkv, N * (dn + dv)))
        wo = self.param("wo", _init(), (N * dv, H))
        with jax.named_scope(scopes.MLA_LATENT):
            c_q = RMSNorm(self.eps, name="q_a_norm")(_mm(a, wq_a, cdt))
            q = _mm(c_q, wq_b, cdt).reshape(B, T, N, dn + dr)
            # the raw latent is kept across the block's recomputation: 576
            # columns for the 10,240 of the keys and values it expands to
            latent = checkpoint_name(_mm(a, wkv_a, cdt), MLA_LATENT)
            c_kv = RMSNorm(self.eps, name="kv_a_norm")(latent[..., :rkv])
            kv = _mm(c_kv, wkv_b, cdt).reshape(B, T, N, dn + dv)
        pos = jnp.arange(T)
        q_r = rotary(q[..., dn:], pos, self.rope_theta)
        k_r = rotary(latent[..., None, rkv:], pos, self.rope_theta)
        q = jnp.concatenate([q[..., :dn], q_r], axis=-1)
        k = jnp.concatenate(
            [kv[..., :dn], jnp.broadcast_to(k_r, (B, T, N, dr))], axis=-1)
        o = masked_attention(q, k, kv[..., dn:], None, self.q_block,
                             self.kv_chunk, cdt)
        return _mm(o.reshape(B, T, N * dv), wo, cdt)


class MoE(nn.Module):
    """The expert layer: the router and the share of the experts held here.
    ``__call__(m)`` routes the tokens it is given; a block whose router reads
    something else (``smallthinker``: the block's input, before attention)
    calls :meth:`route` on that itself and hands the routing over. Three
    facts of a type, each its own: the routing rule (``softmax_routing``:
    top-k of the logits and a softmax over the chosen, no ``expert_bias`` in
    the tree; else sigmoid scores and Trinity's rule), the experts'
    activation (``relu``: ReGLU for SwiGLU) and a sigmoid gate on the shared
    expert (``shared_gate``: ``shared_expert_gate`` in the tree)."""

    hidden_size: int
    num_experts: int
    top_k: int
    experts_held: int
    first_expert: int
    width: int
    shared_width: int
    route_norm: bool
    route_scale: float
    compute_dtype: str | None = None
    softmax_routing: bool = False
    relu: bool = False
    shared_gate: bool = False

    def setup(self):
        E, F, H = self.experts_held, self.width, self.hidden_size
        self.router = self.param("router", _init(), (H, self.num_experts))
        if not self.softmax_routing:
            self.expert_bias = self.param(
                "expert_bias", nn.initializers.zeros, (self.num_experts,))
        self.w1 = self.param("w1", _init(), (E, H, F))
        self.w3 = self.param("w3", _init(), (E, H, F))
        self.w2 = self.param("w2", _init(), (E, F, H))
        if self.shared_width:
            self.shared = SwiGLU(self.shared_width, self.compute_dtype)
        if self.shared_gate:
            self.shared_expert_gate = self.param(
                "shared_expert_gate", _init(), (H, 1))

    def route(self, x):
        """``(sel, w) [B, T, k]`` over ALL experts from ``x [B, T, H]``."""
        with jax.named_scope(scopes.MOE_ROUTE):
            # the router reads float32 (as the family's code does): a top-k
            # over rounded scores picks other experts than the model's
            logits = jnp.matmul(
                x.astype(jnp.float32), self.router.astype(jnp.float32),
                precision=jax.lax.Precision.HIGHEST)
            if self.softmax_routing:
                return route_chosen(logits, self.top_k)
            return route(jax.nn.sigmoid(logits), self.expert_bias, self.top_k,
                         self.route_norm, self.route_scale)

    def __call__(self, m, routing=None):
        cdt = compute_dtype_of(self.compute_dtype)
        B, T, H = m.shape
        sel, w = self.route(m) if routing is None else routing
        # a routing counter for whoever asks (apply(..., mutable=
        # ["intermediates"])): assignments on each held expert, [B, held]
        local = sel - self.first_expert
        self.sow("intermediates", "held_counts", jnp.sum(
            local[..., None] == jnp.arange(self.experts_held), axis=(1, 2),
            dtype=jnp.int32))
        with jax.named_scope(scopes.MOE_EXPERTS):
            # a site's sequences are just tokens to the experts
            y = routed_experts(
                m.reshape(B * T, H), sel.reshape(B * T, -1),
                w.reshape(B * T, -1), self.w1, self.w3, self.w2,
                self.first_expert, cdt, self.relu,
            ).reshape(B, T, H)
            # kept across the block's recomputation (BLOCK_KEEPS): the
            # backward pass runs the layer's forward in chunks itself and
            # need not run it twice
            y = checkpoint_name(y, ROUTED_OUT)
        if self.shared_width:
            with jax.named_scope(scopes.MOE_SHARED):
                if self.shared_gate:
                    y = y + self.shared(m) * jax.nn.sigmoid(
                        _mm(m, self.shared_expert_gate, cdt))
                else:
                    y = y + self.shared(m)
        return y


@dataclasses.dataclass(frozen=True)
class Dims:
    """The decoder's sizes, as a block reads them."""

    hidden_size: int = 2048
    num_attention_heads: int = 32
    num_key_value_heads: int = 4
    head_dim: int = 128
    intermediate_size: int = 6144
    moe_intermediate_size: int = 1024
    num_experts: int = 128
    num_experts_per_tok: int = 8
    num_shared_experts: int = 1
    experts_held: int = 128
    first_expert: int = 0
    num_dense_layers: int = 2
    layer_types: tuple = (SLIDING, SLIDING, SLIDING, FULL) * 8
    sliding_window: int = 2048
    rope_theta: float = 10000.0
    rms_norm_eps: float = 1e-5
    route_norm: bool = True
    route_scale: float = 2.826
    compute_dtype: str | None = None
    q_block: int = 512  # query rows an attention block holds (XLA path)
    kv_chunk: int = 2048  # step in which a full layer's key prefix grows
    model_type: str = AFMOE
    # latent attention's widths and the prediction depths beyond the next
    # token (model_type glm4_moe_lite; 0 = the type has none)
    q_lora_rank: int = 0
    kv_lora_rank: int = 0
    qk_nope_head_dim: int = 0
    qk_rope_head_dim: int = 0
    v_head_dim: int = 0
    num_nextn_predict_layers: int = 0
    mtp_loss_weight: float = 0.3
    # positions a ``conv`` layer's filter covers (model_type lfm2_moe)
    conv_L_cache: int = 3
    # a ``linear_attention`` layer's Gated DeltaNet and the share of a full
    # layer's heads that rotary positions cover (model_type qwen3_next)
    linear_num_key_heads: int = 0
    linear_num_value_heads: int = 0
    linear_key_head_dim: int = 0
    linear_value_head_dim: int = 0
    linear_conv_kernel_dim: int = 4
    partial_rotary_factor: float = 1.0


class Block(nn.Module):
    dims: Dims
    layer: int

    @nn.compact
    def __call__(self, h):
        c = self.dims
        latent = c.model_type == GLM4_MOE_LITE
        early = c.model_type == SMALLTHINKER  # the router reads the input
        lfm = c.model_type == LFM2_MOE
        qwen = c.model_type == QWEN3_NEXT

        def norm(name):
            return RMSNorm(c.rms_norm_eps, name=name)

        def branch(name, y):
            # Trinity's block norms a branch's output too, inside the
            # residual; the other types have the two pre-norms only
            return norm(name)(y) if c.model_type == AFMOE else y

        dense = self.layer < c.num_dense_layers
        if not dense:
            moe = MoE(c.hidden_size, c.num_experts, c.num_experts_per_tok,
                      c.experts_held, c.first_expert, c.moe_intermediate_size,
                      c.moe_intermediate_size * c.num_shared_experts,
                      c.route_norm, c.route_scale, c.compute_dtype,
                      softmax_routing=early or qwen, relu=early,
                      shared_gate=qwen, name="moe")
        # smallthinker routes on the block's un-normed INPUT: the experts a
        # token goes to do not depend on what attention adds to it
        routing = moe.route(h) if early else None
        a = norm("input_norm")(h)
        if latent:
            with jax.named_scope(scopes.ATTENTION_MLA):
                o = LatentAttention(
                    c.num_attention_heads, c.q_lora_rank, c.kv_lora_rank,
                    c.qk_nope_head_dim, c.qk_rope_head_dim, c.v_head_dim,
                    c.rope_theta, c.rms_norm_eps, c.q_block, c.kv_chunk,
                    c.compute_dtype, name="attn")(a)
        elif c.layer_types[self.layer] == CONV:
            # the token mixer sits where attention sits, under its name
            with jax.named_scope(scopes.SHORT_CONV):
                o = ShortConv(c.hidden_size, c.conv_L_cache, c.compute_dtype,
                              name="attn")(a)
        elif c.layer_types[self.layer] == LINEAR:
            with jax.named_scope(scopes.DELTA_RULE):
                o = GatedDeltaNet(
                    c.linear_num_key_heads, c.linear_num_value_heads,
                    c.linear_key_head_dim, c.linear_value_head_dim,
                    c.linear_conv_kernel_dim, c.rms_norm_eps, c.compute_dtype,
                    name="linear_attn")(a)
        else:
            sliding = c.layer_types[self.layer] == SLIDING
            with jax.named_scope(scopes.ATTENTION_WINDOW if sliding
                                 else scopes.ATTENTION_FULL):
                o = Attention(
                    c.num_attention_heads, c.num_key_value_heads, c.head_dim,
                    c.sliding_window if sliding else None, c.rope_theta,
                    c.rms_norm_eps, c.q_block, c.kv_chunk, c.compute_dtype,
                    qk_norm=not early, gate=not (early or lfm),
                    rope=True if lfm or qwen else None,
                    # static: two numbers of the configuration
                    rotary_dim=(None if c.partial_rotary_factor == 1 else int(  # jaxlint: disable=R005
                        c.head_dim * c.partial_rotary_factor)),
                    name="attn")(a)
        h = h + branch("post_attn_norm", o)
        m = norm("pre_mlp_norm")(h)
        if dense:
            y = SwiGLU(c.intermediate_size, c.compute_dtype, name="mlp")(m)
        else:
            y = moe(m, routing)
        return h + branch("post_mlp_norm", y)


class NextDepth(nn.Module):
    """One multi-token-prediction module: from the hidden states ``h [B, T,
    hidden]`` of the depth before and the embeddings ``e`` of the tokens one
    position on, the hidden states that predict the token after those, and
    the scale of the module's own norm before the (shared) head."""

    dims: Dims

    @nn.compact
    def __call__(self, h, e):
        c = self.dims
        both = jnp.concatenate([RMSNorm(c.rms_norm_eps, name="enorm")(e),
                                RMSNorm(c.rms_norm_eps, name="hnorm")(h)], -1)
        eh = self.param("eh_proj", _init(), (both.shape[-1], h.shape[-1]))
        # an expert block of the model's kind: its index is past the dense
        # layers
        block = nn.remat(Block, policy=BLOCK_KEEPS)(
            c, max(len(c.layer_types), c.num_dense_layers), name="block")
        out = block(_mm(both, eh, compute_dtype_of(c.compute_dtype)))
        return out, self.param("norm", nn.initializers.ones, (h.shape[-1],))


def _head_logits(h, norm_scale, head, eps, cdt):
    return _mm(rms_norm(h, norm_scale, eps), head, cdt)


class AFMoE(nn.Module):
    """The decoder, of any type (``dims.model_type``). A sample is
    ``seq_len + 1`` token ids: the model reads the first ``seq_len``, the loss
    the last ``seq_len``. ``__call__`` returns whole logits of the next-token
    depth (inference, tests); training goes through :meth:`task_loss`, which
    the trainer's ``FederatedTask`` finds."""

    dims: Dims = Dims()
    vocab_rows: int = 200192
    mup_enabled: bool = True
    # the head is the embedding's own matrix: no ``lm_head`` in the tree
    tie_word_embeddings: bool = False
    loss_block: int = 1024  # positions the head and the loss hold at a time
    init_tokens: int = 8  # positions the forward traces under init()

    @property
    def compute_dtype(self):
        return self.dims.compute_dtype

    def setup(self):
        d = self.dims
        # smallthinker's router reads the UN-normed stream: the stream's scale
        # decides the routing. With rows of norm 1 under branches of norm 10
        # and more, every token's stream is the branches' common part, the
        # router sends a layer's tokens to one or two experts, and Adam moves
        # all their logits together (PERF.md §6, PR 34); rows at unit
        # variance keep a token's own vector the larger part, as the scaled
        # embedding does for Trinity
        std = 1.0 if d.model_type == SMALLTHINKER else 0.02
        self.embed = self.param(
            "embed", _init(std), (self.vocab_rows, d.hidden_size))
        self.blocks = [
            nn.remat(Block, policy=BLOCK_KEEPS)(d, i, name=f"layer_{i}")
            for i in range(len(d.layer_types))
        ]
        self.final_norm = self.param(
            "final_norm", nn.initializers.ones, (d.hidden_size,))
        if not self.tie_word_embeddings:
            self.lm_head = self.param(
                "lm_head", _init(), (d.hidden_size, self.vocab_rows))
        if d.num_nextn_predict_layers:
            self.mtp = NextDepth(d, name="mtp")

    def init(self, rngs, *args, **kwargs):
        """``nn.Module.init`` under one ``jax.jit``: op by op, half a billion
        normal draws and a traced forward are minutes on a chip."""
        fn = functools.partial(nn.Module.init, self, **kwargs)
        return jax.jit(fn)(rngs, *args)

    def hidden(self, tokens):
        """``tokens [B, T]`` -> the last block's output ``[B, T, hidden]``."""
        tokens = tokens.astype(jnp.int32)
        if self.is_initializing():
            # no parameter's shape depends on the sequence length, and init
            # runs op by op: a handful of positions declares everything
            tokens = tokens[:, : self.init_tokens]
        h = self._embed(tokens)
        for block in self.blocks:
            h = block(h)
        if self.is_initializing() and self.dims.num_nextn_predict_layers:
            self.mtp(h, h)  # the second depth's parameters, by shape
        return h

    def _embed(self, tokens):
        h = jnp.take(self.embed, tokens, axis=0)
        if self.mup_enabled:
            h = h * math.sqrt(self.dims.hidden_size)
        return h

    def _logits_fn(self, norm_scale=None):
        # tied, the head's gradient and the embedding's scatter-add land on
        # the one matrix
        head = self.embed.T if self.tie_word_embeddings else self.lm_head
        return functools.partial(
            _head_logits, head=head, eps=self.dims.rms_norm_eps,
            norm_scale=self.final_norm if norm_scale is None else norm_scale,
            cdt=compute_dtype_of(self.dims.compute_dtype))

    def __call__(self, x, train: bool = True, mask=None):
        """Logits ``[B, T, vocab_rows]`` for the first ``T = x.shape[1] - 1``
        ids of each row (the last id is only ever a target)."""
        h = self.hidden(x[:, :-1])
        with jax.named_scope(scopes.LM_HEAD):
            return self._logits_fn()(h)

    def _summed_nll(self, h, targets, logits_of, counts=None):
        """Cross-entropy of ``h [B, T, H]`` against ``targets [B, T]`` summed
        over the positions (those where ``counts [B, T]`` holds, if given),
        ``[B]`` float32, the head and the softmax over ``loss_block``
        positions at a time."""
        B, T, H = h.shape
        lb = min(self.loss_block, T)
        if T % lb:
            raise ValueError(f"sequence {T} is not a multiple of loss_block {lb}")

        @jax.checkpoint
        def block_nll(args):
            hb, tb, *cb = args  # [B, lb, H], [B, lb]
            logits = logits_of(hb)  # [B, lb, V] float32
            lse = jax.nn.logsumexp(logits, axis=-1)
            picked = jnp.take_along_axis(logits, tb[..., None], axis=-1)[..., 0]
            nll = lse - picked
            if cb:
                nll = jnp.where(cb[0], nll, 0.0)
            return nll.sum(axis=-1)

        def blocks(a):
            return jnp.moveaxis(a.reshape((B, T // lb, lb) + a.shape[2:]), 1, 0)

        parts = (h, targets) if counts is None else (h, targets, counts)
        with jax.named_scope(scopes.LM_HEAD):
            nll = jax.lax.map(block_nll, tuple(blocks(a) for a in parts))
        return nll.sum(axis=0)

    def token_losses(self, x):
        """The training loss of each row, ``[B]`` float32: the mean next-token
        cross-entropy and, where the model has a second prediction depth,
        ``mtp_loss_weight`` times that depth's mean."""
        tokens = x.astype(jnp.int32)
        h = self.hidden(tokens[:, :-1])
        T = h.shape[1]
        loss = self._summed_nll(h, tokens[:, 1:], self._logits_fn()) / T
        if self.dims.num_nextn_predict_layers:
            with jax.named_scope(scopes.MTP):
                # position i reads h_i and E[t_{i+1}] and predicts t_{i+2};
                # the last position has no such target and does not count
                h2, norm2 = self.mtp(h, self._embed(tokens[:, 1:]))
                targets = jnp.pad(tokens[:, 2:], ((0, 0), (0, 1)))
                counts = jnp.broadcast_to(jnp.arange(T) < T - 1, targets.shape)
                deeper = self._summed_nll(
                    h2, targets, self._logits_fn(norm2), counts) / max(T - 1, 1)
            loss = loss + self.dims.mtp_loss_weight * deeper
        return loss

    def task_loss(self, variables, x, w):
        """The task's training loss for ``FederatedTask``: rows weighted by
        ``w [B]`` (0 = padding)."""
        per_row = self.apply(variables, x, method=AFMoE.token_losses)
        return (per_row * w).sum() / jnp.maximum(w.sum(), 1.0)
