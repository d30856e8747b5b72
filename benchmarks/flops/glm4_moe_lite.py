"""Operations of the ``glm4_moe_lite`` decoder share a chip holds, from shapes
alone.

``train_flops_per_sample`` (a sample is one sequence of ``seq_len`` tokens) is
3 x the matmul FLOPs of the forward pass (forward plus backward; recomputed
operations do not count): latent attention's five projections (hidden ->
query latent -> heads, hidden -> key-value latent and the shared rotary key,
latent -> the heads' keys and values, heads -> hidden: 43.5 MFLOP a token a
layer), the scores and the weighted values over the UNMASKED causal pairs
only (``2 (nope + rope) + 2 v`` = 4 x 256 a pair and head), the dense MLP,
the shared expert, the router, the routed experts at the EXPECTED number of
held assignments a token (``num_experts_per_tok x experts_held /
num_experts``: what uniform routing sends here; the real count is on an
earlier line of every run), the head over the held rows of the vocabulary
and, where the configuration has a second prediction depth, that depth's
projection, block and head. At the cell's sizes (5 layers, 8 of 64 experts,
19,360 rows, no second depth): 537 MFLOP a token in matmuls + 419 in
attention = 956 MFLOP a token forward, 23.5 TFLOP a trained sequence.

``kernel_model`` is what the attention kernels (``models/afmoe.py ATTN_FWD /
ATTN_DQ / ATTN_DKV``: jax's splash-attention Pallas kernels, here with one
query head a key-value head at head width 256) do in one round, call by call,
over the unmasked pairs only: the forward ONCE a layer (the block's checkpoint
keeps its output and log-sum-exp), dq and dkv once. The grouped products of
the routed experts are ``jax.lax.ragged_dot``, which the TPU compiler lowers
itself: they are no Pallas call of the program's and are not in the model.
"""

from __future__ import annotations


def _depths(a) -> int:
    """Blocks that run a round: the layers and the second depth's block."""
    return a.num_hidden_layers + a.num_nextn_predict_layers


def unmasked_pairs(a) -> int:
    """(query, key) pairs one sequence's attention keeps, all blocks: every
    layer is full causal attention (the second depth's has one row less: the
    kernels run it over the whole sequence all the same)."""
    return _depths(a) * a.seq_len * (a.seq_len + 1) // 2


def forward_flops_per_sequence(cfg) -> dict:
    """Forward matmul FLOPs of one sequence, by part."""
    a = cfg.lm_args
    t, h, n = a.seq_len, a.hidden_size, a.num_attention_heads
    qk, v = a.qk_nope_head_dim + a.qk_rope_head_dim, a.v_head_dim
    held = a.experts_held or a.num_experts
    vocab = a.vocab_rows or a.vocab_size
    deeper = a.num_nextn_predict_layers
    dense = min(a.num_dense_layers, a.num_hidden_layers)
    moe_layers = a.num_hidden_layers - dense + deeper
    expert = 3 * 2 * h * a.moe_intermediate_size
    return {
        "projections": _depths(a) * t * 2 * (
            h * a.q_lora_rank + a.q_lora_rank * n * qk
            + h * (a.kv_lora_rank + a.qk_rope_head_dim)
            + a.kv_lora_rank * n * (a.qk_nope_head_dim + v) + n * v * h),
        "attention": unmasked_pairs(a) * n * (2 * qk + 2 * v),
        "dense_mlp": dense * t * 3 * 2 * h * a.intermediate_size,
        "shared_expert": moe_layers * t * expert * a.num_shared_experts,
        "router": moe_layers * t * 2 * h * a.num_experts,
        "routed_experts": moe_layers * t * expert
        * a.num_experts_per_tok * held / a.num_experts,
        "head": (1 + deeper) * t * 2 * h * vocab,
        "next_depth_projection": deeper * t * 2 * (2 * h) * h,
    }


def train_flops_per_sample(cfg) -> float:
    return 3.0 * float(sum(forward_flops_per_sequence(cfg).values()))


def kernel_model(cfg, rows_per_round: int) -> dict:
    """Least work of the attention kernels in one round of ``rows_per_round``
    sequences on one device. ``calls``: one entry per kernel with how many
    run a round (``count``: one a block, the forward too), its matmul
    ``flops`` and the ``bytes`` it has to stream at the least, per call.

    Per unmasked pair and head, at one width ``d`` for queries, keys and
    values: forward ``q k^T`` and ``p v`` (4 d); the queries' backward ``q
    k^T``, ``do v^T`` and ``ds k`` (6 d); the keys' and values' backward ``q
    k^T``, ``do v^T``, ``p^T do`` and ``ds^T q`` (8 d). A kernel computes
    whole blocks, the masked part of a diagonal block included, so it does
    more than this. Bytes, as ``flops/afmoe.py`` reckons them, with keys and
    values at the FULL head count (latent attention's up-projected form has
    a key and a value head a query head): every call reads q, k, v once; the
    forward writes o and the log-sum-exp, the backward calls read o, do and
    the log-sum-exp and write their cotangents, at the compute dtype (float32
    for the log-sum-exp and the cotangents)."""
    a = cfg.lm_args
    blocks = _depths(a)
    t, n = a.seq_len, a.num_attention_heads
    d = a.v_head_dim  # == qk_nope_head_dim + qk_rope_head_dim (the registry)
    act = 2 if a.compute_dtype == "bfloat16" else 4
    per_block = rows_per_round * unmasked_pairs(a) * n * d / blocks
    q, kv, lse = t * n * d, 2 * t * n * d, t * n * 4
    rows = rows_per_round
    calls = [
        {"name": "forward", "count": blocks, "flops": 4.0 * per_block,
         "bytes": float(rows * ((2 * q + kv) * act + lse))},
        {"name": "backward_dq", "count": blocks, "flops": 6.0 * per_block,
         "bytes": float(rows * ((3 * q + kv) * act + lse + q * 4))},
        {"name": "backward_dkv", "count": blocks, "flops": 8.0 * per_block,
         "bytes": float(rows * ((3 * q + kv) * act + lse + kv * 4))},
    ]
    return {"calls": calls,
            "flops": sum(c["count"] * c["flops"] for c in calls),
            "bytes": sum(c["count"] * c["bytes"] for c in calls)}
