"""Operations of the ``qwen3_next`` decoder share a chip holds, from shapes
alone.

``train_flops_per_sample`` (a sample is one sequence of ``seq_len`` tokens) is
3 x the FLOPs of the forward pass (forward plus backward; recomputed
operations do not count): a ``linear_attention`` layer's two input
projections (hidden -> q | k | v | z and hidden -> b | a') and its output
projection (the depth-wise convolution's four multiply-adds a channel, the
gates and the norms are element-wise work and are not counted) and its gated
delta rule at ``6 d_k d_v`` FLOPs a position and value head (the state's
decay and update, ``S^T k`` and ``S^T q``: what the recurrence needs
whatever algorithm computes it; the chunked form does more); a
``full_attention`` layer's five projections (q, its gate, k, v, o) with the
scores and the weighted values over the UNMASKED pairs only (causal); the
router; the shared expert and its gate; the routed SwiGLU experts at the
EXPECTED number of held assignments a token (``num_experts_per_tok x
experts_held / num_experts``: what uniform routing sends here; the real count
is on an earlier line of every run) and the head over the held rows of the
vocabulary. At the cell's sizes (three Gated DeltaNet layers and one
attention layer, 16 of 512 experts, 18,992 rows, 8,192 tokens): 452.4 MFLOP a
token forward, 11.12 TFLOP a trained sequence.

``kernel_model`` is what the attention kernels (``models/afmoe.py ATTN_FWD /
ATTN_DQ / ATTN_DKV``: jax's splash-attention Pallas kernels, here with eight
query heads a key-value head at head width 256) do in one round, call by
call, over the unmasked pairs only: forward, dq and dkv ONCE an ATTENTION
layer (a ``linear_attention`` layer calls none; the block's checkpoint keeps
the forward's output and log-sum-exp). The delta rule is XLA's, and the
grouped products of the routed experts are ``jax.lax.ragged_dot``, which the
TPU compiler lowers itself: neither is a Pallas call of the program's and
neither is in the model.
"""

from __future__ import annotations

FULL = "full_attention"
LINEAR = "linear_attention"


def _layer_types(a) -> tuple:
    from dinunet_implementations_tpu.runner.registry import afmoe_layer_types

    return afmoe_layer_types(a)


def causal_pairs(t: int) -> int:
    """(query, key) pairs one sequence of ``t`` positions keeps in one causal
    layer: ``j <= i``."""
    return t * (t + 1) // 2


def attention_layers(a) -> int:
    return sum(kind == FULL for kind in _layer_types(a))


def linear_layers(a) -> int:
    return sum(kind == LINEAR for kind in _layer_types(a))


def forward_flops_per_sequence(cfg) -> dict:
    """Forward FLOPs of one sequence, by part."""
    a = cfg.lm_args
    t, h = a.seq_len, a.hidden_size
    qd = a.num_attention_heads * a.head_dim
    kvd = a.num_key_value_heads * a.head_dim
    keys = a.linear_num_key_heads * a.linear_key_head_dim
    values = a.linear_num_value_heads * a.linear_value_head_dim
    held = a.experts_held or a.num_experts
    vocab = a.vocab_rows or a.vocab_size
    layers, full, linear = (len(_layer_types(a)), attention_layers(a),
                            linear_layers(a))
    expert = 3 * 2 * h * a.moe_intermediate_size
    return {
        "delta_projections": linear * t * 2 * h * (
            2 * keys + 2 * values + 2 * a.linear_num_value_heads + values),
        "delta_rule": linear * t * a.linear_num_value_heads
        * 6 * a.linear_key_head_dim * a.linear_value_head_dim,
        "projections": full * t * 2 * h * (3 * qd + 2 * kvd),
        "attention": full * causal_pairs(t) * a.num_attention_heads
        * 4 * a.head_dim,
        "router": layers * t * 2 * h * a.num_experts,
        "shared_expert": layers * t * (
            a.num_shared_experts * expert + 2 * h),
        "routed_experts": layers * t * expert
        * a.num_experts_per_tok * held / a.num_experts,
        "head": t * 2 * h * vocab,
    }


def train_flops_per_sample(cfg) -> float:
    return 3.0 * float(sum(forward_flops_per_sequence(cfg).values()))


def kernel_model(cfg, rows_per_round: int) -> dict:
    """Least work of the attention kernels in one round of ``rows_per_round``
    sequences on one device. ``calls``: one entry per kernel with how many
    run a round (``count``: one an attention layer, the forward too), its
    matmul ``flops`` and the ``bytes`` it has to stream at the least, per
    call.

    Per unmasked pair and query head: forward ``q k^T`` and ``p v`` (4 d);
    the queries' backward ``q k^T``, ``do v^T`` and ``ds k`` (6 d); the keys'
    and values' backward ``q k^T``, ``do v^T``, ``p^T do`` and ``ds^T q`` (8
    d), ``d`` the head width. A kernel computes whole blocks, the masked part
    of a block on the mask's edge included, so it does more than this. Bytes,
    as ``flops/afmoe.py`` reckons them: every call reads q, k, v once; the
    forward writes o and the log-sum-exp, the backward calls read o, do and
    the log-sum-exp and write their cotangents, at the compute dtype (float32
    for the log-sum-exp and the cotangents)."""
    a = cfg.lm_args
    full = attention_layers(a)
    t, d = a.seq_len, a.head_dim
    n, g = a.num_attention_heads, a.num_key_value_heads
    act = 2 if a.compute_dtype == "bfloat16" else 4
    per_layer = rows_per_round * causal_pairs(t) * n * d
    q, kv, lse = t * n * d, 2 * t * g * d, t * n * 4
    rows = rows_per_round
    calls = [
        {"name": "forward", "count": full, "flops": 4.0 * per_layer,
         "bytes": float(rows * ((2 * q + kv) * act + lse))},
        {"name": "backward_dq", "count": full, "flops": 6.0 * per_layer,
         "bytes": float(rows * ((3 * q + kv) * act + lse + q * 4))},
        {"name": "backward_dkv", "count": full, "flops": 8.0 * per_layer,
         "bytes": float(rows * ((3 * q + kv) * act + lse + kv * 4))},
    ]
    return {"calls": calls,
            "flops": sum(c["count"] * c["flops"] for c in calls),
            "bytes": sum(c["count"] * c["bytes"] for c in calls)}
