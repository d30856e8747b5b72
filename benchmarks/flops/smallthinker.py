"""Operations of the ``smallthinker`` decoder share a chip holds, from shapes
alone.

``train_flops_per_sample`` (a sample is one sequence of ``seq_len`` tokens) is
3 x the matmul FLOPs of the forward pass (forward plus backward; recomputed
operations do not count): the attention projections (q, k, v, o: no output
gate), the scores and the weighted values over the UNMASKED pairs only (causal
on a full layer, inside the window on a sliding one), the router, the routed
ReGLU experts at the EXPECTED number of held assignments a token
(``num_experts_per_tok x experts_held / num_experts``: what uniform routing
sends here; the real count is on an earlier line of every run) and the head
over the held rows of the vocabulary. The model has no dense layer and no
shared expert. At the cell's sizes (4 layers, 8 of 64 experts, 18,992 rows,
16,384 tokens): 301.7 MFLOP a token in matmuls + 271.6 in attention = 573.3
MFLOP a token forward, 28.2 TFLOP a trained sequence.

``kernel_model`` is what the attention kernels (``models/afmoe.py ATTN_FWD /
ATTN_DQ / ATTN_DKV``: jax's splash-attention Pallas kernels, here with seven
query heads a key-value head at head width 128) do in one round, call by
call, over the unmasked pairs only: forward, dq and dkv ONCE a layer (the
block's checkpoint keeps the forward's output and log-sum-exp). The grouped
products of the routed experts are ``jax.lax.ragged_dot``, which the TPU
compiler lowers itself: they are no Pallas call of the program's and are not
in the model.
"""

from __future__ import annotations


def _layer_types(a) -> tuple:
    from dinunet_implementations_tpu.runner.registry import afmoe_layer_types

    return afmoe_layer_types(a)


def layer_pairs(t: int, window: int | None) -> int:
    """(query, key) pairs one sequence of ``t`` positions keeps in one layer:
    ``j <= i`` and, with a window, ``j > i - window``."""
    if window is None or window >= t:
        return t * (t + 1) // 2
    return window * (window + 1) // 2 + (t - window) * window


def unmasked_pairs(a) -> int:
    """The same, all layers."""
    return sum(
        layer_pairs(a.seq_len, None if kind == "full_attention"
                    else a.sliding_window)
        for kind in _layer_types(a))


def forward_flops_per_sequence(cfg) -> dict:
    """Forward matmul FLOPs of one sequence, by part."""
    a = cfg.lm_args
    t, h = a.seq_len, a.hidden_size
    qd = a.num_attention_heads * a.head_dim
    kvd = a.num_key_value_heads * a.head_dim
    held = a.experts_held or a.num_experts
    vocab = a.vocab_rows or a.vocab_size
    layers = len(_layer_types(a))
    expert = 3 * 2 * h * a.moe_intermediate_size
    return {
        "projections": layers * t * 2 * h * (2 * qd + 2 * kvd),
        "attention": unmasked_pairs(a) * a.num_attention_heads * 4 * a.head_dim,
        "router": layers * t * 2 * h * a.num_experts,
        "routed_experts": layers * t * expert
        * a.num_experts_per_tok * held / a.num_experts,
        "head": t * 2 * h * vocab,
    }


def train_flops_per_sample(cfg) -> float:
    return 3.0 * float(sum(forward_flops_per_sequence(cfg).values()))


def kernel_model(cfg, rows_per_round: int) -> dict:
    """Least work of the attention kernels in one round of ``rows_per_round``
    sequences on one device. ``calls``: one entry per kernel with how many
    run a round (``count``: one a layer, the forward too), its matmul
    ``flops`` and the ``bytes`` it has to stream at the least, per call as a
    mean over the layers.

    Per unmasked pair and query head: forward ``q k^T`` and ``p v`` (4 d);
    the queries' backward ``q k^T``, ``do v^T`` and ``ds k`` (6 d); the keys'
    and values' backward ``q k^T``, ``do v^T``, ``p^T do`` and ``ds^T q`` (8
    d). A kernel computes whole blocks, the masked part of a block on the
    mask's edge included, so it does more than this. Bytes, as
    ``flops/afmoe.py`` reckons them: every call reads q, k, v once; the
    forward writes o and the log-sum-exp, the backward calls read o, do and
    the log-sum-exp and write their cotangents, at the compute dtype (float32
    for the log-sum-exp and the cotangents)."""
    a = cfg.lm_args
    layers = len(_layer_types(a))
    t, d = a.seq_len, a.head_dim
    n, g = a.num_attention_heads, a.num_key_value_heads
    act = 2 if a.compute_dtype == "bfloat16" else 4
    per_layer = rows_per_round * unmasked_pairs(a) * n * d / layers
    q, kv, lse = t * n * d, 2 * t * g * d, t * n * 4
    rows = rows_per_round
    calls = [
        {"name": "forward", "count": layers, "flops": 4.0 * per_layer,
         "bytes": float(rows * ((2 * q + kv) * act + lse))},
        {"name": "backward_dq", "count": layers, "flops": 6.0 * per_layer,
         "bytes": float(rows * ((3 * q + kv) * act + lse + q * 4))},
        {"name": "backward_dkv", "count": layers, "flops": 8.0 * per_layer,
         "bytes": float(rows * ((3 * q + kv) * act + lse + kv * 4))},
    ]
    return {"calls": calls,
            "flops": sum(c["count"] * c["flops"] for c in calls),
            "bytes": sum(c["count"] * c["bytes"] for c in calls)}
