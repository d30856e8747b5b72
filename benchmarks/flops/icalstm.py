"""Operations and bytes of the ICA-LSTM, from shapes alone.

``train_flops_per_sample`` is copied from ``bench.flops_per_sample_dims`` (the
repo's own arithmetic, judged sound in ISSUE 22; the original stays in
``bench.py`` until ROADMAP D5 deletes it): matmul FLOPs of the forward pass
(encoder, both LSTM directions, classifier head) times 3 for forward plus
backward. Recomputed operations do not count.

``kernel_model`` is what the Mosaic calls of ``ops/lstm_pallas.py`` do in one
federated round, call by call, as the kernels are written (read from the
kernels' code and from the operands the four ``tpu_custom_call``s carry in a
v5e trace, PR 22): a forward and a backward call per direction. The weight
gradients and ``dx`` are XLA einsums outside the kernels (``_vjp_fused_bwd``)
and are NOT kernel work.
"""

from __future__ import annotations


def _dims(cfg) -> dict:
    a = cfg.ica_args
    return {
        "windows": int(a.temporal_size / a.window_size),
        "enc_in": a.num_components * a.window_size,
        "enc_out": a.input_size,
        "hidden": a.hidden_size,
        "directions": 2 if a.bidirectional else 1,
        "act_bytes": 2 if a.compute_dtype == "bfloat16" else 4,
    }


def train_flops_per_sample(cfg) -> float:
    d = _dims(cfg)
    h = d["hidden"] // d["directions"]
    enc = d["windows"] * d["enc_in"] * d["enc_out"] * 2
    lstm = d["windows"] * d["directions"] * (d["enc_out"] * 4 * h + h * 4 * h) * 2
    head = d["hidden"] * 256 * 2 + 256 * 64 * 2 + 64 * 2 * 2
    return 3.0 * (enc + lstm + head)


def kernel_model(cfg, rows_per_round: int) -> dict:
    """Least work of the LSTM kernels in one round of ``rows_per_round``
    samples on one device. ``calls``: one entry per kind of Mosaic call with
    how many run a round (one per direction), its matmul ``flops`` and the
    ``bytes`` it has to stream through HBM at the least; ``flops`` and
    ``bytes`` are the round's totals.

    - forward (``_fwd_fused_kernel``): per row and step ``x @ W_ih`` and
      ``h @ W_hh`` (2 * (D*4h + h*4h) FLOPs); reads ``x [T, N, D]``, writes six
      ``[T, N, h]`` streams (hs, cs and the four gate activations);
    - backward (``_bwd_kernel``): per row and step only ``dp @ W_hh^T``
      (2 * h*4h FLOPs); reads the four gate activations, cs and the cotangent
      dhs, writes the four pre-activation cotangents. The kernel fetches cs a
      second time, shifted by one step; the least reads it once.

    Streams are at the compute dtype, unpadded (in HBM a 174-wide bf16 stream
    is stored 256 wide, so the kernels move more than this); weights, biases
    and the ``[N, h]`` carries are small and stay in VMEM."""
    d = _dims(cfg)
    h = d["hidden"] // d["directions"]
    steps = d["windows"] * rows_per_round  # rows x time steps of one direction
    stream = steps * h * d["act_bytes"]
    calls = [
        {"name": "forward", "count": d["directions"],
         "flops": float(steps * 2 * (d["enc_out"] * 4 * h + h * 4 * h)),
         "bytes": float(steps * d["enc_out"] * d["act_bytes"] + 6 * stream)},
        {"name": "backward", "count": d["directions"],
         "flops": float(steps * 2 * (h * 4 * h)),
         "bytes": float(10 * stream)},
    ]
    return {"calls": calls,
            "flops": sum(c["count"] * c["flops"] for c in calls),
            "bytes": sum(c["count"] * c["bytes"] for c in calls)}
