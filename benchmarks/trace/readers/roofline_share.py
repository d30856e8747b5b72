"""A kernel's share of its roofline, in %: the least time the chip could take
for one round of it divided by the measured device time of the matching
operations per round. The least time is the sum over the kernel's calls
(``flops/<name>.py kernel_model``: ``calls`` with ``count``, ``flops``,
``bytes``) of ``max(flops / peak_flops, bytes / peak_bandwidth)``, peaks from
``peaks.json``. The model is printed on an earlier line (``facts``), so which
bound applies to which call can be read there. Nothing matches or the model
has no kernel -> None."""

import numpy as np

from benchmarks.trace import reduce


def least_seconds(model: dict, peak: dict) -> float:
    return float(sum(
        c["count"] * max(c["flops"] / peak["bf16_flops_per_s"],
                         c["bytes"] / peak["hbm_bytes_per_s"])
        for c in model["calls"]))


def read(ctx, pattern: str, field: str = "text"):
    model = ctx.facts.get("kernel_model")
    rounds = ctx.facts.get("rounds_traced")
    if (ctx.trace is None or not ctx.trace.devices or not model or not rounds
            or not ctx.facts.get("peak")):
        return None
    sec = np.mean(list(
        reduce.matching_seconds(ctx.trace, ctx.window, pattern, field).values()))
    if sec <= 0:
        return None
    return float(100.0 * least_seconds(model, ctx.facts["peak"]) / (sec / rounds))
