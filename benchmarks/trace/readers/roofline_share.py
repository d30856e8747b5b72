"""A kernel family's share of its roofline, in %: the least time the chip could
take for one round of it divided by the measured device time of the matching
operations per round. The least time is the sum over the family's calls
(``flops/<name>.py kernel_model``: ``calls`` with ``name``, ``count``,
``flops``, ``bytes``) of ``max(flops / peak_flops, bytes / peak_bandwidth)``,
peaks from ``peaks.json``. ``calls`` lists the names of the family's calls;
without it every call of the model is summed. A configuration whose program
runs two kernel families names each call, and gives each family's metric file
its ``calls`` beside the ``pattern`` that matches that family's operations.
The model is printed on an earlier line (``facts``), so which bound applies to
which call can be read there. Nothing matches or the model has no kernel ->
None."""

import numpy as np

from benchmarks.trace import reduce


def least_seconds(model: dict, peak: dict, calls=None) -> float:
    """The least time of ``model``'s calls named in ``calls`` (all without
    it); a name the model does not have is an error of the metric file."""
    chosen = model["calls"]
    if calls is not None:
        unknown = set(calls) - {c["name"] for c in chosen}
        if unknown:
            raise ValueError(f"kernel_model has no call {sorted(unknown)}")
        chosen = [c for c in chosen if c["name"] in calls]
    return float(sum(
        c["count"] * max(c["flops"] / peak["bf16_flops_per_s"],
                         c["bytes"] / peak["hbm_bytes_per_s"])
        for c in chosen))


def read(ctx, pattern: str, field: str = "text", calls=None):
    model = ctx.facts.get("kernel_model")
    rounds = ctx.facts.get("rounds_traced")
    if (ctx.trace is None or not ctx.trace.devices or not model or not rounds
            or not ctx.facts.get("peak")):
        return None
    sec = np.mean(list(
        reduce.matching_seconds(ctx.trace, ctx.window, pattern, field).values()))
    if sec <= 0:
        return None
    least = least_seconds(model, ctx.facts["peak"], calls)
    return float(100.0 * least / (sec / rounds))
