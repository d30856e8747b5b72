"""Model FLOP/s utilization, in %: forward+backward matmul FLOPs a sample
(``flops/<name>.py train_flops_per_sample``) x samples a second of the traced
window / (chips x bf16 peak of the device_kind)."""


def read(ctx):
    f = ctx.facts
    if (not f.get("train_flops_per_sample") or not f.get("samples_per_s")
            or not f.get("peak")):
        return None
    return float(100.0 * f["train_flops_per_sample"] * f["samples_per_s"]
                 / (f["chips"] * f["peak"]["bf16_flops_per_s"]))
