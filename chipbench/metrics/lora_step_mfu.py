"""Device, whole step of a LoRA client: model FLOP utilization over the
traced window.  The family's ``train_flops_per_sample`` (a product with
the frozen base counts twice, forward and input gradient; every other
product three times; remat's recomputed forward not at all) x the valid
samples trained in the traced run, over window x the chip's peak
(``peaks.json``, bf16).  ``mfu`` counts 3 x the forward, which
overstates a frozen base's step by about 1.5x.  Nothing to read where
the cell's family has no such count."""


def read(ctx):
    count = getattr(ctx.cell.family, "train_flops_per_sample", None)
    samples = ctx.stats.get("samples")
    if count is None or not samples or ctx.window_s <= 0.0:
        return None
    work = count(ctx.config["model"]) * samples
    return 100.0 * work / (ctx.window_s * ctx.peaks["flops_per_s"])
