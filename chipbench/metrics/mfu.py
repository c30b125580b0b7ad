"""Device, whole step: model FLOP utilization over the traced window.
3 x the forward FLOPs of every valid sample trained (padded or masked
scan steps not counted; the forward from the cell's model family,
``families/<name>.py``'s ``forward_flops_per_sample``) over window x the
chip's peak (``peaks.json``, bf16)."""


def read(ctx):
    samples = ctx.stats.get("samples")
    if not samples or ctx.window_s <= 0.0:
        return None
    work = ctx.flops.train_flops(ctx.cell.family, ctx.config["model"],
                                 samples)
    return 100.0 * work / (ctx.window_s * ctx.peaks["flops_per_s"])
