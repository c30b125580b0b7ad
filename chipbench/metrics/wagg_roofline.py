"""Blend engine + kernel: the weighted-aggregation kernel's share of its
HBM roofline, least time over kernel time, for the FedAvg round's
C = M call in ``jit_mac_rows``: it reads the global and C client rows
and writes the global, (C + 2)·n·4 bytes (``flops.wagg_bytes``, n the
cell's model family's ``param_count``; the pad copy before the call is
not counted).  The same kernel inside the compiled scan's ``jit_seg``
shows in the trace as a 1.9 µs op for a 20 MB call, 13x faster than HBM
allows, so its time there is not the kernel's: those calls are left
out, and a cell whose only calls are there gets no reading."""
from chipbench.trace import WAGG_KERNEL

PROGRAM = "jit_mac_rows("


def read(ctx):
    calls = [s for _n, mod, s in ctx.ops_matching(WAGG_KERNEL)
             if mod.startswith(PROGRAM)]
    if not calls:
        return None
    n = ctx.cell.family.param_count(ctx.config["model"])
    M = ctx.config["task"]["num_clients"]
    least_s = len(calls) * ctx.flops.wagg_bytes(n, M) \
        / ctx.peaks["hbm_bytes_per_s"]
    return 100.0 * least_s / sum(calls)
