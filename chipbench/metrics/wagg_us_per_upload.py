"""Blend engine + kernel: device time of the Pallas weighted-aggregation
kernel per upload, over its calls in the FedAvg round's ``jit_mac_rows``
(one C = M call per round of M uploads).  Calls inside the compiled
scan are left out: the trace times them far shorter than their bytes
allow (``wagg_roofline``)."""
from chipbench.trace import WAGG_KERNEL

PROGRAM = "jit_mac_rows("


def read(ctx):
    calls = [s for _n, mod, s in ctx.ops_matching(WAGG_KERNEL)
             if mod.startswith(PROGRAM)]
    uploads = ctx.stats.get("uploads")
    if not calls or not uploads:
        return None
    return 1e6 * sum(calls) / uploads
