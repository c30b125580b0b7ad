"""Client plane, per-event retrain scan: device time of the compiled
scan segments (``jit_seg``) less the weighted-aggregation kernel inside
them, per upload."""
from chipbench.trace import WAGG_KERNEL

PROGRAM = "jit_seg("


def read(ctx):
    seg_s, n = ctx.module_time_s(PROGRAM)
    uploads = ctx.stats.get("uploads")
    if not n or not uploads:
        return None
    kern_s = sum(s for _n, mod, s in ctx.ops_matching(WAGG_KERNEL)
                 if mod.startswith(PROGRAM))
    return 1e3 * (seg_s - kern_s) / uploads
