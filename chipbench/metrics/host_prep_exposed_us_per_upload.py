"""Executor: the host preparation the device waits for, per upload: the
device's idle time inside the union of the program's host spans
``afl.compile_trace`` (schedule, betas, fault realization),
``afl.stage_events`` (every event's batches), ``afl.segment_inputs``
(each segment's padded scan inputs) and ``plane.stage_fleet`` (a fleet
round's batches) within ``bench.run``, in microseconds per upload.
Preparation that runs while the device works on earlier launches does
not count: only the exposed part can hold ``uploads_per_s`` back.  A
program that records none of these spans gets no reading."""
import numpy as np

from chipbench.trace import gaps, union_ns

SPANS = ("afl.compile_trace", "afl.stage_events", "afl.segment_inputs",
         "plane.stage_fleet")


def read(ctx):
    h, ops = ctx.trace.host, ctx.trace.ops
    sel = [i for i, n in enumerate(h.names) if n in SPANS]
    uploads = ctx.stats.get("uploads")
    if not sel or not uploads:
        return None
    prep_s = np.clip(h.start[sel], ctx.t0, ctx.t1)
    prep_e = np.clip(h.end[sel], ctx.t0, ctx.t1)
    idle = np.asarray(gaps(ops.start, ops.end, ctx.t0, ctx.t1))
    idle = idle.reshape(-1, 2)
    # idle within prep = idle + prep - (idle or prep); gaps are disjoint
    both = union_ns(np.concatenate([idle[:, 0], prep_s]),
                    np.concatenate([idle[:, 1], prep_e]))
    exposed = float(np.sum(idle[:, 1] - idle[:, 0])) \
        + union_ns(prep_s, prep_e) - both
    return 1e-3 * exposed / uploads
