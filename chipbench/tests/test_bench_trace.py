"""The trace reducers on a trace built here: idle share, kernel time,
the roofline and utilization arithmetic."""
import numpy as np
import pytest

from chipbench import flops
from chipbench.spec import Cell, benchmark, family, metric_reader
from chipbench.trace import (WAGG_KERNEL, Events, Trace, TraceContext,
                             _assign_modules, gaps, union_ns)

MS = 1e6        # ns
# a Mosaic kernel's op as the TPU trace names it
KERNEL_OP = ("%closed_call.10 = f32[1,13312,128] custom-call(f32[3] %a, "
             "f32[1,13312,128] %b, f32[1,1,13312,128] %c), "
             f"{WAGG_KERNEL}, operand_layout_constraints={{}}")


def _cell(config_model, per_layer):
    config = {"family": "paper_cnn", "model": config_model,
              "task": {"num_clients": 100, "batch_size": 5,
                       "local_batches_per_step": 8}}
    return Cell("t", 1, config, {}, {}, per_layer, [])


MODEL = {"image_size": 28, "channels": 1, "conv1": 32, "conv2": 64,
         "kernel": 5, "fc": 512, "num_classes": 10}
PEAKS = {"flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}


def _trace(uploads=4):
    # one fleet round (2 ms), then a scan segment of 4 uploads (8 ms)
    # holding one blend kernel per upload (0.1 ms each), an idle gap of
    # 5 ms, then a FedAvg-style MAC program (1 ms kernel inside)
    mods = [("jit__lambda(11)", 0.0, 2 * MS), ("jit_seg(12)", 3 * MS, 8 * MS),
            ("jit_mac_rows(13)", 16 * MS, 1 * MS)]
    ops = [("fusion.1", 0.0, 2 * MS)]
    for u in range(uploads):
        t = 3 * MS + u * 2 * MS
        ops += [("convolution.7", t, 1.9 * MS),
                (KERNEL_OP, t + 1.9 * MS, 0.1 * MS)]
    ops += [(KERNEL_OP, 16 * MS, 1 * MS)]
    host = [("bench.run", 0.0, 20 * MS), ("eval", 11 * MS, 4 * MS)]
    ops_e, mods_e = Events.of(ops), Events.of(mods)
    return Trace(ops_e, _assign_modules(ops_e, mods_e), mods_e,
                 Events.of(host), 1)


def _ctx(stats):
    return TraceContext(_trace(), cell=_cell(MODEL, []), stats=stats,
                        peaks=PEAKS, flops=flops)


def test_union_and_gaps():
    s = np.array([0.0, 1.0, 5.0])
    e = np.array([2.0, 3.0, 6.0])
    assert union_ns(s, e) == 4.0
    assert gaps(s, e, 0.0, 8.0) == [(3.0, 5.0), (6.0, 8.0)]


def test_window_busy_and_idle_share():
    ctx = _ctx({"uploads": 4})
    assert ctx.window_s == pytest.approx(0.020)
    # busy: 0-2, 3-11, 16-17 ms
    assert ctx.busy_s == pytest.approx(0.011)
    idle = metric_reader("device_idle_share")(ctx)
    assert idle == pytest.approx(100 * (1 - 11 / 20))
    gaps_ = ctx.breakdown()["idle_gaps"]
    assert gaps_[0] == ["eval", pytest.approx(0.005)]


def test_kernel_time_and_scan_time_per_upload():
    ctx = _ctx({"uploads": 4})
    # only the MAC program's call counts: 1 ms over 4 uploads
    assert metric_reader("wagg_us_per_upload")(ctx) == pytest.approx(
        1e6 * 1e-3 / 4)
    # jit_seg 8 ms less its four 0.1 ms kernels, over 4 uploads
    assert metric_reader("scan_retrain_ms_per_upload")(ctx) == \
        pytest.approx((8 - 0.4) / 4)
    assert metric_reader("fleet_round_ms")(ctx) == pytest.approx(2.0)


def test_roofline_counts_the_mac_call_bytes():
    ctx = _ctx({"uploads": 4})
    n = family("paper_cnn").param_count(MODEL)
    least = flops.wagg_bytes(n, 100, 1) / PEAKS["hbm_bytes_per_s"]
    got = metric_reader("wagg_roofline")(ctx)
    assert got == pytest.approx(100 * least / 1e-3)
    # pinned exactly: a change of the counts does not pass unseen
    assert got == 82.86385347985347


def test_mfu_and_launches_from_counters():
    ctx = _ctx({"uploads": 4, "samples": 1000, "launches": 2})
    fwd = family("paper_cnn").forward_flops_per_sample(MODEL)
    want = 100 * 3 * fwd * 1000 / (0.020 * PEAKS["flops_per_s"])
    assert metric_reader("mfu")(ctx) == pytest.approx(want)
    # pinned exactly: a change of the counts does not pass unseen
    assert metric_reader("mfu")(ctx) == 1.869007918781726
    assert metric_reader("launches_per_kupload")(ctx) == 500.0


def test_readers_leave_out_what_they_cannot_read():
    empty = Trace(Events.of([]), [], Events.of([]), Events.of([]), 1)
    ctx = TraceContext(empty, cell=_cell(MODEL, []), stats={},
                       peaks=PEAKS, flops=flops)
    for m in benchmark()["per_layer"]:
        assert metric_reader(m["name"])(ctx) is None, m["name"]


def test_breakdown_names_ops_by_instruction_and_shape():
    from chipbench.trace import short_op

    assert short_op("%reshape.157 = f32[166337,10]{1,0:T(8,128)S(1)} "
                    "reshape(f32[1663370]{0:T(1024)S(1)} %gte.363)") == \
        "%reshape.157 = f32[166337,10] reshape"
    assert short_op("%f.2 = (f32[8]{0:T(1024)}, f32[1,8]{1,0:T(1,128)}) "
                    "fusion(f32[100,8]{1,0:T(8,128)} %p)") == \
        "%f.2 = (f32[8], f32[1,8]) fusion"
    ops = _ctx({"uploads": 4}).breakdown()["device_ops"]
    assert ops[0] == ["convolution.7", pytest.approx(0.0076)]
