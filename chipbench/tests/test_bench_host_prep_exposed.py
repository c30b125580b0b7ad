"""``host_prep_exposed_us_per_upload`` reads the device's idle time
under the program's own host spans: on a trace built here, through a
whole traced harness run of each cell (cut to a CPU size), and not at
all where the program records none.  The program's spans leave every
other reader's value as it was, and name the device's idle gaps in the
breakdown."""
import pytest

from chipbench import flops, harness, spec
from chipbench.spec import Cell, benchmark, metric_reader
from chipbench.tests.tiny import run_tiny
from chipbench.trace import Events, Trace, TraceContext, _assign_modules

MS = 1e6        # ns
READER = "host_prep_exposed_us_per_upload"
MODEL = {"image_size": 28, "channels": 1, "conv1": 32, "conv2": 64,
         "kernel": 5, "fc": 512, "num_classes": 10}
PEAKS = spec.peaks("TPU v5 lite")
KERNEL_OP = ("%blend.10 = f32[1,13312,128] custom-call(f32[2] %a), "
             'custom_call_target="tpu_custom_call"')

# what the program records inside bench.run (0-20 ms): the trace
# compile, the staging, two segments each with its inputs, an eval and
# a fleet round's staging, one under the round (hidden by the device's
# work); the last staging runs past the window
PROGRAM_SPANS = [
    ("afl.compile_trace", 0.0, 1 * MS),
    ("afl.stage_events", 1 * MS, 2 * MS),
    ("afl.segment", 3 * MS, 4 * MS),
    ("afl.segment_inputs", 3 * MS, 0.5 * MS),
    ("afl.segment", 7 * MS, 4 * MS),
    ("afl.segment_inputs", 7 * MS, 0.5 * MS),
    ("afl.eval", 11 * MS, 1.5 * MS),
    ("plane.stage_fleet", 12.5 * MS, 0.5 * MS),
    ("plane.stage_fleet", 14 * MS, 1 * MS),
    ("plane.stage_fleet", 19 * MS, 3 * MS),
]


def _trace(program_spans):
    # device: the fleet init 2.5-3 ms, two segments 3.5-7 and 7.5-11 ms
    # (a blend kernel in each), the fleet round 13-19 ms
    mods = [("jit__lambda(11)", 2.5 * MS, 0.5 * MS),
            ("jit_seg(12)", 3.5 * MS, 3.5 * MS),
            ("jit_seg(12)", 7.5 * MS, 3.5 * MS),
            ("jit__lambda(11)", 13 * MS, 6 * MS)]
    ops = [("fusion.1", 2.5 * MS, 0.5 * MS),
           ("convolution.7", 3.5 * MS, 3 * MS),
           (KERNEL_OP, 6.5 * MS, 0.5 * MS),
           ("convolution.7", 7.5 * MS, 3 * MS),
           (KERNEL_OP, 10.5 * MS, 0.5 * MS),
           ("fusion.1", 13 * MS, 6 * MS)]
    host = [("bench.run", 0.0, 20 * MS)] + list(program_spans)
    ops_e, mods_e = Events.of(ops), Events.of(mods)
    return Trace(ops_e, _assign_modules(ops_e, mods_e), mods_e,
                 Events.of(host), 1)


def _ctx(program_spans, stats):
    cell = Cell("t", 1, {"family": "paper_cnn", "model": MODEL, "task": {
        "num_clients": 100, "batch_size": 5, "local_batches_per_step": 8}},
        {}, {}, [], [])
    return TraceContext(_trace(program_spans), cell=cell, stats=stats,
                        peaks=PEAKS, flops=flops)


STATS = {"uploads": 4, "samples": 1000, "launches": 3}


def test_device_idle_under_the_preparation_spans():
    # preparation 0-3 ms (compile, staging), 3-3.5 and 7-7.5 (segment
    # inputs), 12.5-13, 14-15 and 19-20 (fleet staging, the last cut at
    # the window's end); the device idles 0-2.5, 3-3.5, 7-7.5, 11-13 and
    # 19-20: 5.0 ms exposed over 4 uploads.  The staging at 2.5-3 and
    # 14-15 runs under device work, and the eval at 11-12.5 is no
    # preparation
    got = metric_reader(READER)(_ctx(PROGRAM_SPANS, STATS))
    assert got == pytest.approx(1e3 * 5.0 / 4)


def test_preparation_hidden_under_device_work_reads_zero():
    hidden = [("afl.stage_events", 4 * MS, 2 * MS),
              ("plane.stage_fleet", 14 * MS, 1 * MS)]
    assert metric_reader(READER)(_ctx(hidden, STATS)) == 0.0


def test_no_reading_without_the_program_spans_or_uploads():
    assert metric_reader(READER)(_ctx([], STATS)) is None
    assert metric_reader(READER)(_ctx(PROGRAM_SPANS, {})) is None


def test_program_spans_leave_the_other_readers_as_they_were():
    for m in benchmark()["per_layer"]:
        if m["name"] == READER:
            continue
        read = metric_reader(m["name"])
        assert read(_ctx(PROGRAM_SPANS, STATS)) == \
            read(_ctx([], STATS)), m["name"]


def test_idle_gaps_are_named_by_the_program_spans():
    gaps = dict((round(d * 1e3, 6), n) for n, d in
                _ctx(PROGRAM_SPANS, STATS).breakdown()["idle_gaps"])
    assert gaps[2.5] == "afl.stage_events"      # 0-2.5 ms before the init
    assert gaps[2.0] == "afl.eval"              # 11-13 ms
    assert gaps[1.0] == "plane.stage_fleet"     # 19-20 ms
    bare = dict((round(d * 1e3, 6), n) for n, d in
                _ctx([], STATS).breakdown()["idle_gaps"])
    assert set(bare.values()) == {"bench.run"}


@pytest.mark.parametrize("workload", ["mnist-csmaafl", "mnist-fedavg"])
def test_traced_run_reports_host_prep_exposed(monkeypatch, tmp_path, workload):
    # the traced path asks for the chip's peaks; a CPU run borrows them,
    # and its numbers are CPU times, checked here only for presence
    monkeypatch.setattr(harness, "peaks",
                        lambda _kind: spec.peaks("TPU v5 lite"))
    out = run_tiny(workload, trace=True, trace_dir=str(tmp_path))
    assert out.line["correct"] is True, out.line["checks"]
    assert out.line["metrics"][READER]["value"] > 0.0
    assert out.line["metrics"][READER]["unit"] == "us"
