"""One run of one cell: set-up, warm-up, the measured window (or the
traced run), the check against the reference, the result line.

The chip check lives in ``run.py``; everything here runs on whatever
device JAX has, so the tests can drive a whole run on the CPU.
"""
from __future__ import annotations

import dataclasses
import gc
import shutil
import time
from typing import Any, Callable, Dict, List, Optional

import numpy as np

from chipbench import check, flops
from chipbench.drivers import RunRecord, derive_seed, make_driver
from chipbench.spec import Cell, metric_reader, peaks
from chipbench.trace import TraceContext, load_trace


class CompileCounter:
    """Counts JAX's compile and compile-cache events while armed."""

    def __init__(self):
        self.armed = False
        self.events: List[str] = []

    def install(self) -> None:
        import jax

        def on_duration(name, _secs, **_kw):
            if self.armed and name.startswith("/jax/core/compile/"):
                self.events.append(name)

        def on_event(name, **_kw):
            if self.armed and name.startswith("/jax/compilation_cache/"):
                self.events.append(name)

        jax.monitoring.register_event_duration_secs_listener(on_duration)
        jax.monitoring.register_event_listener(on_event)


@dataclasses.dataclass
class Outcome:
    line: Dict[str, Any]          # the result line
    info: Dict[str, Any]          # printed on an earlier line


def _peak_bytes(devices) -> Optional[int]:
    got = [int((d.memory_stats() or {}).get("peak_bytes_in_use", -1))
           for d in devices]
    return max(got) if got and max(got) >= 0 else None


def check_answers(cell: Cell, answers) -> Dict[str, float]:
    """The program's answers against the plain reference."""
    from chipbench.reference import Reference

    ref = Reference(cell.family, cell.config, answers[0].data_seed)
    prec = cell.config["precision"]
    return check.merge(check.numbers(check.program(a),
                                     check.replay(ref, a, prec), a)
                       for a in answers)


def run_cell(cell: Cell, seed: int, seconds: float, trace: bool, *,
             t_start: float, trace_dir: Optional[str] = None,
             devices=None, log: Callable[[str], None] = print) -> Outcome:
    """Set up, warm up (one short run that reaches every program the
    window uses, ``drivers.py``), then either drive whole runs back to
    back until ``seconds`` have passed (the window closes at a run
    boundary, so it holds only whole runs) or trace one whole run; then
    check one finished run, drawn from the seed, against the
    reference."""
    import jax

    devices = devices if devices is not None else jax.devices()
    counter = CompileCounter()
    counter.install()
    with jax.default_matmul_precision(cell.config["precision"]):
        with jax.profiler.TraceAnnotation("bench.setup"):
            driver = make_driver(cell.config, cell.traffic, seed)
            driver.setup()
        with jax.profiler.TraceAnnotation("bench.warmup"):
            warm = driver.run(derive_seed(seed, 2), warmup=True)
        setup_s = time.perf_counter() - t_start
        log(f"setup setup_s={setup_s} warmup_uploads={warm.uploads} "
            f"warmup_stats={warm.stats}")
        del warm
        records: List[RunRecord] = []
        counter.armed = True
        if trace:
            shutil.rmtree(trace_dir, ignore_errors=True)
            opts = jax.profiler.ProfileOptions()
            # host spans come from TraceAnnotations and JAX's own events;
            # the Python tracer would slow the host it is measuring
            opts.python_tracer_level = 0
            jax.profiler.start_trace(trace_dir, profiler_options=opts)
        t0 = time.perf_counter()
        while not records or (not trace and
                              time.perf_counter() - t0 < seconds):
            with jax.profiler.TraceAnnotation("bench.run"):
                records.append(driver.run(derive_seed(seed, 1,
                                                      len(records))))
        window_s = time.perf_counter() - t0
        if trace:
            jax.profiler.stop_trace()
        counter.armed = False
    peak = _peak_bytes(devices[:cell.chips])
    uploads = sum(r.uploads for r in records)
    info = {"window_s": window_s, "uploads": uploads, "runs": len(records),
            "window_compile_events": len(counter.events),
            "compile_event_names": sorted(set(counter.events)),
            "memory_peak_bytes": peak, "run_stats": records[-1].stats}
    ctx = None
    if trace:
        r = records[0]
        ctx = TraceContext(load_trace(trace_dir), cell=cell,
                           stats=dict(r.stats, uploads=r.uploads,
                                      samples=r.samples),
                           peaks=peaks(devices[0].device_kind), flops=flops)

    # one finished run, drawn from the seed, is checked once the window
    # has closed and the program's state is freed
    rng = np.random.default_rng(derive_seed(seed, 3))
    answers = records[int(rng.integers(len(records)))].answers
    del driver, records
    gc.collect()
    got = check_answers(cell, answers)
    correct = check.verdict(got, cell.limits)

    dev = {"platform": devices[0].platform, "kind": devices[0].device_kind,
           "count": cell.chips}
    if peak is not None:
        dev["memory_peak_bytes"] = peak
    line: Dict[str, Any] = {"correct": correct, "attempted": info["runs"],
                            "failed": 0 if correct else 1}
    if trace:
        metrics = {}
        for m in cell.per_layer:
            v = metric_reader(m["name"])(ctx)
            if v is not None:
                metrics[m["name"]] = {"value": float(v), "unit": m["unit"]}
        dev.update(busy_s=ctx.busy_s, window_s=ctx.window_s)
        line.update(metrics=metrics, device=dev,
                    breakdown=ctx.breakdown())
    else:
        measured = {"uploads_per_s": uploads / window_s, "setup_s": setup_s}
        line.update(metrics={m["name"]: {"value": measured[m["name"]],
                                         "unit": m["unit"]}
                             for m in cell.end_to_end}, device=dev)
    # each compared number beside its limit, last in the line
    line["checks"] = {k: {"value": got.get(k, float("nan")),
                          "limit": cell.limits.get(k, float("nan"))}
                      for k in sorted(set(got) | set(cell.limits))}
    return Outcome(line, info)
