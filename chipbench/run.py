"""Run one benchmark cell once on the chip and print its result line.

    python3 chipbench/run.py --workload mnist-csmaafl --seed 7 \
        --seconds 10 --trace 0

Set-up (task, fleet, plane, warm-up run: every program the window uses
is compiled or loaded from JAX's persistent cache) counts as ``setup_s``;
then the window drives whole runs back to back for ``--seconds``
(``--trace 0``: the end-to-end metrics), or one whole traced run
(``--trace 1``: the per-layer metrics, read from the device trace and
the runner's counters).  The finished run drawn from the seed is then
checked against the plain float32 reference (``reference.py``); every
compared number is printed beside its limit, last on stderr and in the
result line's ``checks``.  The last stdout line is the JSON result.

Exits non-zero, printing no result, when JAX's first device is not a TPU
or there are fewer devices than the cell asks for.
"""
from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)


def _fail(msg: str) -> int:
    print(f"chipbench: {msg}", file=sys.stderr)
    return 2


def start_compile_cache():
    """JAX's persistent compilation cache at the program's fixed path
    (``repro.launch.compile_cache``), keeping every program, however
    quick to compile, so that only a checkout's first run compiles."""
    import jax

    from repro.launch.compile_cache import enable_compile_cache

    path = enable_compile_cache()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    return path


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)

    sys.path[:0] = [ROOT, os.path.join(ROOT, "src")]
    from chipbench.spec import load_cell

    cell = load_cell(args.workload)
    import jax

    devices = jax.devices()
    if devices[0].platform != "tpu":
        return _fail(f"no TPU: JAX's first device is {devices[0].platform}")
    if len(devices) < cell.chips:
        return _fail(f"{args.workload} needs {cell.chips} chips, JAX sees "
                     f"{len(devices)}")
    cache = start_compile_cache()

    from chipbench.harness import run_cell

    trace_dir = os.path.join(ROOT, ".chipbench_trace", args.workload)
    out = run_cell(cell, args.seed, args.seconds, bool(args.trace),
                   t_start=T_START, trace_dir=trace_dir,
                   log=lambda s: print(s, flush=True))
    print("info " + json.dumps(dict(out.info, compile_cache=cache),
                               default=str), flush=True)
    for k, v in out.line["checks"].items():
        print(f"check {k} value={v['value']!r} limit={v['limit']!r}",
              file=sys.stderr)
    print(json.dumps(out.line), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
