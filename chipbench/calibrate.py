"""Read the correctness check's numbers on many seeds, for its limits.

    python3 chipbench/calibrate.py --workload mnist-csmaafl \
        --base-seed 9001 --seeds 12 --control-seeds 3 [--out FILE]

In one process, at the cell's own size, through the same entry and
programs as the benchmark's window:

* ``sound`` — the program's finished runs on ``--seeds`` run seeds
  against the plain reference: the lower reading of each number is the
  largest of these;
* ``control`` — on the first ``--control-seeds`` of them, the reference
  computed at the next precision below the configuration's ("highest"
  -> "high") put in the program's place;
* ``half_batch`` / ``alter_answer`` (and, for CSMAAFL, ``blend_bf16`` /
  ``blend_coef``) — the reference with that fault put in the program's
  place (``reference.FAULTS``).

The program's runs all finish before the reference starts, as in a
benchmark run.  Prints the summary of every reading; ``--out`` writes
all of them.
"""
from __future__ import annotations

import argparse
import gc
import json
import os
import sys
import time
from typing import Any, Dict, List

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)

# the next matmul precision below the configuration's
LOWER = {"highest": "high"}


def _readings(cell, answers, ref, substitutes: bool
              ) -> Dict[str, List[Dict]]:
    from chipbench import check

    prec = cell.config["precision"]
    out: Dict[str, List[Dict]] = {"sound": []}
    for a in answers:
        sound = check.replay(ref, a, prec)
        mine = check.program(a)
        out["sound"].append(check.numbers(mine, sound, a))
        if "marks" in sound:
            # head_gap's measure at every eval point, to see it grow
            out.setdefault("mark_gaps", []).append({
                j: check.diff_gap(check.host(a.marks[j]), sound["marks"][j],
                                  check.host(a.w0)) for j in sorted(a.marks)})
        if not substitutes:
            continue
        faults = ["half_batch", "alter_answer"]
        if a.kind == "afl":
            faults += ["blend_bf16", "blend_coef"]
        for tag, p, fault in ([("control", LOWER[prec], None)]
                              + [(f, prec, f) for f in faults]):
            got = check.numbers(check.replay(ref, a, p, fault), sound, a)
            out.setdefault(tag, []).append(got)
    return out


def calibrate(cell, base_seed: int, seeds: int, control_seeds: int,
              log=print) -> Dict[str, Any]:
    import jax

    from chipbench.drivers import derive_seed, make_driver
    from chipbench.reference import Reference

    readings: Dict[str, List[Dict]] = {}
    with jax.default_matmul_precision(cell.config["precision"]):
        t = time.perf_counter()
        driver = make_driver(cell.config, cell.traffic, base_seed)
        driver.setup()
        driver.run(derive_seed(base_seed, 2))
        log(f"setup {time.perf_counter() - t:.1f} s")
        runs = []
        for i in range(seeds):
            t = time.perf_counter()
            runs.append(driver.run(derive_seed(base_seed, 1, i)))
            log(f"run {i}: {time.perf_counter() - t:.2f} s "
                f"{runs[-1].uploads} uploads")
    del driver
    gc.collect()
    ref = None
    for i, rec in enumerate(runs):
        if ref is None:
            ref = Reference(cell.family, cell.config,
                            rec.answers[0].data_seed)
        t = time.perf_counter()
        got = _readings(cell, rec.answers, ref, i < control_seeds)
        log(f"readings {i}: {time.perf_counter() - t:.2f} s {got}")
        for k, v in got.items():
            readings.setdefault(k, []).extend(v)
    summary = {}
    for tag, rows in readings.items():
        if tag == "mark_gaps":
            continue
        names = sorted({k for r in rows for k in r})
        summary[tag] = {k: {"max": max(r[k] for r in rows),
                            "min": min(r[k] for r in rows)}
                        for k in names}
    return {"workload": cell.name, "base_seed": base_seed,
            "readings": readings, "summary": summary}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--base-seed", type=int, required=True)
    ap.add_argument("--seeds", type=int, default=12)
    ap.add_argument("--control-seeds", type=int, default=3)
    ap.add_argument("--out", default=None)
    args = ap.parse_args(argv)
    sys.path[:0] = [ROOT, os.path.join(ROOT, "src")]
    import jax

    from chipbench.run import start_compile_cache
    from chipbench.spec import load_cell

    cell = load_cell(args.workload)
    if jax.devices()[0].platform != "tpu":
        print("calibrate: no TPU", file=sys.stderr)
        return 2
    start_compile_cache()
    out = calibrate(cell, args.base_seed, args.seeds, args.control_seeds,
                    log=lambda s: print(s, flush=True))
    text = json.dumps(out)
    if args.out:
        os.makedirs(os.path.dirname(os.path.abspath(args.out)),
                    exist_ok=True)
        with open(args.out, "w") as f:
            f.write(text)
    print(json.dumps(out["summary"]))
    return 0


if __name__ == "__main__":
    sys.exit(main())
