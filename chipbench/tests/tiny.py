"""A cell of BENCHMARK.json cut to a CPU test's size (narrow CNN, few
clients and uploads), and a whole harness run of it with the chip check
skipped."""
import time

from chipbench import harness, spec


def tiny_cell(workload: str):
    cell = spec.load_cell(workload)
    cell.config["model"].update(conv1=4, conv2=8, fc=16)
    cell.config["task"].update(num_clients=8, train_n=800, test_n=100)
    run = cell.traffic["run"]
    if run["algorithm"] == "fedavg":
        run.update(iterations=4, eval_every=2)
    else:
        run.update(iterations=32, eval_every=16)
    return cell


def run_tiny(workload: str, seed: int = 2 ** 31 + 17, trace: bool = False,
             trace_dir=None):
    cell = tiny_cell(workload)
    return harness.run_cell(cell, seed, 0.2, trace,
                            t_start=time.perf_counter(), trace_dir=trace_dir,
                            log=lambda s: None)
