"""Driver of ``repro.api.run``, the run facade: ``api.run(task,
RunConfig, fleet=, client_plane=, params0=, eval_fn=)``, with the
traffic's ``run`` block as the ``RunConfig`` (CSMAAFL on the compiled
loop, FedAvg, ...).

The task, the fleet and the client plane are built once in set-up and
passed in, so every run reuses the programs the plane already compiled:
built anew per call, each run would trace and compile again.  The task
is the configuration's model family's (``families/<name>.py``:
``task``).  Every run starts from weights the benchmark makes from the
run's seed (the family's ``init_params``, one jitted call), and hands
the task's own eval to the run through ``eval_fn``, under a
``bench.eval`` span; the eval also keeps the global it was given, for
the check.

What a run leaves for the check (``check.py``) besides its final model:
a CSMAAFL run the globals at its eval points and the last uploader's
retrained row (read from ``AFLResult.state``'s fleet buffer at the
plane's index of that client).
"""
from __future__ import annotations

from typing import Any, Dict

import numpy as np

from chipbench.drivers import Answer, RunRecord, derive_seed

_FLEET_DEFAULTS = {"tau": 1.0, "hetero_a": 4.0, "adaptive": True,
                   "min_steps": 1, "max_steps": 8, "base_local_steps": 1,
                   "seed": 0}


def row_index(plane, cid: int) -> int:
    """Where client ``cid``'s row sits in the plane's buffer: its slot
    on a paged plane, its id on a dense one."""
    if getattr(plane, "paged", False):
        return plane.slot_index(cid)
    return cid


class ApiRunDriver:
    """``repro.api.run`` with the traffic's ``run`` block."""

    def __init__(self, config: Dict[str, Any], traffic: Dict[str, Any],
                 seed: int):
        self.config, self.traffic, self.seed = config, traffic, seed
        self.model, self.task_cfg = config["model"], config["task"]
        self.data_seed = derive_seed(seed, 0)

    def setup(self):
        import jax

        from chipbench.spec import family
        from repro import api
        from repro.core.scheduler import make_fleet

        run = dict(self.traffic["run"])
        fleet = dict(_FLEET_DEFAULTS, **run.pop("fleet", {}))
        fleet["num_clients"] = self.task_cfg["num_clients"]
        self.fleet_cfg = fleet
        self.cfg = api.RunConfig.from_dict(dict(run, fleet=fleet))
        self.fedavg = self.cfg.algorithm == "fedavg"
        self.iid = bool(self.traffic.get("iid", True))
        fam = family(self.config.get("family"))
        self.task = fam.task(self.config, self.iid, self.data_seed)
        self.per_iteration = fam.samples_per_iteration(self.config)
        self.fleet = make_fleet(
            fleet["num_clients"], tau=fleet["tau"],
            hetero_a=fleet["hetero_a"],
            samples_per_client=self.task.num_samples(), seed=fleet["seed"],
            adaptive=fleet["adaptive"], min_steps=fleet["min_steps"],
            max_steps=fleet["max_steps"],
            base_local_steps=fleet["base_local_steps"])
        self.plane = self.task.client_plane(self.fleet)

        def bench_init(s):
            return fam.init_params(self.model, s)

        self.init = jax.jit(bench_init)

    def _eval_fn(self, snaps: list):
        import jax

        def eval_fn(params):
            snaps.append(params)
            with jax.profiler.TraceAnnotation("bench.eval"):
                return self.task.eval_fn(params)

        return eval_fn

    def run(self, run_seed: int, warmup: bool = False) -> RunRecord:
        import jax

        from repro import api

        w0 = self.init(run_seed)
        cfg = self.cfg.replace(seed=run_seed)
        if warmup:
            cfg = cfg.replace(iterations=min(
                cfg.iterations,
                int(self.traffic.get("warmup_iterations", cfg.iterations))))
        snaps: list = []
        eval_fn = self._eval_fn(snaps) if cfg.evaluate else None
        M = len(self.fleet)
        part = {"kind": "iid" if self.iid else "label",
                "seed": self.data_seed}
        per = self.per_iteration
        round_samples = sum(c.local_steps for c in self.fleet) * per
        if self.fedavg:
            params, _hist = api.run(self.task, cfg, fleet=self.fleet,
                                    client_plane=self.plane, params0=w0,
                                    eval_fn=eval_fn)
            jax.block_until_ready(params)
            rounds = cfg.iterations
            ans = Answer("fedavg", run_seed, self.data_seed, w0, params,
                         part, self.fleet_cfg, {"rounds": rounds})
            return RunRecord(rounds * M, [ans],
                             samples=rounds * round_samples)
        res = api.run(self.task, cfg, fleet=self.fleet,
                      client_plane=self.plane, params0=w0, eval_fn=eval_fn)
        # the eval points: upload 0 and every eval_every-th one
        at = [0] + [j for j in range(1, cfg.iterations + 1)
                    if j % cfg.eval_every == 0] if cfg.evaluate else []
        last = int(res.events[-1].cid)
        # the fleet buffer goes with the run: keep the last uploader's row
        buf = res.state["fleet_buf"]
        row = self.plane.engine.unflatten(buf[row_index(self.plane, last)])
        jax.block_until_ready((res.params, row))
        run = {"iterations": cfg.iterations, "tau_u": cfg.timing.tau_u,
               "tau_d": cfg.timing.tau_d, "gamma": cfg.gamma,
               "mu_momentum": cfg.mu_momentum}
        ans = Answer("afl", run_seed, self.data_seed, w0, res.params, part,
                     self.fleet_cfg, run,
                     cids=np.asarray([ev.cid for ev in res.events],
                                     np.int32),
                     betas=np.asarray(res.betas, np.float64),
                     last_cid=last, last_row=row,
                     marks={j: p for j, p in zip(at, snaps)
                            if 0 < j < cfg.iterations})
        samples = round_samples + per * int(sum(
            ev.local_steps for ev in res.events))
        stats = {k: res.stats[k] for k in ("launches", "segments",
                                           "variants")}
        return RunRecord(len(res.events), [ans], samples=samples,
                         stats=stats)


def make(config, traffic, seed) -> ApiRunDriver:
    return ApiRunDriver(config, traffic, seed)
