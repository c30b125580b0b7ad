"""What every program entry's driver gives the harness.

A traffic file names its ``entry``; the driver of that entry is
``entries/<entry>.py`` (``make(config, traffic, seed)``), found by name
like a metric's reader, so a later cell that drives another entry adds a
file and edits none.  A driver has

* ``setup()`` — build what the entry needs once (task, fleet, plane), so
  every run reuses the programs already compiled;
* ``run(run_seed, warmup=False) -> RunRecord`` — one whole run as a user
  calls it, from weights made from ``run_seed``; ``warmup`` runs the
  traffic's ``warmup_iterations`` instead of its full length, the
  shortest run that reaches every program the full one uses.

Besides its final model, a run leaves in its ``Answer`` what the check
replays from the program's own state (``check.py``).
"""
from __future__ import annotations

import dataclasses
from typing import Any, Dict, List, Optional

import numpy as np


def derive_seed(seed: int, *path: int) -> int:
    """A 31-bit seed for one purpose (``path``) of run seed ``seed``."""
    ss = np.random.SeedSequence(abs(int(seed)),
                                spawn_key=tuple(int(p) for p in path))
    return int(ss.generate_state(1, np.uint32)[0] & 0x7FFFFFFF)


@dataclasses.dataclass
class Answer:
    """One finished run, as the reference needs it to check it."""
    kind: str                    # "afl" | "fedavg"
    seed: int
    data_seed: int               # seed of the training set (and partition)
    w0: Any                      # device pytree the run started from
    params: Any                  # device pytree the run ended with
    partition: Dict[str, Any]    # {"kind": "iid"|"label", "seed"}
    fleet: Dict[str, Any]
    run: Dict[str, Any]          # iterations / rounds, timing, gamma, mu
    cids: Optional[np.ndarray] = None
    betas: Optional[np.ndarray] = None
    # AFL: the last upload's client and its retrained row (device pytree)
    last_cid: Optional[int] = None
    last_row: Any = None
    # AFL: the global at each eval point inside the run, by upload count
    marks: Dict[int, Any] = dataclasses.field(default_factory=dict)


@dataclasses.dataclass
class RunRecord:
    uploads: int                 # uploads applied to a global model
    answers: List[Answer]
    samples: int = 0             # valid samples trained
    stats: Dict[str, Any] = dataclasses.field(default_factory=dict)


def make_driver(config, traffic, seed):
    from chipbench.spec import entry_driver

    return entry_driver(traffic["entry"])(config, traffic, seed)
