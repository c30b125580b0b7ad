"""Operations and bytes of the benchmarked work, from the shapes in a
configuration's ``model`` block (never from the program or XLA).  The
model's own counts come from its family (``families/<name>.py``:
``param_count``, ``forward_flops_per_sample``)."""
from __future__ import annotations

from typing import Dict

BYTES_F32 = 4


def train_flops(family, model: Dict[str, int], samples: int) -> float:
    """Forward and backward over ``samples`` trained samples: 3 x the
    family's forward (the backward is two matmuls of the forward's
    size)."""
    return 3.0 * family.forward_flops_per_sample(model) * samples


def wagg_bytes(n: int, clients: int, runs: int = 1) -> int:
    """Least HBM traffic of one weighted-aggregation kernel call: read
    the global and ``clients`` rows, write the global, per run."""
    return (clients + 2) * n * BYTES_F32 * runs
