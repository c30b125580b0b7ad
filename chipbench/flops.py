"""Operations and bytes of the benchmarked work, from the shapes in a
configuration's ``model`` block (never from the program or XLA)."""
from __future__ import annotations

from typing import Dict

BYTES_F32 = 4


def param_count(model: Dict[str, int]) -> int:
    """Parameters of the paper CNN: two SAME 5x5 convs, two 2x2 pools,
    fc and the class head, with biases."""
    k, ch, c1, c2 = (model["kernel"], model["channels"], model["conv1"],
                     model["conv2"])
    flat = (model["image_size"] // 4) ** 2 * c2
    return (k * k * ch * c1 + c1 + k * k * c1 * c2 + c2
            + flat * model["fc"] + model["fc"]
            + model["fc"] * model["num_classes"] + model["num_classes"])


def forward_flops_per_sample(model: Dict[str, int]) -> int:
    """2 x the multiply-adds of one image's forward pass, counted over
    every output position of the SAME convolutions (the zero border
    included: the chip computes it)."""
    s, k, ch, c1, c2 = (model["image_size"], model["kernel"],
                        model["channels"], model["conv1"], model["conv2"])
    macs = (s * s * c1 * k * k * ch
            + (s // 2) ** 2 * c2 * k * k * c1
            + (s // 4) ** 2 * c2 * model["fc"]
            + model["fc"] * model["num_classes"])
    return 2 * macs


def train_flops(model: Dict[str, int], samples: int) -> float:
    """Forward and backward over ``samples`` trained samples: 3 x the
    forward (the backward is two matmuls of the forward's size)."""
    return 3.0 * forward_flops_per_sample(model) * samples


def wagg_bytes(n: int, clients: int, runs: int = 1) -> int:
    """Least HBM traffic of one weighted-aggregation kernel call: read
    the global and ``clients`` rows, write the global, per run."""
    return (clients + 2) * n * BYTES_F32 * runs
