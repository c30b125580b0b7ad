"""LoRA adapters over a frozen base (Hu et al., arXiv 2106.09685).

A federated LoRA client trains only low-rank adapters and exchanges
them; the base model is frozen and shared (FedIT, arXiv 2305.05644;
OpenFedLLM, arXiv 2402.06954).  Every target weight ``W`` is applied
unmerged, ``x @ W + (alpha / r) * (x @ A) @ B``, so no gradient is
formed for ``W``: only the input gradients flow through it.

* ``init_adapters`` — the adapter tree: one dict per layer, each target
  weight's name -> ``{"a": (fan_in, r), "b": (r, fan_out)}``, A drawn
  from a seeded normal over sqrt(fan_in) and B zero (QLoRA's targets,
  arXiv 2305.14314: every linear layer of every block).
* ``attach`` — the base's parameters with each target replaced by an
  ``Adapted`` weight, which ``layers.dense`` applies.
* ``frozen_base`` — the base a run trains over, drawn weight by weight
  from (seed, layer, weight name), so a plain reference can draw the
  same base without the model code's key tree.
"""
from __future__ import annotations

import dataclasses
import math
from typing import Any, Dict, List, Tuple

import jax
import jax.numpy as jnp

from repro.configs.base import MAMBA, ModelConfig

Params = Dict[str, Any]

# the adapted weights of each block kind, in their draw order
MAMBA_TARGETS = ("in_proj", "out_proj", "w_in", "w_out")
ATTN_TARGETS = ("wq", "wk", "wv", "wo", "w_in", "w_out")
# weights whose leading two axes are contracted (the attention output)
_TWO_AXIS_INPUT = ("wo",)
# the base's draw order: a weight's key is fold_in(fold_in(key(seed),
# layer), its index here); the embedding takes layer = num_layers
BASE_NAMES = ("embed", "in_proj", "conv_w", "conv_b", "A_log", "dt_bias",
              "D", "out_proj", "wq", "wk", "wv", "wo", "w_in", "w_out")


@dataclasses.dataclass(frozen=True)
class Adapted:
    """A frozen weight ``w`` with its adapter ``a`` (fan_in, r) and ``b``
    (r, fan_out), ``scale`` = alpha / r; ``n_in`` leading axes of ``w``
    are contracted with the input's trailing ones."""
    w: Any
    a: Any
    b: Any
    scale: float
    n_in: int = 1

    def add_low_rank(self, x: jnp.ndarray, y: jnp.ndarray) -> jnp.ndarray:
        """``y`` (= x times ``w``) plus scale * (x @ a) @ b."""
        lead = x.shape[:x.ndim - self.n_in]
        low = (x.reshape(lead + (-1,)) @ self.a) @ self.b
        return y + (self.scale * low).reshape(y.shape)


jax.tree_util.register_dataclass(Adapted, data_fields=["w", "a", "b"],
                                 meta_fields=["scale", "n_in"])


def targets(kind: str) -> Tuple[str, ...]:
    return MAMBA_TARGETS if kind == MAMBA else ATTN_TARGETS


def _fan(shape, name: str) -> Tuple[int, int]:
    """(fan_in, fan_out) of a weight of ``shape``."""
    k = 2 if name in _TWO_AXIS_INPUT else 1
    return math.prod(shape[:k]), math.prod(shape[k:])


def _weights(block: Params) -> Dict[str, Any]:
    """A block's adaptable weights by name (mixer, attention, MLP)."""
    out = {}
    for sub in ("mixer", "attn", "mlp"):
        out.update(block.get(sub, {}))
    return out


def _unrolled(params: Params) -> List[Params]:
    stack = params["stack"]
    if stack["period"]:
        raise ValueError("LoRA attaches to an unrolled stack "
                         "(ModelConfig.scan_layers=False)")
    return stack["rem"]


def init_adapters(cfg: ModelConfig, rank: int, key,
                  base_shapes: Params) -> List[Params]:
    """Adapters for every target of every layer of ``base_shapes`` (the
    base's parameters or their ``jax.eval_shape``); A of target t of
    layer l from fold_in(fold_in(key, l), t's index in its kind's
    targets)."""
    out = []
    for i, (kind, block) in enumerate(zip(cfg.blocks,
                                          _unrolled(base_shapes))):
        ws = _weights(block)
        layer = {}
        for j, name in enumerate(targets(kind)):
            fan_in, fan_out = _fan(ws[name].shape, name)
            k = jax.random.fold_in(jax.random.fold_in(key, i), j)
            layer[name] = {
                "a": jax.random.normal(k, (fan_in, rank), jnp.float32)
                / math.sqrt(fan_in),
                "b": jnp.zeros((rank, fan_out), jnp.float32)}
        out.append(layer)
    return out


def attach(params: Params, adapters: List[Params], scale: float
           ) -> Params:
    """``params`` with each adapted weight of layer i wrapped with
    ``adapters[i]``."""
    blocks = []
    for block, layer in zip(_unrolled(params), adapters):
        block = dict(block)
        for sub in ("mixer", "attn", "mlp"):
            if sub not in block:
                continue
            ws = dict(block[sub])
            for name, ab in layer.items():
                if name in ws:
                    ws[name] = Adapted(
                        ws[name], ab["a"], ab["b"], scale,
                        2 if name in _TWO_AXIS_INPUT else 1)
            block[sub] = ws
        blocks.append(block)
    return dict(params, stack={"period": [], "rem": blocks})


def frozen_base(cfg: ModelConfig, seed: int) -> Params:
    """The base's parameters (unrolled stack), each weight drawn from
    fold_in(fold_in(PRNGKey(seed), layer), ``BASE_NAMES`` index):
    matrices N(0, 1) / sqrt(fan_in), drawn as (fan_in, fan_out); the
    embedding N(0, 1) * 0.02; the depthwise conv N(0, 1) / sqrt(d_conv)
    and a zero bias; A_log = log U(1, 16); dt_bias = softplus^-1(dt),
    dt = exp U(log 1e-3, log 1e-1); D = 1; every norm the identity."""
    from repro.models import transformer as tmod

    cfg = dataclasses.replace(cfg, scan_layers=False)
    shapes = jax.eval_shape(
        lambda: tmod.init_params(cfg, jax.random.PRNGKey(0)))
    root = jax.random.PRNGKey(seed)

    def draw(path, leaf):
        keys = [getattr(p, "key", getattr(p, "idx", None)) for p in path]
        name = keys[-1]
        layer = keys[2] if keys[0] == "stack" else cfg.num_layers
        if name not in BASE_NAMES:          # norm scales: (1 + 0) x
            return jnp.zeros(leaf.shape, leaf.dtype)
        k = jax.random.fold_in(jax.random.fold_in(root, layer),
                               BASE_NAMES.index(name))
        shape = leaf.shape
        if name == "embed":
            return 0.02 * jax.random.normal(k, shape, jnp.float32)
        if name == "conv_w":
            return jax.random.normal(k, shape) / math.sqrt(shape[0])
        if name == "conv_b":
            return jnp.zeros(shape, jnp.float32)
        if name == "A_log":
            return jnp.log(jax.random.uniform(k, shape, minval=1.0,
                                              maxval=16.0))
        if name == "dt_bias":
            dt = jnp.exp(jax.random.uniform(
                k, shape, minval=math.log(1e-3), maxval=math.log(1e-1)))
            return dt + jnp.log(-jnp.expm1(-dt))
        if name == "D":
            return jnp.ones(shape, jnp.float32)
        fan_in, fan_out = _fan(shape, name)
        w = jax.random.normal(k, (fan_in, fan_out)) / math.sqrt(fan_in)
        return w.reshape(shape)

    return jax.tree_util.tree_map_with_path(draw, shapes)
