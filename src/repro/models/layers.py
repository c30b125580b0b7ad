"""Primitive neural-net layers as pure functions over explicit param pytrees.

No flax/haiku: params are nested dicts of jnp arrays, init fns take a PRNG
key, apply fns are pure.  This keeps the federated core (which manipulates
whole parameter pytrees as the unit of aggregation) trivially composable.
"""
from __future__ import annotations

import math
from typing import Any, Dict, Optional

import jax
import jax.numpy as jnp

from repro.models.lora import Adapted

Params = Dict[str, Any]


def dense(w, x: jnp.ndarray, eq: Optional[str] = None) -> jnp.ndarray:
    """``x @ w``, or ``einsum(eq, x, w)`` where ``w`` has more than two
    axes; a LoRA-adapted weight (``lora.Adapted``) adds its low-rank
    term, unmerged."""
    if isinstance(w, Adapted):
        return w.add_low_rank(x, dense(w.w, x, eq))
    return jnp.einsum(eq, x, w) if eq else x @ w


# ---------------------------------------------------------------------------
# Init helpers
# ---------------------------------------------------------------------------
def dense_init(key, d_in: int, d_out: int, *, dtype=jnp.float32,
               scale: Optional[float] = None) -> jnp.ndarray:
    """Truncated-normal fan-in init (matches common LLM inits)."""
    if scale is None:
        scale = 1.0 / math.sqrt(d_in)
    return (jax.random.truncated_normal(key, -2.0, 2.0, (d_in, d_out)) *
            scale).astype(dtype)


def embed_init(key, vocab: int, d: int, *, dtype=jnp.float32) -> jnp.ndarray:
    return (jax.random.normal(key, (vocab, d)) * 0.02).astype(dtype)


# ---------------------------------------------------------------------------
# Norms
# ---------------------------------------------------------------------------
def rmsnorm_init(d: int) -> Params:
    return {"scale": jnp.zeros((d,), jnp.float32)}


def rmsnorm(params: Params, x: jnp.ndarray, eps: float = 1e-6) -> jnp.ndarray:
    """RMSNorm with (1 + scale) parameterization (gemma/llama style)."""
    dt = x.dtype
    x = x.astype(jnp.float32)
    var = jnp.mean(jnp.square(x), axis=-1, keepdims=True)
    x = x * jax.lax.rsqrt(var + eps)
    return (x * (1.0 + params["scale"])).astype(dt)


# ---------------------------------------------------------------------------
# Softcap (gemma2)
# ---------------------------------------------------------------------------
def softcap(x: jnp.ndarray, cap: float) -> jnp.ndarray:
    if cap <= 0.0:
        return x
    return cap * jnp.tanh(x / cap)


# ---------------------------------------------------------------------------
# RoPE
# ---------------------------------------------------------------------------
def rope_frequencies(head_dim: int, theta: float) -> jnp.ndarray:
    return 1.0 / (theta ** (jnp.arange(0, head_dim, 2, dtype=jnp.float32)
                            / head_dim))


def apply_rope(x: jnp.ndarray, positions: jnp.ndarray,
               theta: float) -> jnp.ndarray:
    """x: (..., S, H, D); positions: broadcastable to (..., S)."""
    freqs = rope_frequencies(x.shape[-1], theta)           # (D/2,)
    angles = positions[..., None].astype(jnp.float32) * freqs  # (..., S, D/2)
    angles = angles[..., None, :]                          # (..., S, 1, D/2)
    sin, cos = jnp.sin(angles), jnp.cos(angles)
    x1, x2 = jnp.split(x.astype(jnp.float32), 2, axis=-1)
    out = jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)
    return out.astype(x.dtype)


# ---------------------------------------------------------------------------
# MLP (gated SwiGLU / GeGLU, or plain GELU)
# ---------------------------------------------------------------------------
_ACTIVATIONS = {"gelu": jax.nn.gelu, "silu": jax.nn.silu}


def mlp_init(key, d_model: int, d_ff: int, *, gated: bool = True,
             packed: bool = False, dtype=jnp.float32) -> Params:
    """``packed``: the gate and up projections as one (d, 2·d_ff)
    ``w_in``, gate first."""
    ks = jax.random.split(key, 3)
    if packed:
        return {"w_in": dense_init(ks[0], d_model, 2 * d_ff, dtype=dtype),
                "w_out": dense_init(ks[2], d_ff, d_model, dtype=dtype)}
    p = {"w_in": dense_init(ks[0], d_model, d_ff, dtype=dtype),
         "w_out": dense_init(ks[2], d_ff, d_model, dtype=dtype)}
    if gated:
        p["w_gate"] = dense_init(ks[1], d_model, d_ff, dtype=dtype)
    return p


def mlp(params: Params, x: jnp.ndarray, *, gated: bool = True,
        act: str = "gelu") -> jnp.ndarray:
    """A packed ``w_in`` (no ``w_gate``) of a gated MLP holds the gate's
    columns first, then the up projection's."""
    f = _ACTIVATIONS[act]
    h = dense(params["w_in"], x)
    if gated and "w_gate" not in params:
        gate, h = jnp.split(h, 2, axis=-1)
        h = f(gate) * h
    elif gated:
        h = f(dense(params["w_gate"], x)) * h
    else:
        h = f(h)
    return dense(params["w_out"], h)


# ---------------------------------------------------------------------------
# Embedding / unembedding
# ---------------------------------------------------------------------------
def embed_lookup(embedding: jnp.ndarray, ids: jnp.ndarray,
                 *, multiplier: float = 1.0) -> jnp.ndarray:
    x = jnp.take(embedding, ids, axis=0)
    if multiplier != 1.0:
        x = x * jnp.asarray(multiplier, x.dtype)
    return x


def scale_logits(logits: jnp.ndarray, final_softcap: float = 0.0,
                 logits_scaling: float = 1.0) -> jnp.ndarray:
    """Logits divided by ``logits_scaling``, then soft-capped."""
    if logits_scaling != 1.0:
        logits = logits / jnp.asarray(logits_scaling, logits.dtype)
    return softcap(logits, final_softcap)


def unembed(x: jnp.ndarray, table: jnp.ndarray,
            final_softcap: float = 0.0,
            logits_scaling: float = 1.0) -> jnp.ndarray:
    """table is always (vocab, d_model)."""
    logits = jnp.einsum("...d,vd->...v", x, table)
    return scale_logits(logits, final_softcap, logits_scaling)


# ---------------------------------------------------------------------------
# Cross entropy (stable, fp32 accumulation)
# ---------------------------------------------------------------------------
def cross_entropy(logits: jnp.ndarray, labels: jnp.ndarray,
                  mask: Optional[jnp.ndarray] = None) -> jnp.ndarray:
    logits = logits.astype(jnp.float32)
    lse = jax.nn.logsumexp(logits, axis=-1)
    ll = jnp.take_along_axis(logits, labels[..., None], axis=-1)[..., 0]
    nll = lse - ll
    if mask is not None:
        mask = mask.astype(jnp.float32)
        return jnp.sum(nll * mask) / jnp.maximum(jnp.sum(mask), 1.0)
    return jnp.mean(nll)


def accuracy(logits: jnp.ndarray, labels: jnp.ndarray) -> jnp.ndarray:
    return jnp.mean((jnp.argmax(logits, axis=-1) == labels)
                    .astype(jnp.float32))
