"""Top-level models: decoder-only LM (dense/MoE/SSM/hybrid/VLM) and the
encoder-decoder (audio) variant.  Pure init/apply functions over param dicts.

Public API (used by core/, launch/, tests/):
  init_params(cfg, key)                      -> params
  forward(params, cfg, batch)                -> (logits, aux_loss)
  loss_fn(params, cfg, batch)                -> (loss, metrics)
  init_cache(cfg, batch, max_len)            -> cache
  prefill(params, cfg, batch)                -> (logits_last, cache)
  decode_step(params, cfg, token, cache, pos)-> (logits, new_cache)

``batch`` is a dict: {"tokens", "labels"(train), "patch_embeds"(vlm),
"frame_embeds"(audio), "dec_tokens"(audio)}.
"""
from __future__ import annotations

from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.configs.base import ENCDEC, ModelConfig, VLM
from repro.models import attention as attn_mod
from repro.models import blocks as blk
from repro.models.layers import (cross_entropy, dense_init, embed_init,
                                 embed_lookup, rmsnorm, rmsnorm_init,
                                 scale_logits, unembed)

Params = Dict[str, Any]


# ---------------------------------------------------------------------------
# Init
# ---------------------------------------------------------------------------
def init_params(cfg: ModelConfig, key, *, dtype=None) -> Params:
    dtype = dtype or jnp.float32
    if cfg.family == ENCDEC:
        return _init_encdec(cfg, key, dtype)
    ks = jax.random.split(key, 4)
    p: Params = {
        "embed": embed_init(ks[0], cfg.vocab_size, cfg.d_model, dtype=dtype),
        "stack": blk.stack_init(ks[1], cfg, dtype=dtype),
        "final_norm": rmsnorm_init(cfg.d_model),
    }
    if not cfg.tie_embeddings:
        p["lm_head"] = embed_init(ks[2], cfg.vocab_size, cfg.d_model,
                                  dtype=dtype)
    if cfg.family == VLM:
        p["vis_proj"] = {
            "w": dense_init(ks[3], cfg.vision_embed_dim, cfg.d_model,
                            dtype=dtype),
            "b": jnp.zeros((cfg.d_model,), dtype),
        }
    return p


def _init_encdec(cfg: ModelConfig, key, dtype) -> Params:
    import dataclasses
    ks = jax.random.split(key, 6)
    # encoder: full-attention blocks over frame embeddings (no vocab embed)
    enc_cfg = dataclasses.replace(
        cfg, num_layers=cfg.enc_layers, moe=None, ssm=None,
        attention=dataclasses.replace(cfg.attention, pattern=(),
                                      sliding_window=0))
    p: Params = {
        "embed": embed_init(ks[0], cfg.vocab_size, cfg.d_model, dtype=dtype),
        "enc_stack": blk.stack_init(ks[1], enc_cfg, dtype=dtype),
        "enc_norm": rmsnorm_init(cfg.d_model),
        "stack": blk.stack_init(ks[2], cfg, dtype=dtype),
        "final_norm": rmsnorm_init(cfg.d_model),
        # one cross-attention per decoder layer, stacked for scan
        "cross": _cross_init(ks[3], cfg, dtype),
    }
    if not cfg.tie_embeddings:
        p["lm_head"] = embed_init(ks[4], cfg.vocab_size, cfg.d_model,
                                  dtype=dtype)
    return p


def _cross_init(key, cfg: ModelConfig, dtype) -> Params:
    ks = jax.random.split(key, cfg.num_layers)
    ps = [{"norm": rmsnorm_init(cfg.d_model),
           "attn": attn_mod.attention_init(k, cfg, dtype=dtype)}
          for k in ks]
    if cfg.scan_layers and cfg.num_layers > 1:
        return {"stacked": jax.tree.map(lambda *xs: jnp.stack(xs), *ps)}
    return {"list": ps}


def enc_config(cfg: ModelConfig) -> ModelConfig:
    import dataclasses
    return dataclasses.replace(
        cfg, num_layers=cfg.enc_layers, moe=None, ssm=None,
        attention=dataclasses.replace(cfg.attention, pattern=(),
                                      sliding_window=0))


# ---------------------------------------------------------------------------
# Input embedding (modality fusion)
# ---------------------------------------------------------------------------
def _embed_inputs(params: Params, cfg: ModelConfig, batch: Dict[str, Any]
                  ) -> jnp.ndarray:
    tokens = batch["tokens"]
    x = embed_lookup(params["embed"], tokens,
                     multiplier=cfg.embedding_multiplier)
    if cfg.family == VLM and "patch_embeds" in batch:
        pe = batch["patch_embeds"]  # (B, P, vision_embed_dim)
        proj = pe.astype(x.dtype) @ params["vis_proj"]["w"] \
            + params["vis_proj"]["b"]
        x = jnp.concatenate([proj, x], axis=1)
    return x


def _lm_head_table(params: Params, cfg: ModelConfig) -> jnp.ndarray:
    return params["embed"] if cfg.tie_embeddings else params["lm_head"]


# ---------------------------------------------------------------------------
# Forward (train) — decoder-only families
# ---------------------------------------------------------------------------
def forward(params: Params, cfg: ModelConfig, batch: Dict[str, Any],
            *, attn_impl: str = "auto") -> Tuple[jnp.ndarray, jnp.ndarray]:
    if cfg.family == ENCDEC:
        return _forward_encdec(params, cfg, batch, attn_impl=attn_impl)
    x = _embed_inputs(params, cfg, batch)
    x, aux = blk.stack_forward(params["stack"], x, cfg, attn_impl=attn_impl)
    x = rmsnorm(params["final_norm"], x, cfg.norm_eps)
    logits = unembed(x, _lm_head_table(params, cfg), cfg.final_logit_softcap,
                     cfg.logits_scaling)
    return logits, aux


def _encode(params: Params, cfg: ModelConfig, frames: jnp.ndarray,
            attn_impl: str = "auto") -> jnp.ndarray:
    ecfg = enc_config(cfg)
    h, _ = blk.stack_forward(params["enc_stack"], frames, ecfg,
                             attn_impl=attn_impl)
    return rmsnorm(params["enc_norm"], h, cfg.norm_eps)


def _apply_cross(cross: Params, x: jnp.ndarray, enc_out: jnp.ndarray,
                 cfg: ModelConfig) -> jnp.ndarray:
    """Apply all cross-attention layers *after* self-attention stack.

    Architectural simplification (recorded in DESIGN.md): instead of
    interleaving cross-attention inside each decoder block, we apply the
    per-layer cross-attentions as a post-stack scan.  Parameter count and
    collective pattern match the interleaved form; this keeps the decoder
    stack reusable across families.
    """
    from repro.models.attention import cross_attention_forward

    def body(h, p):
        hn = rmsnorm(p["norm"], h, cfg.norm_eps)
        return h + cross_attention_forward(p["attn"], hn, enc_out, cfg), None

    if "stacked" in cross:
        b = jax.checkpoint(body, prevent_cse=False) if cfg.remat else body
        x, _ = jax.lax.scan(b, x, cross["stacked"])
        return x
    for p in cross["list"]:
        x, _ = body(x, p)
    return x


def _forward_encdec(params: Params, cfg: ModelConfig, batch: Dict[str, Any],
                    attn_impl: str = "auto"):
    enc_out = _encode(params, cfg, batch["frame_embeds"].astype(
        params["embed"].dtype), attn_impl)
    x = embed_lookup(params["embed"], batch["tokens"],
                     multiplier=cfg.embedding_multiplier)
    x, aux = blk.stack_forward(params["stack"], x, cfg, attn_impl=attn_impl)
    x = _apply_cross(params["cross"], x, enc_out, cfg)
    x = rmsnorm(params["final_norm"], x, cfg.norm_eps)
    logits = unembed(x, _lm_head_table(params, cfg), cfg.final_logit_softcap,
                     cfg.logits_scaling)
    return logits, aux


# ---------------------------------------------------------------------------
# Training loss
# ---------------------------------------------------------------------------
def hidden_forward(params: Params, cfg: ModelConfig, batch: Dict[str, Any],
                   *, attn_impl: str = "auto"
                   ) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """Forward up to the final norm (no unembed). Returns (x, aux)."""
    if cfg.family == ENCDEC:
        enc_out = _encode(params, cfg, batch["frame_embeds"].astype(
            params["embed"].dtype), attn_impl)
        x = embed_lookup(params["embed"], batch["tokens"],
                         multiplier=cfg.embedding_multiplier)
        x, aux = blk.stack_forward(params["stack"], x, cfg,
                                   attn_impl=attn_impl)
        x = _apply_cross(params["cross"], x, enc_out, cfg)
    else:
        x = _embed_inputs(params, cfg, batch)
        x, aux = blk.stack_forward(params["stack"], x, cfg,
                                   attn_impl=attn_impl)
    return rmsnorm(params["final_norm"], x, cfg.norm_eps), aux


def chunked_cross_entropy(x: jnp.ndarray, table: jnp.ndarray,
                          labels: jnp.ndarray, *, final_softcap: float = 0.0,
                          logits_scaling: float = 1.0, chunk: int = 128,
                          row_weights: Optional[jnp.ndarray] = None
                          ) -> jnp.ndarray:
    """CE over vocab-sharded logits without materializing (B,S,V).

    Scans over sequence chunks; within a chunk the label logit is computed
    with a one-hot contraction (GSPMD-friendly on a vocab-sharded table).

    ``row_weights`` (B,): when given, returns Σ_r w_r · Σ_t nll_rt (the
    caller pre-scales — used by the fused federated step where w_r encodes
    the CSMAAFL client coefficient / tokens-per-client); when None, returns
    the plain mean over valid tokens.
    """
    B, S, d = x.shape
    V = table.shape[0]
    nc = -(-S // chunk)
    pad = nc * chunk - S
    if pad:
        x = jnp.pad(x, ((0, 0), (0, pad), (0, 0)))
        labels = jnp.pad(labels, ((0, 0), (0, pad)), constant_values=-1)
    xs = x.reshape(B, nc, chunk, d).transpose(1, 0, 2, 3)
    ls = labels.reshape(B, nc, chunk).transpose(1, 0, 2)

    def body(carry, inp):
        xc, lc = inp                                   # (B,c,d), (B,c)
        logits = jnp.einsum("bcd,vd->bcv", xc, table).astype(jnp.float32)
        logits = scale_logits(logits, final_softcap, logits_scaling)
        lse = jax.nn.logsumexp(logits, axis=-1)        # (B,c)
        oh = jax.nn.one_hot(lc, V, dtype=jnp.float32)  # (B,c,V)
        ll = jnp.sum(logits * oh, axis=-1)
        valid = (lc >= 0).astype(jnp.float32)
        nll = (lse - ll) * valid                       # (B,c)
        if row_weights is not None:
            nll = nll * row_weights[:, None].astype(jnp.float32)
        return (carry[0] + jnp.sum(nll), carry[1] + jnp.sum(valid)), None

    # checkpoint: recompute each chunk's logits in backward rather than
    # keeping (B,c,V) per chunk alive
    (tot, cnt), _ = jax.lax.scan(jax.checkpoint(body, prevent_cse=False),
                                 (jnp.float32(0), jnp.float32(0)), (xs, ls))
    if row_weights is not None:
        return tot
    return tot / jnp.maximum(cnt, 1.0)


def loss_fn(params: Params, cfg: ModelConfig, batch: Dict[str, Any],
            *, attn_impl: str = "auto", chunked: bool = None):
    """``batch["row_weights"]`` (B,), when present, switches to weighted-sum
    semantics (federated fused step): loss = Σ_r w_r Σ_t nll_rt + aux."""
    labels = batch["labels"]
    row_weights = batch.get("row_weights")
    if chunked is None:
        # chunk whenever the full (B,S,V) logits would be large
        B, S = labels.shape[0], labels.shape[1]
        chunked = B * S * cfg.vocab_size > (1 << 28)
    if chunked or row_weights is not None:
        x, aux = hidden_forward(params, cfg, batch, attn_impl=attn_impl)
        if cfg.family == VLM and "patch_embeds" in batch:
            P = batch["patch_embeds"].shape[1]
            x = x[:, P:, :]
        loss = chunked_cross_entropy(x, _lm_head_table(params, cfg), labels,
                                     final_softcap=cfg.final_logit_softcap,
                                     logits_scaling=cfg.logits_scaling,
                                     row_weights=row_weights)
    else:
        logits, aux = forward(params, cfg, batch, attn_impl=attn_impl)
        if cfg.family == VLM and "patch_embeds" in batch:
            P = batch["patch_embeds"].shape[1]
            logits = logits[:, P:, :]
        mask = batch.get("loss_mask")
        loss = cross_entropy(logits, labels, mask)
    total = loss + aux
    return total, {"ce": loss, "aux": aux}


# ---------------------------------------------------------------------------
# Serving: prefill + decode
# ---------------------------------------------------------------------------
def init_cache(cfg: ModelConfig, batch: int, max_len: int,
               dtype=jnp.bfloat16) -> Params:
    cache = blk.stack_init_cache(cfg, batch, max_len, dtype)
    if cfg.family == ENCDEC:
        enc_len = max_len // cfg.enc_seq_divisor
        return {"dec": cache,
                "enc_out": jnp.zeros((batch, enc_len, cfg.d_model), dtype)}
    return {"dec": cache}


def prefill(params: Params, cfg: ModelConfig, batch: Dict[str, Any],
            cache: Optional[Params] = None, *, attn_impl: str = "auto"):
    """Run the full prompt, fill caches, return (last_logits, cache)."""
    x = _embed_inputs(params, cfg, batch)
    B, S = x.shape[0], x.shape[1]
    if cache is None:
        cache = init_cache(cfg, B, S)
    if cfg.family == ENCDEC:
        enc_out = _encode(params, cfg, batch["frame_embeds"].astype(
            params["embed"].dtype), attn_impl)
        h, dec_cache = blk.stack_prefill(params["stack"], x, cfg,
                                         cache["dec"], attn_impl=attn_impl)
        h = _apply_cross(params["cross"], h, enc_out, cfg)
        new_cache = {"dec": dec_cache,
                     "enc_out": enc_out.astype(cache["enc_out"].dtype)}
    else:
        h, dec_cache = blk.stack_prefill(params["stack"], x, cfg,
                                         cache["dec"], attn_impl=attn_impl)
        new_cache = {"dec": dec_cache}
    h = rmsnorm(params["final_norm"], h[:, -1:, :], cfg.norm_eps)
    logits = unembed(h, _lm_head_table(params, cfg), cfg.final_logit_softcap,
                     cfg.logits_scaling)
    return logits, new_cache


def decode_step(params: Params, cfg: ModelConfig, token: jnp.ndarray,
                cache: Params, pos: jnp.ndarray):
    """token (B, 1) int32; pos scalar int32 (absolute position of `token`).
    Returns (logits (B,1,V), new_cache)."""
    x = embed_lookup(params["embed"], token,
                     multiplier=cfg.embedding_multiplier)
    h, new_dec = blk.stack_decode(params["stack"], x, cache["dec"], cfg,
                                  pos=pos)
    new_cache = {"dec": new_dec}
    if cfg.family == ENCDEC:
        h = _apply_cross(params["cross"], h, cache["enc_out"], cfg)
        new_cache["enc_out"] = cache["enc_out"]
    h = rmsnorm(params["final_norm"], h, cfg.norm_eps)
    logits = unembed(h, _lm_head_table(params, cfg), cfg.final_logit_softcap,
                     cfg.logits_scaling)
    return logits, new_cache


def num_params(params: Params) -> int:
    return sum(x.size for x in jax.tree.leaves(params))
