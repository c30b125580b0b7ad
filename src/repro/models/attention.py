"""Attention: GQA / MHA, full and sliding-window, train/prefill/decode.

Three execution paths, all numerically interchangeable (tested):

* ``naive_attention``   — plain einsum softmax; the oracle. O(S^2) memory.
* ``blockwise_attention`` — online-softmax over KV blocks via ``lax.scan``;
  O(S * block) memory.  Default for prefill/training at long S (this is the
  pure-JAX flash algorithm; the Pallas kernel in ``repro.kernels`` is the
  TPU-tiled version of the same math).
* ``decode_attention``  — one query position against a KV cache (full or
  ring-buffered sliding window).  O(S) per token; with the cache sequence
  dim sharded, GSPMD turns the softmax into partial-softmax + all-reduce
  (flash-decode).

KV caches:
* full layers   : (B, S_max, Hkv, D) with a scalar ``pos`` cursor.
* window layers : ring buffer (B, W, Hkv, D); slot = pos mod W.
"""
from __future__ import annotations

import math
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig
from repro.models.layers import apply_rope, dense, dense_init, softcap

Params = Dict[str, Any]
NEG_INF = -2.0e38


# ---------------------------------------------------------------------------
# Params
# ---------------------------------------------------------------------------
def attention_init(key, cfg: ModelConfig, *, dtype=jnp.float32) -> Params:
    d, h, hkv = cfg.d_model, cfg.num_heads, cfg.num_kv_heads
    hd = cfg.resolved_head_dim
    ks = jax.random.split(key, 4)
    p = {
        "wq": dense_init(ks[0], d, h * hd, dtype=dtype).reshape(d, h, hd),
        "wk": dense_init(ks[1], d, hkv * hd, dtype=dtype).reshape(d, hkv, hd),
        "wv": dense_init(ks[2], d, hkv * hd, dtype=dtype).reshape(d, hkv, hd),
        "wo": dense_init(ks[3], h * hd, d, dtype=dtype).reshape(h, hd, d),
    }
    if cfg.attention.qkv_bias:
        p["bq"] = jnp.zeros((h, hd), dtype)
        p["bk"] = jnp.zeros((hkv, hd), dtype)
        p["bv"] = jnp.zeros((hkv, hd), dtype)
    return p


def _project_qkv(params: Params, x: jnp.ndarray, cfg: ModelConfig,
                 positions: jnp.ndarray
                 ) -> Tuple[jnp.ndarray, jnp.ndarray, jnp.ndarray]:
    """x: (B, S, d) -> q (B,S,H,D), k/v (B,S,Hkv,D), RoPE applied
    unless the model has no position embedding ("nope")."""
    q = dense(params["wq"], x, "bsd,dhk->bshk")
    k = dense(params["wk"], x, "bsd,dhk->bshk")
    v = dense(params["wv"], x, "bsd,dhk->bshk")
    if "bq" in params:
        q = q + params["bq"]
        k = k + params["bk"]
        v = v + params["bv"]
    if cfg.attention.position_embedding == "nope":
        return q, k, v
    theta = cfg.attention.rope_theta
    q = apply_rope(q, positions, theta)
    k = apply_rope(k, positions, theta)
    return q, k, v


def _q_scale(cfg: ModelConfig) -> float:
    s = cfg.attention.query_pre_attn_scalar
    return 1.0 / math.sqrt(s if s > 0 else cfg.resolved_head_dim)


def _repeat_kv(k: jnp.ndarray, num_heads: int) -> jnp.ndarray:
    """(B,S,Hkv,D) -> (B,S,H,D) by repeating each kv head H/Hkv times."""
    hkv = k.shape[-2]
    if hkv == num_heads:
        return k
    return jnp.repeat(k, num_heads // hkv, axis=-2)


# ---------------------------------------------------------------------------
# Masks
# ---------------------------------------------------------------------------
def causal_window_mask(q_pos: jnp.ndarray, k_pos: jnp.ndarray,
                       window: int) -> jnp.ndarray:
    """bool (…, Sq, Sk): True = attend. window<=0 means full causal."""
    diff = q_pos[..., :, None] - k_pos[..., None, :]
    mask = diff >= 0
    if window > 0:
        mask &= diff < window
    return mask


# ---------------------------------------------------------------------------
# Naive oracle
# ---------------------------------------------------------------------------
def naive_attention(q: jnp.ndarray, k: jnp.ndarray, v: jnp.ndarray,
                    mask: jnp.ndarray, *, scale: float,
                    logit_cap: float = 0.0) -> jnp.ndarray:
    """q (B,Sq,H,D), k/v (B,Sk,H,D), mask (B?,Sq,Sk) or (Sq,Sk)."""
    logits = jnp.einsum("bqhd,bkhd->bhqk", q.astype(jnp.float32),
                        k.astype(jnp.float32)) * scale
    logits = softcap(logits, logit_cap)
    if mask.ndim == 2:
        mask = mask[None, None]
    elif mask.ndim == 3:
        mask = mask[:, None]
    logits = jnp.where(mask, logits, NEG_INF)
    w = jax.nn.softmax(logits, axis=-1)
    out = jnp.einsum("bhqk,bkhd->bqhd", w, v.astype(jnp.float32))
    return out.astype(q.dtype)


# ---------------------------------------------------------------------------
# Blockwise (online softmax) — the memory-efficient pure-JAX path
# ---------------------------------------------------------------------------
def blockwise_attention(q: jnp.ndarray, k: jnp.ndarray, v: jnp.ndarray,
                        *, q_positions: jnp.ndarray,
                        k_positions: jnp.ndarray,
                        window: int, scale: float,
                        logit_cap: float = 0.0,
                        kv_block: int = 512,
                        q_block: int = 0) -> jnp.ndarray:
    """Causal (optionally windowed) attention with O(q_block * kv_block)
    live logits.  q (B,Sq,H,D); k/v (B,Sk,H,D) with H == q heads
    (pre-repeated).

    Scans KV blocks carrying (m, l, acc) online-softmax state; when
    ``q_block`` > 0 an outer scan over query blocks bounds the live buffer
    to (B,H,q_block,kv_block) — required at 32k+ sequence lengths for
    architectures whose head count does not shard evenly.
    """
    if q_block and q.shape[1] > q_block:
        Sq = q.shape[1]
        nqb = -(-Sq // q_block)
        padq = nqb * q_block - Sq
        qp = jnp.pad(q, ((0, 0), (0, padq), (0, 0), (0, 0)))
        pp = jnp.pad(q_positions, (0, padq), constant_values=-1)
        qb = qp.reshape(q.shape[0], nqb, q_block, *q.shape[2:])
        pb = pp.reshape(nqb, q_block)

        def one(idx):
            return blockwise_attention(
                qb[:, idx], k, v, q_positions=pb[idx],
                k_positions=k_positions, window=window, scale=scale,
                logit_cap=logit_cap, kv_block=kv_block, q_block=0)

        out = jax.lax.map(one, jnp.arange(nqb))          # (nqb,B,qb,H,D)
        out = out.transpose(1, 0, 2, 3, 4).reshape(
            q.shape[0], nqb * q_block, *q.shape[2:])
        return out[:, :Sq]
    B, Sq, H, D = q.shape
    G = k.shape[2]                   # kv heads; H % G == 0 (GQA grouped)
    rep = H // G
    Sk = k.shape[1]
    nb = -(-Sk // kv_block)
    pad = nb * kv_block - Sk
    if pad:
        k = jnp.pad(k, ((0, 0), (0, pad), (0, 0), (0, 0)))
        v = jnp.pad(v, ((0, 0), (0, pad), (0, 0), (0, 0)))
        k_positions = jnp.pad(k_positions, (0, pad), constant_values=-1)
    kb = k.reshape(B, nb, kv_block, G, D).transpose(1, 0, 2, 3, 4)
    vb = v.reshape(B, nb, kv_block, G, D).transpose(1, 0, 2, 3, 4)
    pb = k_positions.reshape(nb, kv_block)

    # grouped-GQA layout: q (B,Sq,G,rep,D) — K/V are NEVER head-repeated
    # (the materialized repeat costs an extra (B,Sk,H,D) buffer and, when
    # kv-head sharding differs from q-head sharding, a per-layer
    # all-gather; the kernel's index_map does the same folding on TPU)
    qg = q.reshape(B, Sq, G, rep, D)
    # keep q/k/v in their storage dtype and accumulate in f32 via
    # preferred_element_type — MXU semantics, and it stops XLA from
    # materializing whole-stack f32 copies of K/V outside the scan
    qs = (qg * jnp.asarray(scale, q.dtype)) if q.dtype == jnp.float32 \
        else (qg.astype(jnp.float32) * scale).astype(q.dtype)

    def body(carry, blk):
        m, l, acc = carry                                    # (B,G,rep,Sq…)
        kblk, vblk, posblk = blk
        logits = jnp.einsum("bqgrd,bkgd->bgrqk", qs, kblk,
                            preferred_element_type=jnp.float32)
        logits = softcap(logits, logit_cap)
        valid = (posblk >= 0)[None, :]                       # (1, kb)
        msk = causal_window_mask(q_positions, posblk, window)  # (Sq, kb)
        msk = msk & valid
        logits = jnp.where(msk[None, None, None], logits, NEG_INF)
        m_blk = jnp.max(logits, axis=-1)                     # (B,G,rep,Sq)
        m_new = jnp.maximum(m, m_blk)
        # guard fully-masked rows: keep m_new finite
        m_safe = jnp.where(m_new == NEG_INF, 0.0, m_new)
        p = jnp.exp(logits - m_safe[..., None])
        p = jnp.where(msk[None, None, None], p, 0.0)
        corr = jnp.exp(jnp.where(m == NEG_INF, NEG_INF, m - m_safe))
        corr = jnp.where(m == NEG_INF, 0.0, corr)
        l_new = l * corr + jnp.sum(p, axis=-1)
        acc_new = acc * corr[..., None] + jnp.einsum(
            "bgrqk,bkgd->bgrqd", p.astype(vblk.dtype), vblk,
            preferred_element_type=jnp.float32)
        return (m_new, l_new, acc_new), None

    m0 = jnp.full((B, G, rep, Sq), NEG_INF, jnp.float32)
    l0 = jnp.zeros((B, G, rep, Sq), jnp.float32)
    a0 = jnp.zeros((B, G, rep, Sq, D), jnp.float32)
    # checkpoint the block body: backward recomputes each block's logits
    # instead of saving the (B,H,Sq,bk) residuals for every block (which
    # would reconstitute the full S^2 attention matrix in HBM)
    (m, l, acc), _ = jax.lax.scan(jax.checkpoint(body, prevent_cse=False),
                                  (m0, l0, a0), (kb, vb, pb))
    out = acc / jnp.maximum(l, 1e-30)[..., None]             # (B,G,rep,Sq,D)
    out = out.transpose(0, 3, 1, 2, 4).reshape(B, Sq, H, D)
    return out.astype(q.dtype)


# ---------------------------------------------------------------------------
# Full attention layer application (train / prefill)
# ---------------------------------------------------------------------------
def attention_forward(params: Params, x: jnp.ndarray, cfg: ModelConfig,
                      *, window: int, positions: Optional[jnp.ndarray] = None,
                      impl: str = "auto",
                      kv_cache_out: bool = False):
    """Self-attention over a full sequence.  Returns (out, (k, v) if
    kv_cache_out) — k/v returned *un-repeated* (Hkv heads) for caching."""
    B, S, _ = x.shape
    if positions is None:
        positions = jnp.arange(S)
    q, k, v = _project_qkv(params, x, cfg, positions)
    scale = _q_scale(cfg)
    cap = cfg.attention.attn_logit_softcap
    from repro.sharding.context import get_attn_sp_specs
    sp = get_attn_sp_specs()
    if sp is not None:
        q_spec, kv_spec = sp
        q = jax.lax.with_sharding_constraint(q, q_spec)
        k = jax.lax.with_sharding_constraint(k, kv_spec)
        v = jax.lax.with_sharding_constraint(v, kv_spec)
    if impl == "auto":
        impl = "blockwise" if S > 2048 else "naive"
    if impl == "naive":
        kr = _repeat_kv(k, cfg.num_heads)
        vr = _repeat_kv(v, cfg.num_heads)
        mask = causal_window_mask(positions, positions, window)
        ctx = naive_attention(q, kr, vr, mask, scale=scale, logit_cap=cap)
    elif impl == "blockwise":
        # with sequence-parallel attention the per-device q rows are S/m,
        # so the live logits tile is already bounded — skip q-blocking
        # (its gather on the sharded dim would force resharding).
        # K/V stay at Hkv heads: grouped-GQA einsums fold the repeat.
        qb = 0 if sp is not None else (2048 if S > 8192 else 0)
        ctx = blockwise_attention(
            q, k, v, q_positions=positions, k_positions=positions,
            window=window, scale=scale, logit_cap=cap, q_block=qb)
    elif impl == "pallas":
        from repro.kernels import pallas_interpret
        from repro.kernels.flash_attention import ops as fa_ops
        ctx = fa_ops.flash_attention(
            q, k, v, causal=True, window=window, scale=scale,
            logit_cap=cap, interpret=pallas_interpret())
    else:
        raise ValueError(f"unknown attention impl {impl}")
    out = dense(params["wo"], ctx, "bshk,hkd->bsd")
    if kv_cache_out:
        return out, (k, v)
    return out


# ---------------------------------------------------------------------------
# KV cache (full + ring) and decode
# ---------------------------------------------------------------------------
def init_kv_cache(cfg: ModelConfig, batch: int, max_len: int, window: int,
                  dtype=jnp.bfloat16) -> Params:
    """window>0 => ring buffer of size min(window, max_len)."""
    L = min(window, max_len) if window > 0 else max_len
    hkv, hd = cfg.num_kv_heads, cfg.resolved_head_dim
    return {
        "k": jnp.zeros((batch, L, hkv, hd), dtype),
        "v": jnp.zeros((batch, L, hkv, hd), dtype),
        # absolute position stored in each slot; -1 = empty
        "slot_pos": jnp.full((L,), -1, jnp.int32),
    }


def fill_kv_cache(cache: Params, k: jnp.ndarray, v: jnp.ndarray,
                  start_pos: int = 0) -> Params:
    """Write a prefill's k/v (B,S,Hkv,D) into the cache (ring-aware)."""
    L = cache["k"].shape[1]
    S = k.shape[1]
    pos = start_pos + jnp.arange(S)
    if S >= L:
        # keep the last L entries, rotated so slot = pos mod L
        k_tail, v_tail, p_tail = k[:, -L:], v[:, -L:], pos[-L:]
        slots = p_tail % L
        order = jnp.argsort(slots)
        return {"k": k_tail[:, order].astype(cache["k"].dtype),
                "v": v_tail[:, order].astype(cache["v"].dtype),
                "slot_pos": p_tail[order].astype(jnp.int32)}
    slots = pos % L
    ck = cache["k"].at[:, slots].set(k.astype(cache["k"].dtype))
    cv = cache["v"].at[:, slots].set(v.astype(cache["v"].dtype))
    sp = cache["slot_pos"].at[slots].set(pos.astype(jnp.int32))
    return {"k": ck, "v": cv, "slot_pos": sp}


def decode_attention(params: Params, x: jnp.ndarray, cache: Params,
                     cfg: ModelConfig, *, pos: jnp.ndarray, window: int
                     ) -> Tuple[jnp.ndarray, Params]:
    """One-token decode.  x: (B, 1, d); pos: scalar int32 (current absolute
    position).  Returns (out (B,1,d), new_cache)."""
    positions = jnp.full((1,), pos, jnp.int32)
    q, k_new, v_new = _project_qkv(params, x, cfg, positions)
    L = cache["k"].shape[1]
    slot = pos % L
    ck = jax.lax.dynamic_update_slice_in_dim(
        cache["k"], k_new.astype(cache["k"].dtype), slot, axis=1)
    cv = jax.lax.dynamic_update_slice_in_dim(
        cache["v"], v_new.astype(cache["v"].dtype), slot, axis=1)
    sp = jax.lax.dynamic_update_slice_in_dim(
        cache["slot_pos"], positions, slot, axis=0)
    new_cache = {"k": ck, "v": cv, "slot_pos": sp}

    kr = _repeat_kv(ck, cfg.num_heads)          # (B, L, H, D)
    vr = _repeat_kv(cv, cfg.num_heads)
    scale = _q_scale(cfg)
    cap = cfg.attention.attn_logit_softcap
    logits = jnp.einsum("bqhd,bkhd->bhqk", q.astype(jnp.float32) * scale,
                        kr.astype(jnp.float32))
    logits = softcap(logits, cap)
    kpos = sp                                    # (L,)
    valid = (kpos >= 0) & (kpos <= pos)
    if window > 0:
        valid &= (pos - kpos) < window
    logits = jnp.where(valid[None, None, None, :], logits, NEG_INF)
    w = jax.nn.softmax(logits, axis=-1)
    ctx = jnp.einsum("bhqk,bkhd->bqhd", w, vr.astype(jnp.float32))
    out = dense(params["wo"], ctx.astype(x.dtype), "bshk,hkd->bsd")
    return out, new_cache


# ---------------------------------------------------------------------------
# Cross-attention (encoder-decoder)
# ---------------------------------------------------------------------------
def cross_attention_forward(params: Params, x: jnp.ndarray,
                            enc_out: jnp.ndarray, cfg: ModelConfig
                            ) -> jnp.ndarray:
    """Decoder cross-attention: queries from x (B,Sq,d), keys/values from
    encoder output (B,Sk,d).  No RoPE, no causal mask."""
    q = jnp.einsum("bsd,dhk->bshk", x, params["wq"])
    k = jnp.einsum("bsd,dhk->bshk", enc_out, params["wk"])
    v = jnp.einsum("bsd,dhk->bshk", enc_out, params["wv"])
    kr = _repeat_kv(k, cfg.num_heads)
    vr = _repeat_kv(v, cfg.num_heads)
    scale = _q_scale(cfg)
    logits = jnp.einsum("bqhd,bkhd->bhqk", q.astype(jnp.float32) * scale,
                        kr.astype(jnp.float32))
    w = jax.nn.softmax(logits, axis=-1)
    ctx = jnp.einsum("bhqk,bkhd->bqhd", w, vr.astype(jnp.float32))
    return jnp.einsum("bshk,hkd->bsd", ctx.astype(x.dtype), params["wo"])
