"""Transformer/Mamba block construction and application.

A model is a sequence of *blocks* (kinds: attn_global / attn_local / mamba).
For compile efficiency the sequence is grouped into:

  * ``periods`` — ``n_full`` repetitions of ``cfg.block_pattern`` whose
    params are stacked along a leading axis and applied with ``lax.scan``;
  * ``rem``     — the (< period) leftover blocks, applied unrolled.

KV/SSM caches mirror this structure (stacked along the same leading axis),
so decode scans carry the cache through the same period body.
"""
from __future__ import annotations

import functools
from typing import Any, Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.configs.base import ATTN_LOCAL, MAMBA, ModelConfig
from repro.models import attention as attn
from repro.models import mamba2, moe
from repro.models.layers import dense, mlp, mlp_init, rmsnorm, rmsnorm_init

Params = Dict[str, Any]


# ---------------------------------------------------------------------------
# Per-block init
# ---------------------------------------------------------------------------
def block_init(key, kind: str, cfg: ModelConfig, *, dtype=jnp.float32
               ) -> Params:
    if kind == MAMBA:
        k1, k2 = jax.random.split(key)
        p = {"norm": rmsnorm_init(cfg.d_model),
             "mixer": mamba2.mamba2_init(k1, cfg, dtype=dtype)}
        if cfg.mamba_mlp:
            p["norm2"] = rmsnorm_init(cfg.d_model)
            p["mlp"] = _mlp_init(k2, cfg, dtype)
        return p
    k1, k2 = jax.random.split(key)
    p = {"norm1": rmsnorm_init(cfg.d_model),
         "attn": attn.attention_init(k1, cfg, dtype=dtype),
         "norm2": rmsnorm_init(cfg.d_model)}
    if cfg.use_post_norms:
        p["post_norm1"] = rmsnorm_init(cfg.d_model)
        p["post_norm2"] = rmsnorm_init(cfg.d_model)
    if cfg.moe is not None:
        p["moe"] = moe.moe_init(k2, cfg, dtype=dtype)
    else:
        p["mlp"] = _mlp_init(k2, cfg, dtype)
    return p


def _mlp_init(key, cfg: ModelConfig, dtype) -> Params:
    return mlp_init(key, cfg.d_model, cfg.d_ff, gated=cfg.mlp_gated,
                    packed=cfg.mlp_packed, dtype=dtype)


def _mlp(params: Params, h: jnp.ndarray, cfg: ModelConfig) -> jnp.ndarray:
    return mlp(params, h, gated=cfg.mlp_gated, act=cfg.mlp_act)


def _add(x: jnp.ndarray, h: jnp.ndarray, cfg: ModelConfig) -> jnp.ndarray:
    """The residual stream plus a sublayer's output, times the model's
    residual multiplier."""
    r = cfg.residual_multiplier
    return x + (h if r == 1.0 else h * jnp.asarray(r, h.dtype))


def _block_window(kind: str, cfg: ModelConfig) -> int:
    return cfg.attention.sliding_window if kind == ATTN_LOCAL else 0


# ---------------------------------------------------------------------------
# Per-block forward (full sequence)
# ---------------------------------------------------------------------------
def _constrain_block_input(h: jnp.ndarray) -> jnp.ndarray:
    """Pin the normed block input to the batch-sharded/S-replicated layout
    (see sharding/context.py) so GSPMD chooses Megatron TP for heads/ff."""
    from repro.sharding.context import get_block_spec
    spec = get_block_spec()
    if spec is not None:
        h = jax.lax.with_sharding_constraint(h, spec)
    return h


def block_forward(params: Params, x: jnp.ndarray, kind: str,
                  cfg: ModelConfig, *, positions: Optional[jnp.ndarray] = None,
                  attn_impl: str = "auto"
                  ) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """Returns (y, aux_loss)."""
    aux = jnp.zeros((), jnp.float32)
    if kind == MAMBA:
        h = _constrain_block_input(rmsnorm(params["norm"], x, cfg.norm_eps))
        x = _add(x, mamba2.mamba2_forward(params["mixer"], h, cfg), cfg)
        return _mamba_mlp(params, x, cfg), aux
    window = _block_window(kind, cfg)
    h = _constrain_block_input(rmsnorm(params["norm1"], x, cfg.norm_eps))
    h = attn.attention_forward(params["attn"], h, cfg, window=window,
                               positions=positions, impl=attn_impl)
    if cfg.use_post_norms:
        h = rmsnorm(params["post_norm1"], h, cfg.norm_eps)
    x = _add(x, h, cfg)
    h = _constrain_block_input(rmsnorm(params["norm2"], x, cfg.norm_eps))
    if cfg.moe is not None:
        h, aux = moe.moe_forward(params["moe"], h, cfg)
    else:
        h = _mlp(params["mlp"], h, cfg)
    if cfg.use_post_norms:
        h = rmsnorm(params["post_norm2"], h, cfg.norm_eps)
    return _add(x, h, cfg), aux


# ---------------------------------------------------------------------------
# Per-block decode (one token, cache update)
# ---------------------------------------------------------------------------
def block_init_cache(kind: str, cfg: ModelConfig, batch: int, max_len: int,
                     dtype=jnp.bfloat16) -> Params:
    if kind == MAMBA:
        return mamba2.init_mamba_cache(cfg, batch, dtype)
    return attn.init_kv_cache(cfg, batch, max_len, _block_window(kind, cfg),
                              dtype)


def block_decode(params: Params, x: jnp.ndarray, cache: Params, kind: str,
                 cfg: ModelConfig, *, pos: jnp.ndarray
                 ) -> Tuple[jnp.ndarray, Params]:
    if kind == MAMBA:
        h = rmsnorm(params["norm"], x, cfg.norm_eps)
        y, new_cache = mamba2.mamba2_decode(params["mixer"], h, cache, cfg)
        return _mamba_mlp(params, _add(x, y, cfg), cfg), new_cache
    window = _block_window(kind, cfg)
    h = rmsnorm(params["norm1"], x, cfg.norm_eps)
    h, new_cache = attn.decode_attention(params["attn"], h, cache, cfg,
                                         pos=pos, window=window)
    if cfg.use_post_norms:
        h = rmsnorm(params["post_norm1"], h, cfg.norm_eps)
    x = _add(x, h, cfg)
    h = rmsnorm(params["norm2"], x, cfg.norm_eps)
    if cfg.moe is not None:
        h = moe.moe_decode(params["moe"], h, cfg)
    else:
        h = _mlp(params["mlp"], h, cfg)
    if cfg.use_post_norms:
        h = rmsnorm(params["post_norm2"], h, cfg.norm_eps)
    return _add(x, h, cfg), new_cache


def _mamba_mlp(params: Params, x: jnp.ndarray, cfg: ModelConfig
               ) -> jnp.ndarray:
    """The MLP sublayer after a Mamba mixer, where the model has one."""
    if not cfg.mamba_mlp:
        return x
    h = _constrain_block_input(rmsnorm(params["norm2"], x, cfg.norm_eps))
    return _add(x, _mlp(params["mlp"], h, cfg), cfg)


# ---------------------------------------------------------------------------
# Stack structure: periods + remainder
# ---------------------------------------------------------------------------
def _remat_group(n_full: int) -> int:
    """Largest divisor of n_full closest to sqrt(n_full) (two-level remat);
    1 when n_full is small or prime-ish."""
    if n_full < 6:
        return 1
    best, target = 1, n_full ** 0.5
    for d in range(2, n_full):
        if n_full % d == 0 and abs(d - target) < abs(best - target):
            best = d
    return best


def stack_layout(cfg: ModelConfig) -> Tuple[int, Tuple[str, ...], Tuple[str, ...]]:
    """(n_full, pattern, remainder_kinds)."""
    pat = cfg.block_pattern
    n_full = cfg.num_layers // len(pat)
    rem = cfg.blocks[n_full * len(pat):]
    return n_full, pat, rem


def stack_init(key, cfg: ModelConfig, *, dtype=jnp.float32) -> Params:
    """Init all blocks; period params stacked over the leading axis."""
    n_full, pat, rem = stack_layout(cfg)
    keys = jax.random.split(key, cfg.num_layers)
    period: List[Params] = []
    if cfg.scan_layers and n_full > 1:
        for p_idx, kind in enumerate(pat):
            ks = [keys[i * len(pat) + p_idx] for i in range(n_full)]
            stacked = jax.tree.map(
                lambda *xs: jnp.stack(xs),
                *[block_init(k, kind, cfg, dtype=dtype) for k in ks])
            period.append(stacked)
        rem_params = [block_init(keys[n_full * len(pat) + i], kind, cfg,
                                 dtype=dtype)
                      for i, kind in enumerate(rem)]
        return {"period": period, "rem": rem_params}
    # unrolled: one params dict per block
    return {"period": [],
            "rem": [block_init(keys[i], kind, cfg, dtype=dtype)
                    for i, kind in enumerate(cfg.blocks)]}


def stack_forward(params: Params, x: jnp.ndarray, cfg: ModelConfig,
                  *, positions: Optional[jnp.ndarray] = None,
                  attn_impl: str = "auto") -> Tuple[jnp.ndarray, jnp.ndarray]:
    """Apply all blocks. Returns (y, total_aux_loss)."""
    n_full, pat, rem = stack_layout(cfg)
    aux = jnp.zeros((), jnp.float32)
    from repro.sharding.context import (get_activation_spec,
                                        get_unzero_specs)
    act_spec = get_activation_spec()
    unzero = get_unzero_specs()

    def _constrain(h):
        if act_spec is not None and h.shape[1] % 8 == 0:
            h = jax.lax.with_sharding_constraint(h, act_spec)
        return h

    def _gather_layer(lp, idx_or_key, section):
        """ZeRO-3: all-gather ONE layer's params inside the scan body so
        only the current layer is fully materialized (FSDP semantics)."""
        if unzero is None:
            return lp
        spec = unzero[section][idx_or_key]
        return jax.tree.map(jax.lax.with_sharding_constraint, lp, spec)

    if params["period"]:
        def period_body(carry, layer_params):
            h, a = carry
            for p_idx, kind in enumerate(pat):
                lp = _gather_layer(layer_params[p_idx], p_idx, "period")
                h_new, a_blk = block_forward(
                    lp, h, kind, cfg,
                    positions=positions, attn_impl=attn_impl)
                h, a = h_new, a + a_blk
            # sequence-parallel storage of the scan carry (see
            # sharding/context.py) — the rematted residual per layer
            h = _constrain(h)
            return (h, a), None

        stacked = tuple(params["period"])
        grp = _remat_group(n_full) if cfg.remat else 1
        if cfg.remat and grp > 1:
            # two-level (√L) remat: outer scan over groups stores only
            # n_full/grp carries; backward recomputes one group at a time,
            # whose inner scan stores grp carries; each period body is
            # itself checkpointed so block internals recompute per layer.
            regrouped = jax.tree.map(
                lambda t: t.reshape(n_full // grp, grp, *t.shape[1:]),
                stacked)

            def outer_body(carry, group_params):
                c, _ = jax.lax.scan(
                    jax.checkpoint(period_body, prevent_cse=False),
                    carry, group_params)
                return c, None

            (x, aux), _ = jax.lax.scan(
                jax.checkpoint(outer_body, prevent_cse=False),
                (x, aux), regrouped)
        else:
            body = period_body
            if cfg.remat:
                body = jax.checkpoint(period_body, prevent_cse=False)
            (x, aux), _ = jax.lax.scan(body, (x, aux), stacked)
        rem_kinds = rem
    else:
        rem_kinds = cfg.blocks
    for i, (p, kind) in enumerate(zip(params["rem"], rem_kinds)):
        p = _gather_layer(p, i, "rem")
        fwd = functools.partial(block_forward, kind=kind, cfg=cfg,
                                positions=positions, attn_impl=attn_impl)
        if cfg.remat:
            # per-block remat: backward keeps only the block's input
            fwd = jax.checkpoint(fwd)
        x, a = fwd(p, x)
        x = _constrain(x)     # same carry layout as the scanned path
        aux = aux + a
    return x, aux


def stack_init_cache(cfg: ModelConfig, batch: int, max_len: int,
                     dtype=jnp.bfloat16) -> Params:
    n_full, pat, rem = stack_layout(cfg)
    if cfg.scan_layers and n_full > 1:
        period = []
        for kind in pat:
            one = block_init_cache(kind, cfg, batch, max_len, dtype)
            period.append(jax.tree.map(
                lambda a: jnp.broadcast_to(a, (n_full,) + a.shape).copy(),
                one))
        rem_caches = [block_init_cache(k, cfg, batch, max_len, dtype)
                      for k in rem]
        return {"period": period, "rem": rem_caches}
    return {"period": [],
            "rem": [block_init_cache(k, cfg, batch, max_len, dtype)
                    for k in cfg.blocks]}


def stack_decode(params: Params, x: jnp.ndarray, cache: Params,
                 cfg: ModelConfig, *, pos: jnp.ndarray
                 ) -> Tuple[jnp.ndarray, Params]:
    n_full, pat, rem = stack_layout(cfg)
    if params["period"]:
        def period_body(h, scanned):
            layer_params, layer_cache = scanned
            new_caches = []
            for p_idx, kind in enumerate(pat):
                h, nc = block_decode(layer_params[p_idx], h,
                                     layer_cache[p_idx], kind, cfg, pos=pos)
                new_caches.append(nc)
            return h, tuple(new_caches)

        x, new_period = jax.lax.scan(
            period_body, x, (tuple(params["period"]), tuple(cache["period"])))
        new_period = list(new_period)
        rem_kinds = rem
    else:
        new_period = []
        rem_kinds = cfg.blocks
    new_rem = []
    for p, c, kind in zip(params["rem"], cache["rem"], rem_kinds):
        x, nc = block_decode(p, x, c, kind, cfg, pos=pos)
        new_rem.append(nc)
    return x, {"period": new_period, "rem": new_rem}


def stack_prefill(params: Params, x: jnp.ndarray, cfg: ModelConfig,
                  cache: Params, *, attn_impl: str = "auto"
                  ) -> Tuple[jnp.ndarray, Params]:
    """Full-sequence forward that also fills the KV caches (prefill).

    Uses the unrolled path when available; with scanned params the cache is
    produced inside the scan.  Mamba blocks update conv+ssm state.
    """
    n_full, pat, rem = stack_layout(cfg)
    positions = jnp.arange(x.shape[1])

    def one_block(p, c, kind, h):
        if kind == MAMBA:
            hn = rmsnorm(p["norm"], h, cfg.norm_eps)
            # full forward; final state via ssd_chunked on the side
            y = mamba2.mamba2_forward(p["mixer"], hn, cfg)
            new_c = _mamba_prefill_state(p["mixer"], hn, cfg, c)
            return _mamba_mlp(p, _add(h, y, cfg), cfg), new_c
        window = _block_window(kind, cfg)
        hn = rmsnorm(p["norm1"], h, cfg.norm_eps)
        out, (k_new, v_new) = attn.attention_forward(
            p["attn"], hn, cfg, window=window, positions=positions,
            impl=attn_impl, kv_cache_out=True)
        if cfg.use_post_norms:
            out = rmsnorm(p["post_norm1"], out, cfg.norm_eps)
        h = _add(h, out, cfg)
        hn = rmsnorm(p["norm2"], h, cfg.norm_eps)
        if cfg.moe is not None:
            hn, _ = moe.moe_forward(p["moe"], hn, cfg)
        else:
            hn = _mlp(p["mlp"], hn, cfg)
        if cfg.use_post_norms:
            hn = rmsnorm(p["post_norm2"], hn, cfg.norm_eps)
        new_c = attn.fill_kv_cache(c, k_new, v_new)
        return _add(h, hn, cfg), new_c

    from repro.sharding.context import get_activation_spec
    act_spec = get_activation_spec()

    if params["period"]:
        def period_body(h, scanned):
            layer_params, layer_cache = scanned
            new_caches = []
            for p_idx, kind in enumerate(pat):
                h, nc = one_block(layer_params[p_idx], layer_cache[p_idx],
                                  kind, h)
                new_caches.append(nc)
            if act_spec is not None and h.shape[1] % 8 == 0:
                h = jax.lax.with_sharding_constraint(h, act_spec)
            return h, tuple(new_caches)

        body = period_body
        if cfg.remat:
            body = jax.checkpoint(period_body, prevent_cse=False)
        x, new_period = jax.lax.scan(
            body, x, (tuple(params["period"]), tuple(cache["period"])))
        new_period = list(new_period)
        rem_kinds = rem
    else:
        new_period = []
        rem_kinds = cfg.blocks
    new_rem = []
    for p, c, kind in zip(params["rem"], cache["rem"], rem_kinds):
        x, nc = one_block(p, c, kind, x)
        new_rem.append(nc)
    return x, {"period": new_period, "rem": new_rem}


def _mamba_prefill_state(mixer: Params, h: jnp.ndarray, cfg: ModelConfig,
                         cache: Params) -> Params:
    """Recompute the final conv + ssm state after a full-sequence pass."""
    s = cfg.ssm
    d_in = cfg.d_inner
    nh = cfg.ssm_heads
    zxbcdt = dense(mixer["in_proj"], h)
    _, xi, Bm, Cm, dt = mamba2._split_in_proj(cfg, zxbcdt)
    xBC = jnp.concatenate([xi, Bm, Cm], axis=-1)
    conv_state = xBC[:, -(s.d_conv - 1):, :]
    xBC = jax.nn.silu(mamba2.causal_conv1d(xBC, mixer["conv_w"],
                                           mixer["conv_b"]))
    xi, Bm, Cm = jnp.split(xBC, [d_in, d_in + s.n_groups * s.d_state],
                           axis=-1)
    dt = jax.nn.softplus(dt.astype(jnp.float32) + mixer["dt_bias"])
    A = -jnp.exp(mixer["A_log"])
    B_, L = h.shape[0], h.shape[1]
    xh = xi.reshape(B_, L, nh, s.head_dim)
    Bh = Bm.reshape(B_, L, s.n_groups, s.d_state)
    Ch = Cm.reshape(B_, L, s.n_groups, s.d_state)
    pad = (-L) % s.chunk_size
    if pad:
        xh = jnp.pad(xh, ((0, 0), (0, pad), (0, 0), (0, 0)))
        dt = jnp.pad(dt, ((0, 0), (0, pad), (0, 0)))
        Bh = jnp.pad(Bh, ((0, 0), (0, pad), (0, 0), (0, 0)))
        Ch = jnp.pad(Ch, ((0, 0), (0, pad), (0, 0), (0, 0)))
    _, final_state = mamba2.ssd_chunked(xh, dt, A, Bh, Ch,
                                        chunk=s.chunk_size)
    return {"conv": conv_state.astype(cache["conv"].dtype),
            "ssm": final_state}
