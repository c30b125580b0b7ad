"""Mamba2 (SSD — state-space duality, arXiv:2405.21060) block in pure JAX.

Implements the chunked SSD algorithm:
  * intra-chunk: quadratic attention-like term  C_c (decay ⊙ B_c^T X_c)
  * inter-chunk: linear recurrence over chunk states
and the O(1) single-token decode recurrence, plus the depthwise causal
conv1d and gated RMSNorm of the Mamba2 block.

Shapes follow the paper: X (B,L,H,P), dt (B,L,H), A (H,) negative,
B/C (B,L,G,N) with G groups broadcast over H heads.

The Pallas kernel in ``repro.kernels.ssd_scan`` implements the intra-chunk
+ state-passing computation with VMEM tiling; ``ssd_chunked`` here is its
jnp oracle (also used on the dry-run path).
"""
from __future__ import annotations

from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig
from repro.models.layers import dense, dense_init

Params = Dict[str, Any]


# ---------------------------------------------------------------------------
# Params
# ---------------------------------------------------------------------------
def mamba2_init(key, cfg: ModelConfig, *, dtype=jnp.float32) -> Params:
    s = cfg.ssm
    d = cfg.d_model
    d_in = cfg.d_inner
    nh = cfg.ssm_heads
    conv_dim = d_in + 2 * s.n_groups * s.d_state
    ks = jax.random.split(key, 6)
    lo, hi = s.a_init_range
    a = jax.random.uniform(ks[3], (nh,), minval=lo, maxval=hi)
    # dt bias via inverse softplus of dt ~ U[1e-3, 1e-1]
    dt = jnp.exp(jax.random.uniform(ks[4], (nh,),
                                    minval=jnp.log(1e-3), maxval=jnp.log(1e-1)))
    dt_bias = dt + jnp.log(-jnp.expm1(-dt))
    return {
        # order: [z (d_in), x (d_in), B (G*N), C (G*N), dt (nh)]
        "in_proj": dense_init(ks[0], d,
                              2 * d_in + 2 * s.n_groups * s.d_state + nh,
                              dtype=dtype),
        "conv_w": (jax.random.normal(ks[1], (s.d_conv, conv_dim)) * 0.1
                   ).astype(dtype),
        "conv_b": jnp.zeros((conv_dim,), dtype),
        "A_log": jnp.log(a).astype(jnp.float32),
        "D": jnp.ones((nh,), jnp.float32),
        "dt_bias": dt_bias.astype(jnp.float32),
        "norm_scale": jnp.zeros((d_in,), jnp.float32),
        "out_proj": dense_init(ks[5], d_in, d, dtype=dtype),
    }


# ---------------------------------------------------------------------------
# SSD chunked algorithm (jnp oracle / default path)
# ---------------------------------------------------------------------------
def _segsum(x: jnp.ndarray) -> jnp.ndarray:
    """Stable segment-sum: out[..., i, j] = sum_{k=j+1..i} x[..., k]
    (lower-triangular); -inf above diagonal."""
    T = x.shape[-1]
    cs = jnp.cumsum(x, axis=-1)
    seg = cs[..., :, None] - cs[..., None, :]
    mask = jnp.tril(jnp.ones((T, T), bool), 0)
    return jnp.where(mask, seg, -jnp.inf)


def ssd_chunked(x: jnp.ndarray, dt: jnp.ndarray, A: jnp.ndarray,
                B: jnp.ndarray, C: jnp.ndarray, *, chunk: int,
                initial_state: Optional[jnp.ndarray] = None
                ) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """SSD over a full sequence.

    x  (Bt, L, H, P)   inputs (already conv'd + activated)
    dt (Bt, L, H)      positive step sizes
    A  (H,)            negative decay rates
    B  (Bt, L, G, N)   input projections  (G groups)
    C  (Bt, L, G, N)   output projections
    Returns (y (Bt,L,H,P), final_state (Bt,H,P,N)).
    """
    Bt, L, H, P = x.shape
    G, N = B.shape[-2], B.shape[-1]
    nc = L // chunk
    assert nc * chunk == L, f"L={L} not divisible by chunk={chunk}"
    rep = H // G

    # fold dt into x (dt * x) and keep dA = dt * A for decays (fp32)
    xf = x.astype(jnp.float32)
    dtf = dt.astype(jnp.float32)
    dA = dtf * A[None, None, :]                          # (Bt,L,H) negative

    # chunk views
    xc = (xf * dtf[..., None]).reshape(Bt, nc, chunk, H, P)
    dAc = dA.reshape(Bt, nc, chunk, H)
    Bc = B.astype(jnp.float32).reshape(Bt, nc, chunk, G, N)
    Cc = C.astype(jnp.float32).reshape(Bt, nc, chunk, G, N)
    Bh = jnp.repeat(Bc, rep, axis=-2)                    # (Bt,nc,Q,H,N)
    Ch = jnp.repeat(Cc, rep, axis=-2)

    # ---- intra-chunk (quadratic within chunk) ----
    Lmat = jnp.exp(_segsum(dAc.transpose(0, 1, 3, 2)))   # (Bt,nc,H,Q,Q)
    scores = jnp.einsum("bcqhn,bckhn->bchqk", Ch, Bh)    # (Bt,nc,H,Q,Q)
    y_intra = jnp.einsum("bchqk,bckhp->bcqhp", scores * Lmat, xc)

    # ---- chunk states: S_c = sum_k decay_to_end(k) * B_k ⊗ x_k ----
    dA_cum = jnp.cumsum(dAc, axis=2)                     # (Bt,nc,Q,H)
    decay_to_end = jnp.exp(dA_cum[:, :, -1:, :] - dA_cum)  # (Bt,nc,Q,H)
    states = jnp.einsum("bcqh,bcqhn,bcqhp->bchpn",
                        decay_to_end, Bh, xc)            # (Bt,nc,H,P,N)

    # ---- inter-chunk recurrence over chunk states ----
    chunk_decay = jnp.exp(dA_cum[:, :, -1, :])           # (Bt,nc,H)

    def scan_fn(carry, inp):
        s_prev = carry                                   # (Bt,H,P,N)
        s_c, dec = inp                                   # (Bt,H,P,N),(Bt,H)
        s_new = s_c + dec[..., None, None] * s_prev
        return s_new, s_prev                             # emit state *before* chunk

    s0 = (jnp.zeros((Bt, H, P, N), jnp.float32) if initial_state is None
          else initial_state.astype(jnp.float32))
    final_state, states_before = jax.lax.scan(
        scan_fn, s0,
        (states.transpose(1, 0, 2, 3, 4), chunk_decay.transpose(1, 0, 2)))
    states_before = states_before.transpose(1, 0, 2, 3, 4)  # (Bt,nc,H,P,N)

    # ---- inter-chunk output: y += C_q * decay_from_start(q) * S_{c-1} ----
    decay_from_start = jnp.exp(dA_cum)                   # (Bt,nc,Q,H)
    y_inter = jnp.einsum("bcqhn,bchpn,bcqh->bcqhp",
                         Ch, states_before, decay_from_start)

    y = (y_intra + y_inter).reshape(Bt, L, H, P)
    return y, final_state


def ssd_decode_step(state: jnp.ndarray, x_t: jnp.ndarray, dt_t: jnp.ndarray,
                    A: jnp.ndarray, B_t: jnp.ndarray, C_t: jnp.ndarray
                    ) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """One-token SSD recurrence.
    state (Bt,H,P,N); x_t (Bt,H,P); dt_t (Bt,H); B_t/C_t (Bt,G,N).
    h <- exp(dt*A) h + (dt*x) ⊗ B ; y = C·h
    """
    H = x_t.shape[1]
    G = B_t.shape[1]
    rep = H // G
    Bh = jnp.repeat(B_t, rep, axis=1).astype(jnp.float32)   # (Bt,H,N)
    Ch = jnp.repeat(C_t, rep, axis=1).astype(jnp.float32)
    dA = jnp.exp(dt_t.astype(jnp.float32) * A[None, :])     # (Bt,H)
    xdt = x_t.astype(jnp.float32) * dt_t[..., None]
    new_state = (dA[..., None, None] * state.astype(jnp.float32)
                 + jnp.einsum("bhp,bhn->bhpn", xdt, Bh))
    y = jnp.einsum("bhpn,bhn->bhp", new_state, Ch)
    return y.astype(x_t.dtype), new_state


# ---------------------------------------------------------------------------
# Conv1d (depthwise causal)
# ---------------------------------------------------------------------------
def causal_conv1d(x: jnp.ndarray, w: jnp.ndarray, b: jnp.ndarray
                  ) -> jnp.ndarray:
    """x (B,L,C); w (K,C) depthwise; causal (left) padding."""
    K = w.shape[0]
    xp = jnp.pad(x, ((0, 0), (K - 1, 0), (0, 0)))
    out = sum(xp[:, i:i + x.shape[1], :] * w[i][None, None, :]
              for i in range(K))
    return out + b[None, None, :]


def conv1d_decode_step(conv_state: jnp.ndarray, x_t: jnp.ndarray,
                       w: jnp.ndarray, b: jnp.ndarray
                       ) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """conv_state (B,K-1,C) = previous inputs; x_t (B,C)."""
    window = jnp.concatenate([conv_state, x_t[:, None, :]], axis=1)  # (B,K,C)
    y = jnp.einsum("bkc,kc->bc", window, w) + b
    return y, window[:, 1:, :]


# ---------------------------------------------------------------------------
# Full Mamba2 block
# ---------------------------------------------------------------------------
def _split_in_proj(cfg: ModelConfig, zxbcdt: jnp.ndarray):
    s = cfg.ssm
    d_in = cfg.d_inner
    gn = s.n_groups * s.d_state
    z, x, Bm, Cm, dt = jnp.split(
        zxbcdt, [d_in, 2 * d_in, 2 * d_in + gn, 2 * d_in + 2 * gn], axis=-1)
    return z, x, Bm, Cm, dt


def _gated_rmsnorm(scale: jnp.ndarray, x: jnp.ndarray, z: jnp.ndarray,
                   eps: float) -> jnp.ndarray:
    """Mamba2's norm: RMSNorm(x * silu(z)) * (1+scale)."""
    dt = x.dtype
    x = x.astype(jnp.float32) * jax.nn.silu(z.astype(jnp.float32))
    var = jnp.mean(jnp.square(x), axis=-1, keepdims=True)
    x = x * jax.lax.rsqrt(var + eps)
    return (x * (1.0 + scale)).astype(dt)


def mamba2_forward(params: Params, x: jnp.ndarray, cfg: ModelConfig,
                   *, use_kernel: bool = False) -> jnp.ndarray:
    """Full-sequence Mamba2 block. x: (B, L, d_model) -> (B, L, d_model)."""
    s = cfg.ssm
    B_, L, _ = x.shape
    d_in = cfg.d_inner
    nh = cfg.ssm_heads
    zxbcdt = dense(params["in_proj"], x)
    z, xi, Bm, Cm, dt = _split_in_proj(cfg, zxbcdt)
    xBC = jnp.concatenate([xi, Bm, Cm], axis=-1)
    xBC = jax.nn.silu(causal_conv1d(xBC, params["conv_w"], params["conv_b"]))
    xi, Bm, Cm = jnp.split(xBC, [d_in, d_in + s.n_groups * s.d_state], axis=-1)
    dt = jax.nn.softplus(dt.astype(jnp.float32) + params["dt_bias"])
    A = -jnp.exp(params["A_log"])
    xh = xi.reshape(B_, L, nh, s.head_dim)
    Bh = Bm.reshape(B_, L, s.n_groups, s.d_state)
    Ch = Cm.reshape(B_, L, s.n_groups, s.d_state)
    # pad L to a chunk multiple
    pad = (-L) % s.chunk_size
    if pad:
        xh = jnp.pad(xh, ((0, 0), (0, pad), (0, 0), (0, 0)))
        dt = jnp.pad(dt, ((0, 0), (0, pad), (0, 0)))
        Bh = jnp.pad(Bh, ((0, 0), (0, pad), (0, 0), (0, 0)))
        Ch = jnp.pad(Ch, ((0, 0), (0, pad), (0, 0), (0, 0)))
    if use_kernel:
        from repro.kernels import pallas_interpret
        from repro.kernels.ssd_scan import ops as ssd_ops
        y, _ = ssd_ops.ssd(xh, dt, A, Bh, Ch, chunk=s.chunk_size,
                           interpret=pallas_interpret())
    else:
        y, _ = ssd_chunked(xh, dt, A, Bh, Ch, chunk=s.chunk_size)
    y = y[:, :L]
    y = y + xi.reshape(B_, L, nh, s.head_dim).astype(jnp.float32) \
        * params["D"][None, None, :, None]
    y = y.reshape(B_, L, d_in).astype(x.dtype)
    y = _gated_rmsnorm(params["norm_scale"], y, z, cfg.norm_eps)
    return dense(params["out_proj"], y)


def init_mamba_cache(cfg: ModelConfig, batch: int, dtype=jnp.bfloat16
                     ) -> Params:
    s = cfg.ssm
    conv_dim = cfg.d_inner + 2 * s.n_groups * s.d_state
    return {
        "conv": jnp.zeros((batch, s.d_conv - 1, conv_dim), dtype),
        "ssm": jnp.zeros((batch, cfg.ssm_heads, s.head_dim, s.d_state),
                         jnp.float32),
    }


def mamba2_decode(params: Params, x: jnp.ndarray, cache: Params,
                  cfg: ModelConfig) -> Tuple[jnp.ndarray, Params]:
    """One-token decode. x: (B, 1, d_model)."""
    s = cfg.ssm
    B_ = x.shape[0]
    d_in = cfg.d_inner
    nh = cfg.ssm_heads
    zxbcdt = dense(params["in_proj"], x[:, 0])
    z, xi, Bm, Cm, dt = _split_in_proj(cfg, zxbcdt)
    xBC = jnp.concatenate([xi, Bm, Cm], axis=-1)
    y_conv, new_conv = conv1d_decode_step(
        cache["conv"].astype(xBC.dtype), xBC, params["conv_w"],
        params["conv_b"])
    xBC = jax.nn.silu(y_conv)
    xi, Bm, Cm = jnp.split(xBC, [d_in, d_in + s.n_groups * s.d_state], axis=-1)
    dt = jax.nn.softplus(dt.astype(jnp.float32) + params["dt_bias"])
    A = -jnp.exp(params["A_log"])
    xh = xi.reshape(B_, nh, s.head_dim)
    Bh = Bm.reshape(B_, s.n_groups, s.d_state)
    Ch = Cm.reshape(B_, s.n_groups, s.d_state)
    y, new_ssm = ssd_decode_step(cache["ssm"], xh, dt, A, Bh, Ch)
    y = y + xh.astype(jnp.float32) * params["D"][None, :, None]
    y = y.reshape(B_, d_in).astype(x.dtype)
    y = _gated_rmsnorm(params["norm_scale"], y, z, cfg.norm_eps)
    out = dense(params["out_proj"], y)[:, None, :]
    return out, {"conv": new_conv.astype(cache["conv"].dtype), "ssm": new_ssm}
