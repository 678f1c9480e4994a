"""Selective state-space blocks: Mamba-1 (falcon-mamba) and Mamba-2 (zamba2).

TPU adaptation (see DESIGN.md): the GPU reference implementations fuse a
sequential scan into a single CUDA kernel with warp-level parallelism.
On TPU we use *chunked* formulations instead:

  * Mamba-1 — per-(channel, state) diagonal decays: within a chunk an
    associative scan (log-depth, elementwise), across chunks a
    sequential ``lax.scan`` carrying the (B, d_inner, N) state.  Peak
    memory is O(B·chunk·d_inner·N), never O(B·S·d_inner·N).
  * Mamba-2 (SSD) — per-head *scalar* decay makes the chunk-local part a
    pair of matmuls (the "attention-like" form), which is exactly what
    the MXU wants; inter-chunk recurrence carries (B, H, P, N) states.

Both have single-token decode steps carrying (conv window, ssm state).
The Pallas kernel (``repro.kernels.ssm_scan``) implements the Mamba-1
chunk step with VMEM tiling; this module is its oracle.
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp

from ..sharding.api import shard
from .common import Builder, silu, softplus


# --------------------------------------------------------------------------- #
# Causal depthwise conv1d
# --------------------------------------------------------------------------- #
def causal_conv(x, w, b, carry=None):
    """x: (B, S, C); w: (C, K); returns (y, new_carry (B, K-1, C))."""
    B, S, C = x.shape
    K = w.shape[1]
    if carry is None:
        carry = jnp.zeros((B, K - 1, C), x.dtype)
    xp = jnp.concatenate([carry, x], axis=1)
    y = jax.lax.conv_general_dilated(
        xp, w.T[:, None, :],                          # (K, I=1, O=C) WIO
        window_strides=(1,), padding="VALID",
        dimension_numbers=("NWC", "WIO", "NWC"),
        feature_group_count=C)
    new_carry = xp[:, -(K - 1):] if K > 1 else carry
    return y + b, new_carry


# --------------------------------------------------------------------------- #
# Mamba-1
# --------------------------------------------------------------------------- #
def mamba1_params(b: Builder, cfg, prefix: str) -> dict:
    D, di, N, R, K = (cfg.d_model, cfg.d_inner, cfg.ssm_state, cfg.dt_rank,
                      cfg.ssm_conv)

    def a_init(key, shape, dtype):
        a = jnp.tile(jnp.arange(1, N + 1, dtype=jnp.float32), (di, 1))
        return jnp.log(a).astype(dtype)

    return {
        "in_proj": b.leaf(f"{prefix}.in_proj", (D, 2 * di), ("embed", "d_inner")),
        "conv_w": b.leaf(f"{prefix}.conv_w", (di, K), ("d_inner", "kernel")),
        "conv_b": b.leaf(f"{prefix}.conv_b", (di,), ("d_inner",), init="zeros"),
        "x_proj": b.leaf(f"{prefix}.x_proj", (di, R + 2 * N), ("d_inner", None)),
        "dt_proj": b.leaf(f"{prefix}.dt_proj", (R, di), ("dt_rank", "d_inner")),
        "dt_bias": b.leaf(f"{prefix}.dt_bias", (di,), ("d_inner",), init="zeros"),
        "A_log": b.leaf(f"{prefix}.A_log", (di, N), ("d_inner", "state"),
                        init=a_init, dtype=jnp.float32),
        "D": b.leaf(f"{prefix}.D", (di,), ("d_inner",), init="ones",
                    dtype=jnp.float32),
        "out_proj": b.leaf(f"{prefix}.out_proj", (di, D), ("d_inner", "embed")),
    }


def _mamba1_inner(cfg, p, xc, z, h0):
    """Scan core.  xc: (B, S, di) post-conv+silu; z: gate; h0: (B, di, N).
    Returns (y (B,S,di), h_final)."""
    B, S, di = xc.shape
    N, R = cfg.ssm_state, cfg.dt_rank
    proj = jnp.einsum("bsc,cr->bsr", xc, p["x_proj"])
    dt_low, B_, C_ = jnp.split(proj, [R, R + N], axis=-1)
    dt = softplus(jnp.einsum("bsr,rc->bsc", dt_low, p["dt_proj"]).astype(jnp.float32)
                  + p["dt_bias"].astype(jnp.float32))           # (B,S,di) fp32
    A = -jnp.exp(p["A_log"].astype(jnp.float32))                # (di, N)

    L = min(cfg.ssm_chunk, S)
    if S % L != 0:
        L = S
    nc = S // L

    def chunk_step(h, xs):
        dt_c, B_c, C_c, x_c = xs        # (B,L,di) (B,L,N) (B,L,N) (B,L,di)
        zlog = dt_c[..., None] * A      # (B,L,di,N) ≤ 0
        dBx = dt_c[..., None] * B_c[:, :, None, :].astype(jnp.float32) \
            * x_c[..., None].astype(jnp.float32)

        dA = shard(jnp.exp(zlog), "batch", None, "d_inner", "state")
        dBx = shard(dBx, "batch", None, "d_inner", "state")
        def op(a, b):
            return (a[0] * b[0], a[1] * b[0] + b[1])
        dec, hs = jax.lax.associative_scan(op, (dA, dBx), axis=1)
        # carry-in contribution: exp(cumsum zlog)·h0 == dec·h0
        hs = hs + dec * h[:, None]
        y = jnp.einsum("blcn,bln->blc", hs, C_c.astype(jnp.float32))
        return hs[:, -1], y

    shape5 = lambda t: t.reshape(B, nc, L, *t.shape[2:]).swapaxes(0, 1)
    xs = (shape5(dt), shape5(B_), shape5(C_), shape5(xc))
    h_fin, ys = jax.lax.scan(chunk_step, h0.astype(jnp.float32), xs)
    y = ys.swapaxes(0, 1).reshape(B, S, di)
    y = y + xc.astype(jnp.float32) * p["D"]
    y = (y * silu(z).astype(jnp.float32)).astype(xc.dtype)
    return y, h_fin


def mamba1_block(cfg, p, x, cache=None):
    """x: (B, S, D).  cache: None (train/prefill from scratch) or dict
    {"conv": (B,K-1,di), "h": (B,di,N)} for single-step decode."""
    B, S, D = x.shape
    di, N = cfg.d_inner, cfg.ssm_state
    xz = jnp.einsum("bsd,de->bse", x, p["in_proj"])
    xz = shard(xz, "batch", "seq", "d_inner")
    xr, z = jnp.split(xz, 2, axis=-1)
    conv_in = cache["conv"] if cache is not None else None
    xc, conv_out = causal_conv(xr, p["conv_w"], p["conv_b"], conv_in)
    xc = silu(xc)
    h0 = cache["h"] if cache is not None else jnp.zeros((B, di, N), jnp.float32)
    if S == 1 and cache is not None:
        # decode: one recurrence step, no scan
        proj = jnp.einsum("bsc,cr->bsr", xc, p["x_proj"])
        dt_low, B_, C_ = jnp.split(proj, [cfg.dt_rank, cfg.dt_rank + N], -1)
        dt = softplus(jnp.einsum("bsr,rc->bsc", dt_low, p["dt_proj"]
                                 ).astype(jnp.float32) + p["dt_bias"])[:, 0]
        A = -jnp.exp(p["A_log"].astype(jnp.float32))
        dA = jnp.exp(dt[..., None] * A)
        dBx = dt[..., None] * B_[:, 0, None, :].astype(jnp.float32) \
            * xc[:, 0, :, None].astype(jnp.float32)
        h = dA * h0 + dBx
        y = jnp.einsum("bcn,bn->bc", h, C_[:, 0].astype(jnp.float32))
        y = y + xc[:, 0].astype(jnp.float32) * p["D"]
        y = (y[:, None] * silu(z).astype(jnp.float32)).astype(x.dtype)
        h_fin = h
    else:
        y, h_fin = _mamba1_inner(cfg, p, xc, z, h0)
    out = jnp.einsum("bsc,cd->bsd", y, p["out_proj"])
    out = shard(out, "batch", "seq", "embed")
    new_cache = {"conv": conv_out, "h": h_fin}
    return out, new_cache


# --------------------------------------------------------------------------- #
# Mamba-2 (SSD)
# --------------------------------------------------------------------------- #
def mamba2_params(b: Builder, cfg, prefix: str) -> dict:
    D, di, N, K = cfg.d_model, cfg.d_inner, cfg.ssm_state, cfg.ssm_conv
    H, G = cfg.ssm_heads, cfg.ssm_ngroups
    d_xbc = di + 2 * G * N
    d_in = di + d_xbc + H

    def a_init(key, shape, dtype):
        return jnp.log(jnp.linspace(1.0, 16.0, H)).astype(dtype)

    return {
        "in_proj": b.leaf(f"{prefix}.in_proj", (D, d_in), ("embed", "d_inner")),
        "conv_w": b.leaf(f"{prefix}.conv_w", (d_xbc, K), ("conv_dim", "kernel")),
        "conv_b": b.leaf(f"{prefix}.conv_b", (d_xbc,), ("conv_dim",), init="zeros"),
        "A_log": b.leaf(f"{prefix}.A_log", (H,), ("ssm_heads",), init=a_init,
                        dtype=jnp.float32),
        "D": b.leaf(f"{prefix}.D", (H,), ("ssm_heads",), init="ones",
                    dtype=jnp.float32),
        "dt_bias": b.leaf(f"{prefix}.dt_bias", (H,), ("ssm_heads",), init="zeros",
                          dtype=jnp.float32),
        "norm": b.leaf(f"{prefix}.norm", (di,), ("d_inner",), init="ones"),
        "out_proj": b.leaf(f"{prefix}.out_proj", (di, D), ("d_inner", "embed")),
    }


def _ssd_chunk(cfg, dt, zlog, x, B_, C_, h0):
    """Chunked SSD.  dt: (B,S,H) input scale; zlog = dt·A ≤ 0 decay exponent;
    x: (B,S,G,Hg,P), the heads in B/C groups; B_,C_: (B,S,G,N).
    Returns (y (B,S,G,Hg,P), h_fin (B,G,Hg,P,N))."""
    Bb, S, G, Hg, P = x.shape
    L = min(cfg.ssm_chunk, S)
    if S % L != 0:
        L = S
    nc = S // L
    grouped = lambda t: t.reshape(*t.shape[:-1], G, Hg)     # (..,H)→(..,G,Hg)

    def chunk_step(h, xs):
        dt_c, z_c, x_c, B_c, C_c = xs       # (B,L,H) ×2, (B,L,G,Hg,P), (B,L,G,N) ×2
        Scum = jnp.cumsum(z_c, axis=1)      # (B,L,H)
        # intra-chunk: att[b,t,s,h] = exp(S_t - S_s)·(C_t·B_s), s ≤ t
        cb = jnp.einsum("btgn,bsgn->btsg", C_c.astype(jnp.float32),
                        B_c.astype(jnp.float32))
        dec = Scum[:, :, None, :] - Scum[:, None, :, :]      # (B,t,s,H)
        dec = shard(dec, "batch", None, None, "ssm_heads")
        tri = jnp.tril(jnp.ones((L, L), bool))[None, :, :, None]
        # mask *before* exp: exp of a positive upper-tri entry would inf
        # out and poison the backward pass with inf·0 NaNs.
        w = jnp.exp(jnp.where(tri, dec, -jnp.inf))
        att = cb[..., None] * grouped(w)                     # (B,t,s,G,Hg)
        dtx = grouped(dt_c)[..., None] * x_c.astype(jnp.float32)
        y = jnp.einsum("btsgh,bsghp->btghp", att, dtx)
        # carry-in: y_t += exp(S_t)·(C_t · h)
        y = y + jnp.einsum("btgn,bghpn->btghp", C_c.astype(jnp.float32),
                           h) * grouped(jnp.exp(Scum))[..., None]
        # new carry: h' = exp(S_L)·h + Σ_s exp(S_L - S_s) B_s ⊗ dtx_s
        wL = grouped(jnp.exp(Scum[:, -1:, :] - Scum))        # (B,L,G,Hg)
        h_new = h * grouped(jnp.exp(Scum[:, -1]))[..., None, None] + \
            jnp.einsum("bsgn,bsghp,bsgh->bghpn", B_c.astype(jnp.float32),
                       dtx, wL)
        return h_new, y

    shape5 = lambda t: t.reshape(Bb, nc, L, *t.shape[2:]).swapaxes(0, 1)
    xs = (shape5(dt), shape5(zlog), shape5(x), shape5(B_), shape5(C_))
    h_fin, ys = jax.lax.scan(chunk_step, h0.astype(jnp.float32), xs)
    y = ys.swapaxes(0, 1).reshape(Bb, S, G, Hg, P)
    return y, h_fin


def gated_group_norm(y, z, scale, groups: int, eps: float):
    """Mamba-2's gated RMSNorm: ``y · silu(z)`` in float32, normalized
    over each of ``groups`` equal slices of the last axis, then scaled."""
    g = y.astype(jnp.float32) * jax.nn.silu(z.astype(jnp.float32))
    g = g.reshape(*g.shape[:-1], groups, -1)
    g = g * jax.lax.rsqrt(jnp.mean(g * g, axis=-1, keepdims=True) + eps)
    return g.reshape(y.shape) * scale.astype(jnp.float32)


def mamba2_block(cfg, p, x, cache=None):
    """x: (B, S, D); cache {"conv": (B,K-1,d_xbc), "h": (B,H,P,N)}.  The
    heads fall in ``cfg.ssm_ngroups`` groups, each group's heads reading
    its own B and C."""
    B, S, D = x.shape
    di, N, H, P = cfg.d_inner, cfg.ssm_state, cfg.ssm_heads, cfg.ssm_head_dim
    G = cfg.ssm_ngroups
    Hg = H // G
    zxbcdt = jnp.einsum("bsd,de->bse", x, p["in_proj"])
    zxbcdt = shard(zxbcdt, "batch", "seq", "d_inner")
    z, xBC, dt_raw = jnp.split(zxbcdt, [di, 2 * di + 2 * G * N], axis=-1)
    conv_in = cache["conv"] if cache is not None else None
    xBC, conv_out = causal_conv(xBC, p["conv_w"], p["conv_b"], conv_in)
    xBC = silu(xBC)
    xr, B_, C_ = jnp.split(xBC, [di, di + G * N], axis=-1)
    xh = xr.reshape(B, S, G, Hg, P)
    B_ = B_.reshape(B, S, G, N)
    C_ = C_.reshape(B, S, G, N)
    A = -jnp.exp(p["A_log"].astype(jnp.float32))              # (H,)
    dt = softplus(dt_raw.astype(jnp.float32) + p["dt_bias"])  # (B,S,H)
    dt = jnp.maximum(dt, cfg.ssm_dt_min)
    zlog = dt * A                                             # decay exponent
    h0 = (cache["h"] if cache is not None
          else jnp.zeros((B, H, P, N), jnp.float32)).reshape(B, G, Hg, P, N)

    if S == 1 and cache is not None:
        dA = jnp.exp(zlog[:, 0]).reshape(B, G, Hg)
        dtx = dt[:, 0].reshape(B, G, Hg, 1) * xh[:, 0].astype(jnp.float32)
        h = h0 * dA[..., None, None] + \
            jnp.einsum("bgn,bghp->bghpn", B_[:, 0].astype(jnp.float32), dtx)
        y = jnp.einsum("bgn,bghpn->bghp", C_[:, 0].astype(jnp.float32),
                       h)[:, None]
        h_fin = h
    else:
        y, h_fin = _ssd_chunk(cfg, dt, zlog, xh, B_, C_, h0)
    y = y + xh.astype(jnp.float32) * p["D"].reshape(G, Hg, 1)
    y = gated_group_norm(y.reshape(B, S, di), z, p["norm"], G, cfg.norm_eps)
    out = jnp.einsum("bsc,cd->bsd", y.astype(x.dtype), p["out_proj"])
    out = shard(out, "batch", "seq", "embed")
    return out, {"conv": conv_out, "h": h_fin.reshape(B, H, P, N)}
