"""GQA attention: projections, RoPE, chunked prefill, cached decode.

The prefill path is a pure-JAX flash attention: the query axis is
unrolled over chunks (Python loop → static), the kv axis is scanned with
an online-softmax carry, and causal chunks above the diagonal are never
materialized — so HLO FLOPs match the causal-optimal count and working
memory is O(chunk²) instead of O(S²).  This is also the oracle the
Pallas kernel (``repro.kernels.flash_attention``) is validated against;
on TPU the kernel replaces it via ``cfg.attn_impl="pallas"``.
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp

from ..sharding.api import shard
from .common import Builder, apply_rope, rms_norm


# --------------------------------------------------------------------------- #
# Params
# --------------------------------------------------------------------------- #
def attn_params(b: Builder, cfg, prefix: str) -> dict:
    D, H, KV, hd = cfg.d_model, cfg.n_heads, cfg.n_kv_heads, cfg.hd
    # Head-count-aware weight sharding: when H divides the TP axis, the
    # classic Megatron column-split over heads applies; otherwise (36H/
    # 24H/12H on 16-way TP) heads would replicate the projections AND
    # their fp32 optimizer moments — shard the contraction dims instead
    # (row-parallel: D for q/k/v, head_dim for o; GSPMD turns the psums
    # into reduce-scatters against the seq-parallel residual).
    from ..sharding.api import get_context
    ctx = get_context()
    tp = ctx.size("model") if ctx is not None else 1
    row_par = tp > 1 and H % tp != 0
    qe = "embed_rp" if row_par else "embed"
    od = "head_dim_rp" if row_par else "head_dim"
    p = {
        "wq": b.leaf(f"{prefix}.wq", (D, H, hd), (qe, "heads", "head_dim")),
        "wk": b.leaf(f"{prefix}.wk", (D, KV, hd), (qe, "kv_heads", "head_dim")),
        "wv": b.leaf(f"{prefix}.wv", (D, KV, hd), (qe, "kv_heads", "head_dim")),
        "wo": b.leaf(f"{prefix}.wo", (H, hd, D), ("heads", od, "embed")),
    }
    if cfg.qk_norm:
        p["q_norm"] = b.leaf(f"{prefix}.q_norm", (hd,), ("head_dim",), init="ones")
        p["k_norm"] = b.leaf(f"{prefix}.k_norm", (hd,), ("head_dim",), init="ones")
    return p


def qkv_project(cfg, p, x, positions, *, rope: bool = True):
    """x: (B, S, D) → q (B,S,H,hd), k/v (B,S,KV,hd)."""
    q = jnp.einsum("bsd,dhk->bshk", x, p["wq"])
    k = jnp.einsum("bsd,dhk->bshk", x, p["wk"])
    v = jnp.einsum("bsd,dhk->bshk", x, p["wv"])
    if cfg.qk_norm:
        q = rms_norm(q, p["q_norm"], cfg.norm_eps)
        k = rms_norm(k, p["k_norm"], cfg.norm_eps)
    if rope:
        q = apply_rope(q, positions, cfg.rope_theta)
        k = apply_rope(k, positions, cfg.rope_theta)
    from ..sharding.api import attn_q_names
    q = shard(q, *attn_q_names(cfg.n_heads))
    k = shard(k, "batch", "seq", "kv_heads", "head_dim")
    v = shard(v, "batch", "seq", "kv_heads", "head_dim")
    return q, k, v


def o_project(p, attn_out):
    y = jnp.einsum("bshk,hkd->bsd", attn_out, p["wo"])
    return shard(y, "batch", "seq", "embed")


# --------------------------------------------------------------------------- #
# Chunked prefill attention (flash-style, causal-exact FLOPs)
# --------------------------------------------------------------------------- #
def _block_attn(q, k, v, bias, scale):
    """One (q-chunk × kv-chunk) block. q:(B,c,KV,G,hd) k/v:(B,j,KV,hd)
    → (scores_max, exp_scores@v, exp_sum) in fp32."""
    s = jnp.einsum("bckgd,bjkd->bkgcj", q, k,
                   preferred_element_type=jnp.float32) * scale
    if bias is not None:
        s = s + bias
    m = jnp.max(s, axis=-1)
    e = jnp.exp(s - m[..., None])
    ev = jnp.einsum("bkgcj,bjkd->bckgd", e, v.astype(jnp.float32))
    return m, ev, jnp.sum(e, axis=-1)


def attend_prefill(cfg, q, k, v, *, causal: bool = True,
                   scale: float | None = None):
    """q: (B,S,H,hd); k,v: (B,T,KV,hd) → (B,S,H,hd).  ``scale``
    multiplies the scores (default ``hd ** -0.5``)."""
    B, S, H, hd = q.shape
    T, KV = k.shape[1], k.shape[2]
    G = H // KV
    scale = scale or 1.0 / math.sqrt(hd)
    qg = q.reshape(B, S, KV, G, hd)

    chunk = cfg.attn_chunk
    if S % chunk != 0 or T % chunk != 0 or S != T and causal:
        chunk = 0
    if chunk == 0 or S <= chunk:
        # single full block
        bias = None
        if causal:
            pos_q = jnp.arange(S)[:, None]
            pos_k = jnp.arange(T)[None, :]
            bias = jnp.where(pos_q >= pos_k, 0.0, -jnp.inf)[None, None, None]
        m, ev, l = _block_attn(qg, k, v, bias, scale)
        out = ev / jnp.moveaxis(l, -1, 1)[..., None]
        return out.reshape(B, S, H, hd).astype(q.dtype)

    nq, nk = S // chunk, T // chunk
    outs = []
    for i in range(nq):
        qi = qg[:, i * chunk:(i + 1) * chunk]
        n_kv = (i + 1) if causal else nk
        ks = k[:, :n_kv * chunk].reshape(B, n_kv, chunk, KV, hd).swapaxes(0, 1)
        vs = v[:, :n_kv * chunk].reshape(B, n_kv, chunk, KV, hd).swapaxes(0, 1)
        js = jnp.arange(n_kv)

        # diagonal-block causal bias (off-diagonal blocks are fully visible)
        pos_q = jnp.arange(chunk)[:, None]
        pos_k = jnp.arange(chunk)[None, :]
        tri = jnp.where(pos_q >= pos_k, 0.0, -jnp.inf)[None, None, None]

        def body(carry, xs, qi=qi, i=i, tri=tri):
            m_run, l_run, acc = carry
            kj, vj, j = xs
            bias = None
            if causal:
                bias = jnp.where(j == i, tri, 0.0)
            m_j, ev_j, l_j = _block_attn(qi, kj, vj, bias, scale)
            m_new = jnp.maximum(m_run, m_j)
            a_run = jnp.exp(m_run - m_new)
            a_j = jnp.exp(m_j - m_new)
            l_new = l_run * a_run + l_j * a_j
            # m/l are (B,KV,G,c); acc is (B,c,KV,G,hd)
            corr = jnp.moveaxis(a_run, -1, 1)[..., None]
            corr_j = jnp.moveaxis(a_j, -1, 1)[..., None]
            acc = acc * corr + ev_j * corr_j
            return (m_new, l_new, acc), None

        init = (jnp.full((B, KV, G, chunk), -jnp.inf, jnp.float32),
                jnp.zeros((B, KV, G, chunk), jnp.float32),
                jnp.zeros((B, chunk, KV, G, hd), jnp.float32))
        (m_f, l_f, acc), _ = jax.lax.scan(body, init, (ks, vs, js))
        out_i = acc / jnp.moveaxis(l_f, -1, 1)[..., None]
        outs.append(out_i)
    out = jnp.concatenate(outs, axis=1)
    return out.reshape(B, S, H, hd).astype(q.dtype)


# --------------------------------------------------------------------------- #
# Decode attention against a KV cache
# --------------------------------------------------------------------------- #
def attend_decode(cfg, q, k_cache, v_cache, pos, *,
                  scale: float | None = None):
    """q: (B,1,H,hd); caches: (B,Smax,KV,hd); pos: scalar index of the
    current token (cache already contains it)."""
    B, _, H, hd = q.shape
    KV = k_cache.shape[2]
    G = H // KV
    scale = scale or 1.0 / math.sqrt(hd)
    qg = q.reshape(B, KV, G, hd)
    s = jnp.einsum("bkgd,bskd->bkgs", qg, k_cache,
                   preferred_element_type=jnp.float32) * scale
    mask = jnp.arange(k_cache.shape[1]) <= pos
    s = jnp.where(mask[None, None, None], s, -jnp.inf)
    w = jax.nn.softmax(s, axis=-1)
    out = jnp.einsum("bkgs,bskd->bkgd", w, v_cache.astype(jnp.float32))
    return out.reshape(B, 1, H, hd).astype(q.dtype)


def cache_update(k_cache, v_cache, k_new, v_new, pos):
    """Insert (B,1,KV,hd) at position ``pos``; caches (B,Smax,KV,hd)."""
    k_cache = jax.lax.dynamic_update_slice(k_cache, k_new.astype(k_cache.dtype),
                                           (0, pos, 0, 0))
    v_cache = jax.lax.dynamic_update_slice(v_cache, v_new.astype(v_cache.dtype),
                                           (0, pos, 0, 0))
    return k_cache, v_cache
