"""Unified LM: dense / MoE / SSM / hybrid / VLM decoders + whisper enc-dec.

Layers are *scanned*: per-layer params are stacked on a leading ``layers``
axis and the forward pass is one ``lax.scan`` whose body is the layer —
HLO size and compile time are O(1) in depth, which is what makes 80-layer
× 512-device dry-runs tractable.  ``cfg.remat`` wraps the scan body in
``jax.checkpoint`` for training.

Caches (serving):
  dense/moe/vlm : {"k","v": (L,B,Smax,KV,hd), "pos"}
  ssm           : {"conv": (L,B,K-1,di), "h": (L,B,di,N), "pos"}
  hybrid        : ssm fields (mamba2 shapes) + {"ak","av": (A,B,Smax,KV,hd)},
                  one K/V slot per shared-block application
  encdec        : dense fields + {"ck","cv": (L,B,F,KV,hd)} cross-attn

``LMChain`` exposes the same layers as the chain of blocks the split
runtime cuts (``runtime.edge``): embed, one block per layer, head.
"""
from __future__ import annotations

import math
from dataclasses import dataclass
from typing import Callable

import jax
import jax.numpy as jnp

from ..sharding.api import kv_cache_names, shard
from .attention import (attend_decode, attend_prefill, attn_params,
                        cache_update, o_project, qkv_project)
from .common import (Builder, embed_lookup, embed_params, layer_norm,
                     lm_logits, rms_norm)
from .mlp import mlp, mlp_params, moe_mlp, moe_params
from .ssm import mamba1_block, mamba1_params, mamba2_block, mamba2_params


class StackedBuilder(Builder):
    """Prefix every leaf with a ``layers`` axis of size n."""

    def __init__(self, base: Builder, n: int):
        self.base, self.n = base, n
        self.dtype = base.dtype

    def leaf(self, path, shape, axes, *, init="normal", scale=None, dtype=None):
        if init == "normal" and scale is None:
            fan_in = shape[0] if len(shape) >= 2 else shape[-1]
            scale = 1.0 / math.sqrt(max(fan_in, 1))
        if callable(init):
            orig = init
            init = lambda k, s, d: jnp.broadcast_to(orig(k, s[1:], d), s)
        return self.base.leaf(path, (self.n, *shape), ("layers", *axes),
                              init=init, scale=scale, dtype=dtype)


# --------------------------------------------------------------------------- #
# Per-layer param defs
# --------------------------------------------------------------------------- #
def _norm_params(b, prefix, d, bias=False):
    p = {"scale": b.leaf(f"{prefix}.scale", (d,), ("embed",), init="ones")}
    if bias:
        p["bias"] = b.leaf(f"{prefix}.bias", (d,), ("embed",), init="zeros")
    return p


def _attn_block_params(b, cfg, prefix, with_mlp=True, bias_norm=False):
    p = {"ln1": _norm_params(b, f"{prefix}.ln1", cfg.d_model, bias_norm),
         "attn": attn_params(b, cfg, f"{prefix}.attn")}
    if with_mlp:
        p["ln2"] = _norm_params(b, f"{prefix}.ln2", cfg.d_model, bias_norm)
        p["mlp"] = mlp_params(b, cfg, f"{prefix}.mlp")
    return p


def layer_params(cfg, b: Builder) -> dict:
    fam = cfg.family
    if fam in ("dense", "vlm"):
        return _attn_block_params(b, cfg, "layer")
    if fam == "moe":
        return {"ln1": _norm_params(b, "layer.ln1", cfg.d_model),
                "attn": attn_params(b, cfg, "layer.attn"),
                "ln2": _norm_params(b, "layer.ln2", cfg.d_model),
                "moe": moe_params(b, cfg, "layer.moe")}
    if fam == "ssm":
        return {"ln": _norm_params(b, "layer.ln", cfg.d_model),
                "mamba": mamba1_params(b, cfg, "layer.mamba")}
    if fam == "hybrid":
        return {"ln": _norm_params(b, "layer.ln", cfg.d_model),
                "mamba": mamba2_params(b, cfg, "layer.mamba")}
    raise ValueError(fam)


def build_params(cfg, b: Builder) -> dict:
    embed, head = embed_params(b, cfg)
    params: dict = {"embed": embed,
                    "final_norm": _norm_params(b, "final_norm", cfg.d_model,
                                               cfg.family == "encdec")}
    if head is not None:
        params["lm_head"] = head

    if cfg.family == "encdec":
        enc = StackedBuilder(b, cfg.n_enc_layers)
        dec = StackedBuilder(b, cfg.n_layers)
        params["enc_layers"] = _attn_block_params(enc, cfg, "enc", bias_norm=True)
        params["dec_layers"] = {
            **_attn_block_params(dec, cfg, "dec", bias_norm=True),
            "ln_x": _norm_params(dec, "dec.ln_x", cfg.d_model, True),
            "xattn": attn_params(dec, cfg, "dec.xattn")}
        params["enc_final_norm"] = _norm_params(b, "enc_final_norm",
                                                cfg.d_model, True)
        return params

    sb = StackedBuilder(b, cfg.n_layers)
    params["layers"] = layer_params(cfg, sb)
    if cfg.family == "hybrid":
        params["shared"] = mem_block_params(
            StackedBuilder(b, cfg.n_mem_blocks), cfg, "shared")
        params["hybrid"] = app_params(StackedBuilder(b, cfg.n_attn_apps),
                                      cfg, "hybrid")
    return params


def mem_block_params(b, cfg, prefix: str) -> dict:
    """One Zamba2 shared (memory) block: RMSNorm over concat(hidden,
    embeddings), attention from that 2·d_model width, RMSNorm, gated MLP."""
    D, H, KV, hd, F = cfg.d_model, cfg.n_heads, cfg.n_kv_heads, cfg.hd, cfg.d_ff
    D2 = 2 * D
    return {
        "in_norm": _norm_params(b, f"{prefix}.in_norm", D2),
        "attn": {
            "wq": b.leaf(f"{prefix}.wq", (D2, H, hd), ("embed", "heads", "head_dim")),
            "wk": b.leaf(f"{prefix}.wk", (D2, KV, hd), ("embed", "kv_heads", "head_dim")),
            "wv": b.leaf(f"{prefix}.wv", (D2, KV, hd), ("embed", "kv_heads", "head_dim")),
            "wo": b.leaf(f"{prefix}.wo", (H, hd, D), ("heads", "head_dim", "embed")),
        },
        "ff_norm": _norm_params(b, f"{prefix}.ff_norm", D),
        "mlp": mlp_params(b, cfg, f"{prefix}.mlp"),
    }


def app_params(b, cfg, prefix: str) -> dict:
    """What one application of a shared block owns: the rank-r adapter of
    its MLP's gate and up projections, and the linear into the layer."""
    D, F, r = cfg.d_model, cfg.d_ff, cfg.adapter_rank
    return {"ad_in": b.leaf(f"{prefix}.ad_in", (D, r), ("embed", None)),
            "ad_gate": b.leaf(f"{prefix}.ad_gate", (r, F), (None, "ff")),
            "ad_up": b.leaf(f"{prefix}.ad_up", (r, F), (None, "ff")),
            "linear": b.leaf(f"{prefix}.linear", (D, D), ("embed", None))}


# --------------------------------------------------------------------------- #
# Blocks
# --------------------------------------------------------------------------- #
def _attn_mlp_block(cfg, p, x, positions, *, kv_cache=None, pos=None,
                    bias_norm=False, rope=True):
    """Standard transformer block.  Returns (x, new_kv or (k, v))."""
    norm = (lambda t, q: layer_norm(t, q["scale"], q["bias"], cfg.norm_eps)) \
        if bias_norm else (lambda t, q: rms_norm(t, q["scale"], cfg.norm_eps))
    h = norm(x, p["ln1"])
    q, k, v = qkv_project(cfg, p["attn"], h, positions, rope=rope)
    if kv_cache is not None:
        kc, vc = cache_update(*kv_cache, k, v, pos)
        o = attend_decode(cfg, q, kc, vc, pos)
        new_kv = (kc, vc)
    else:
        o = attend_prefill(cfg, q, k, v, causal=True)
        new_kv = (k, v)
    x = x + o_project(p["attn"], o)
    if "mlp" in p:
        h2 = norm(x, p["ln2"])
        x = x + mlp(cfg, p["mlp"], h2)
    return x, new_kv


def _moe_block(cfg, p, x, positions, *, kv_cache=None, pos=None):
    h = rms_norm(x, p["ln1"]["scale"], cfg.norm_eps)
    q, k, v = qkv_project(cfg, p["attn"], h, positions)
    if kv_cache is not None:
        kc, vc = cache_update(*kv_cache, k, v, pos)
        o = attend_decode(cfg, q, kc, vc, pos)
        new_kv = (kc, vc)
    else:
        o = attend_prefill(cfg, q, k, v, causal=True)
        new_kv = (k, v)
    x = x + o_project(p["attn"], o)
    h2 = rms_norm(x, p["ln2"]["scale"], cfg.norm_eps)
    from .mlp import moe_mlp_gshard
    moe_fn = moe_mlp_gshard if cfg.moe_impl == "gshard" else moe_mlp
    y, aux = moe_fn(cfg, p["moe"], h2)
    return x + y, new_kv, aux


def _ssm_block(cfg, p, x, cache=None, t=None):
    """A Mamba layer; ``t`` (hybrid) joins its input, not its residual."""
    h = rms_norm(x if t is None else x + t, p["ln"]["scale"], cfg.norm_eps)
    block = mamba1_block if cfg.family == "ssm" else mamba2_block
    y, new_cache = block(cfg, p["mamba"], h, cache)
    return x + y, new_cache


def mem_block(cfg, sp, ap, x, emb, positions, *, kv_cache=None, pos=None):
    """One application of a Zamba2 shared block (``sp``) with its own
    adapter and linear (``ap``): RMSNorm(concat(x, emb)) → attention
    (scores scaled by (hd/2)^-0.5) → RMSNorm → gated MLP → linear; no
    residual of its own.  → (what joins the layer's Mamba input, (k, v))."""
    eps = cfg.norm_eps
    h = rms_norm(jnp.concatenate([x, emb], axis=-1), sp["in_norm"]["scale"],
                 eps)
    q, k, v = qkv_project(cfg, sp["attn"], h, positions)
    scale = (cfg.hd / 2) ** -0.5
    if kv_cache is not None:
        kc, vc = cache_update(*kv_cache, k, v, pos)
        o = attend_decode(cfg, q, kc, vc, pos, scale=scale)
        new_kv = (kc, vc)
    else:
        o = attend_prefill(cfg, q, k, v, causal=True, scale=scale)
        new_kv = (k, v)
    h = rms_norm(o_project(sp["attn"], o), sp["ff_norm"]["scale"], eps)
    y = mlp(cfg, sp["mlp"], h, adapter=ap)
    return jnp.einsum("bsd,de->bse", y, ap["linear"]), new_kv


def _app(mem, a, n_mem_blocks):
    """(shared block, application params) of application ``a`` (traced)."""
    pick = lambda i: (lambda w: jax.lax.dynamic_index_in_dim(w, i, 0, False))
    return (jax.tree.map(pick(a % n_mem_blocks), mem["shared"]),
            jax.tree.map(pick(a), mem["hybrid"]))


def hybrid_layer(cfg, p_i, a, x, emb, positions, mem, *, cache=None,
                 akv=None, slot=None, pos=None):
    """A layer of the hybrid family: a Mamba-2 layer whose input gains
    shared-block application ``a``'s output (``a`` < 0: none, a plain
    Mamba-2 layer).  ``mem``: the stacked ``{"shared", "hybrid"}`` params.
    ``akv``: the (ak, av) K/V caches of every application, kept in slot
    ``slot``: prefill (``pos`` None) writes this prompt's k, v there,
    decode reads and extends them.  → (y, Mamba cache, akv)."""
    def with_block(args):
        x, akv = args
        sp, ap = _app(mem, a, cfg.n_mem_blocks)
        if akv is None:
            return mem_block(cfg, sp, ap, x, emb, positions)[0], None
        ak, av = akv
        if pos is None:
            t, (k, v) = mem_block(cfg, sp, ap, x, emb, positions)
            pad = ((0, 0), (0, ak.shape[2] - k.shape[1]), (0, 0), (0, 0))
            k, v = jnp.pad(k, pad), jnp.pad(v, pad)
        else:
            kv = tuple(jax.lax.dynamic_index_in_dim(c, slot, 0, False)
                       for c in akv)
            t, (k, v) = mem_block(cfg, sp, ap, x, emb, positions,
                                  kv_cache=kv, pos=pos)
        at = (slot, 0, 0, 0, 0)
        return t, (jax.lax.dynamic_update_slice(ak, k[None].astype(ak.dtype), at),
                   jax.lax.dynamic_update_slice(av, v[None].astype(av.dtype), at))

    def without(args):
        x, akv = args
        return jnp.zeros_like(x), akv
    t, akv = jax.lax.cond(a >= 0, with_block, without, (x, akv))
    y, nc = _ssm_block(cfg, p_i, x, cache=cache, t=t)
    return y, nc, akv


# --------------------------------------------------------------------------- #
# Decoder trunk (scan over layers), one function per execution mode
# --------------------------------------------------------------------------- #
def _maybe_remat(fn, cfg):
    return jax.checkpoint(fn) if cfg.remat else fn


def _shard_residual(x, cfg):
    """Layer-boundary residual constraint.  With ``cfg.seq_parallel`` the
    saved (remat) activations shard their seq dim over 'model'
    (Megatron-SP) — §Perf iteration 1: cuts checkpointed-activation
    memory by the TP degree and de-duplicates attention compute on archs
    whose head counts don't divide the TP axis."""
    return shard(x, "batch", "seq_sp" if cfg.seq_parallel else "seq",
                 "embed")


def _empty_kv(cfg, B, S):
    KV, hd = cfg.n_kv_heads, cfg.hd
    dt = jnp.bfloat16 if cfg.dtype == "bfloat16" else jnp.float32
    return (jnp.zeros((B, S, KV, hd), dt), jnp.zeros((B, S, KV, hd), dt))


def trunk_train(cfg, params, x, positions):
    """Returns (hidden, aux_loss)."""
    fam = cfg.family
    layers = params["layers"]

    if fam in ("dense", "vlm"):
        def body(c, p_i):
            c = _shard_residual(c, cfg)
            y, _ = _attn_mlp_block(cfg, p_i, c, positions)
            return _shard_residual(y, cfg), None
        x, _ = jax.lax.scan(_maybe_remat(body, cfg), x, layers)
        return x, 0.0

    if fam == "moe":
        def body(c, p_i):
            x, aux_sum = c
            x = _shard_residual(x, cfg)
            y, _, aux = _moe_block(cfg, p_i, x, positions)
            return (_shard_residual(y, cfg), aux_sum + aux), None
        (x, aux), _ = jax.lax.scan(_maybe_remat(body, cfg), (x, 0.0), layers)
        return x, aux / cfg.n_layers

    if fam == "ssm":
        def body(c, p_i):
            c = _shard_residual(c, cfg)
            y, _ = _ssm_block(cfg, p_i, c)
            return _shard_residual(y, cfg), None
        x, _ = jax.lax.scan(_maybe_remat(body, cfg), x, layers)
        return x, 0.0

    if fam == "hybrid":
        mem = {"shared": params["shared"], "hybrid": params["hybrid"]}
        emb = x

        def body(c, xs):
            p_i, a = xs
            c = _shard_residual(c, cfg)
            y, _, _ = hybrid_layer(cfg, p_i, a, c, emb, positions, mem)
            return _shard_residual(y, cfg), None
        apps = jnp.asarray(cfg.attn_apps, jnp.int32)
        x, _ = jax.lax.scan(_maybe_remat(body, cfg), x, (layers, apps))
        return x, 0.0

    raise ValueError(fam)


def trunk_prefill(cfg, params, x, positions, cache_len: int):
    """Returns (hidden, cache).  ``cache_len >= S`` (cache pre-padded)."""
    fam = cfg.family
    layers = params["layers"]
    B, S, _ = x.shape
    pad = cache_len - S

    def pad_kv(k, v):
        if pad == 0:
            return k, v
        pk = jnp.zeros((B, pad, *k.shape[2:]), k.dtype)
        return (jnp.concatenate([k, pk], 1), jnp.concatenate([v, pk], 1))

    if fam in ("dense", "vlm", "moe"):
        blk = _attn_mlp_block if fam != "moe" else None

        def body(c, p_i):
            if fam == "moe":
                y, (k, v), _ = _moe_block(cfg, p_i, c, positions)
            else:
                y, (k, v) = _attn_mlp_block(cfg, p_i, c, positions)
            return y, pad_kv(k, v)
        x, (ks, vs) = jax.lax.scan(body, x, layers)
        names = kv_cache_names(cfg.n_kv_heads, cfg.hd)
        cache = {"k": shard(ks, *names), "v": shard(vs, *names),
                 "pos": jnp.int32(S)}
        return x, cache

    if fam == "ssm":
        def body(c, p_i):
            y, nc = _ssm_block(cfg, p_i, c)
            return y, nc
        x, caches = jax.lax.scan(body, x, layers)
        return x, {**caches, "pos": jnp.int32(S)}

    if fam == "hybrid":
        mem = {"shared": params["shared"], "hybrid": params["hybrid"]}
        emb = x
        akv = tuple(jnp.zeros((cfg.n_attn_apps, B, cache_len, cfg.n_kv_heads,
                               cfg.hd), x.dtype) for _ in range(2))

        def body(carry, xs):
            c, akv = carry
            p_i, a = xs
            y, nc, akv = hybrid_layer(cfg, p_i, a, c, emb, positions, mem,
                                      akv=akv, slot=a)
            return (y, akv), nc
        apps = jnp.asarray(cfg.attn_apps, jnp.int32)
        (x, (ak, av)), caches = jax.lax.scan(body, (x, akv), (layers, apps))
        return x, {**caches, "ak": ak, "av": av, "pos": jnp.int32(S)}

    raise ValueError(fam)


def trunk_decode(cfg, params, x, cache):
    """x: (B,1,D); returns (hidden, new_cache)."""
    fam = cfg.family
    layers = params["layers"]
    pos = cache["pos"]
    positions = pos[None]  # (1,)

    if fam in ("dense", "vlm", "moe"):
        def body(c, xs):
            p_i, k_i, v_i = xs
            if fam == "moe":
                y, (k, v), _ = _moe_block(cfg, p_i, c, positions,
                                          kv_cache=(k_i, v_i), pos=pos)
            else:
                y, (k, v) = _attn_mlp_block(cfg, p_i, c, positions,
                                            kv_cache=(k_i, v_i), pos=pos)
            return y, (k, v)
        x, (ks, vs) = jax.lax.scan(body, x, (layers, cache["k"], cache["v"]))
        return x, {"k": ks, "v": vs, "pos": pos + 1}

    if fam == "ssm":
        def body(c, xs):
            p_i, cc = xs
            y, nc = _ssm_block(cfg, p_i, c, cache=cc)
            return y, nc
        sub = {k: cache[k] for k in ("conv", "h")}
        x, new = jax.lax.scan(body, x, (layers, sub))
        return x, {**new, "pos": pos + 1}

    if fam == "hybrid":
        mem = {"shared": params["shared"], "hybrid": params["hybrid"]}
        emb = x

        def body(carry, xs):
            c, akv = carry
            p_i, cc, a = xs
            y, nc, akv = hybrid_layer(cfg, p_i, a, c, emb, positions, mem,
                                      cache=cc, akv=akv, slot=a, pos=pos)
            return (y, akv), nc
        sub = {k: cache[k] for k in ("conv", "h")}
        apps = jnp.asarray(cfg.attn_apps, jnp.int32)
        (x, (ak, av)), new = jax.lax.scan(
            body, (x, (cache["ak"], cache["av"])), (layers, sub, apps))
        return x, {**new, "ak": ak, "av": av, "pos": pos + 1}

    raise ValueError(fam)


# --------------------------------------------------------------------------- #
# Embedding entry points (vlm merges patch embeds)
# --------------------------------------------------------------------------- #
def embed_inputs(cfg, params, inputs: dict):
    tok = embed_lookup(params["embed"]["table"], inputs["tokens"])
    if cfg.family == "vlm":
        img = inputs["img"].astype(tok.dtype)           # (B, P, D) stub
        img = shard(img, "batch", "patches", "embed")
        tok = jnp.concatenate([img, tok], axis=1)
    return tok


def final_hidden(cfg, params, x):
    fn = params["final_norm"]
    if cfg.family == "encdec":
        return layer_norm(x, fn["scale"], fn["bias"], cfg.norm_eps)
    return rms_norm(x, fn["scale"], cfg.norm_eps)


# --------------------------------------------------------------------------- #
# Whisper enc-dec
# --------------------------------------------------------------------------- #
def encode(cfg, params, frames):
    """frames: (B, F, D) stub conv-frontend output → encoder hidden."""
    x = frames.astype(jnp.bfloat16 if cfg.dtype == "bfloat16" else jnp.float32)
    x = shard(x, "batch", "frames", "embed")
    positions = jnp.arange(x.shape[1])

    def body(c, p_i):
        norm = lambda t, q: layer_norm(t, q["scale"], q["bias"], cfg.norm_eps)
        c = _shard_residual(c, cfg)
        h = norm(c, p_i["ln1"])
        q, k, v = qkv_project(cfg, p_i["attn"], h, positions)
        o = attend_prefill(cfg, q, k, v, causal=False)
        c = c + o_project(p_i["attn"], o)
        h2 = norm(c, p_i["ln2"])
        return _shard_residual(c + mlp(cfg, p_i["mlp"], h2), cfg), None
    x, _ = jax.lax.scan(_maybe_remat(body, cfg), x, params["enc_layers"])
    fn = params["enc_final_norm"]
    return layer_norm(x, fn["scale"], fn["bias"], cfg.norm_eps)


def _dec_layer(cfg, p_i, c, enc_or_ckv, positions, kv_cache=None, pos=None):
    norm = lambda t, q: layer_norm(t, q["scale"], q["bias"], cfg.norm_eps)
    c, new_kv = _attn_mlp_block(
        cfg, {"ln1": p_i["ln1"], "attn": p_i["attn"]}, c, positions,
        kv_cache=kv_cache, pos=pos, bias_norm=True)
    # cross-attention
    h = norm(c, p_i["ln_x"])
    q = jnp.einsum("bsd,dhk->bshk", h, p_i["xattn"]["wq"])
    if isinstance(enc_or_ckv, tuple):                    # cached cross k/v
        ck, cv = enc_or_ckv
    else:
        ck = jnp.einsum("bfd,dhk->bfhk", enc_or_ckv, p_i["xattn"]["wk"])
        cv = jnp.einsum("bfd,dhk->bfhk", enc_or_ckv, p_i["xattn"]["wv"])
    o = attend_prefill(cfg, q, ck, cv, causal=False)
    c = c + o_project(p_i["xattn"], o)
    h2 = norm(c, p_i["ln2"])
    c = c + mlp(cfg, p_i["mlp"], h2)
    return c, new_kv, (ck, cv)


def decoder_train(cfg, params, tokens, enc_hidden):
    x = embed_lookup(params["embed"]["table"], tokens)
    positions = jnp.arange(tokens.shape[1])

    def body(c, p_i):
        c = _shard_residual(c, cfg)
        y, _, _ = _dec_layer(cfg, p_i, c, enc_hidden, positions)
        return _shard_residual(y, cfg), None
    x, _ = jax.lax.scan(_maybe_remat(body, cfg), x, params["dec_layers"])
    return final_hidden(cfg, params, x)


def decoder_prefill(cfg, params, tokens, enc_hidden, cache_len: int):
    B, S = tokens.shape
    x = embed_lookup(params["embed"]["table"], tokens)
    positions = jnp.arange(S)
    pad = cache_len - S

    def body(c, p_i):
        y, (k, v), (ck, cv) = _dec_layer(cfg, p_i, c, enc_hidden, positions)
        if pad:
            z = jnp.zeros((B, pad, *k.shape[2:]), k.dtype)
            k, v = jnp.concatenate([k, z], 1), jnp.concatenate([v, z], 1)
        return y, (k, v, ck, cv)
    x, (ks, vs, cks, cvs) = jax.lax.scan(body, x, params["dec_layers"])
    cache = {"k": ks, "v": vs, "ck": cks, "cv": cvs, "pos": jnp.int32(S)}
    return final_hidden(cfg, params, x), cache


def decoder_decode(cfg, params, token, cache):
    x = embed_lookup(params["embed"]["table"], token)
    pos = cache["pos"]
    positions = pos[None]

    def body(c, xs):
        p_i, k_i, v_i, ck_i, cv_i = xs
        y, (k, v), _ = _dec_layer(cfg, p_i, c, (ck_i, cv_i), positions,
                                  kv_cache=(k_i, v_i), pos=pos)
        return y, (k, v)
    x, (ks, vs) = jax.lax.scan(body, x, (params["dec_layers"], cache["k"],
                                         cache["v"], cache["ck"], cache["cv"]))
    new = {"k": ks, "v": vs, "ck": cache["ck"], "cv": cache["cv"],
           "pos": pos + 1}
    return final_hidden(cfg, params, x), new


# --------------------------------------------------------------------------- #
# Top-level model entry points
# --------------------------------------------------------------------------- #
def forward_train(cfg, params, inputs: dict):
    """→ (logits fp32, aux_loss)."""
    if cfg.family == "encdec":
        enc = encode(cfg, params, inputs["frames"])
        x = decoder_train(cfg, params, inputs["tokens"], enc)
        return lm_logits(x, params["embed"], params.get("lm_head")), 0.0
    x = embed_inputs(cfg, params, inputs)
    positions = jnp.arange(x.shape[1])
    x, aux = trunk_train(cfg, params, x, positions)
    x = final_hidden(cfg, params, x)
    return lm_logits(x, params["embed"], params.get("lm_head")), aux


def forward_prefill(cfg, params, inputs: dict, cache_len: int | None = None):
    """→ (last-token logits fp32, cache)."""
    if cfg.family == "encdec":
        enc = encode(cfg, params, inputs["frames"])
        S = inputs["tokens"].shape[1]
        x, cache = decoder_prefill(cfg, params, inputs["tokens"], enc,
                                   cache_len or S)
        logits = lm_logits(x[:, -1:], params["embed"], params.get("lm_head"))
        return logits, cache
    x = embed_inputs(cfg, params, inputs)
    S = x.shape[1]
    positions = jnp.arange(S)
    x, cache = trunk_prefill(cfg, params, x, positions, cache_len or S)
    x = final_hidden(cfg, params, x)
    logits = lm_logits(x[:, -1:], params["embed"], params.get("lm_head"))
    return logits, cache


def forward_decode(cfg, params, token, cache):
    """token: (B,1) int32 → (logits fp32 (B,1,V), new cache)."""
    if cfg.family == "encdec":
        x, new = decoder_decode(cfg, params, token, cache)
        return lm_logits(x, params["embed"], params.get("lm_head")), new
    x = embed_lookup(params["embed"]["table"], token)
    x, new = trunk_decode(cfg, params, x, cache)
    x = final_hidden(cfg, params, x)
    return lm_logits(x, params["embed"], params.get("lm_head")), new


# --------------------------------------------------------------------------- #
# The model as a chain of blocks, for the split runtime
# --------------------------------------------------------------------------- #
@dataclass(frozen=True)
class ChainBlock:
    """One block of an ``LMChain``: ``apply(p, state) -> state``."""

    apply: Callable


class LMChain:
    """A hybrid decoder (Zamba2) as the chain of blocks that
    ``EdgePipeline`` and ``Worker`` cut and run, as they do a
    ``CNNModel``: ``embed`` (token ids (B, S) → state), one block per
    layer (state → state) and ``head`` (state → last-position logits
    (B, V), float32).  The state between blocks is ``{"residual": (B, S,
    D), "embed": (B, S, D)}``: the residual stream and the original
    embeddings the shared blocks read, so a hop carries both.  Blocks
    are named as in ``arch_block_graph``."""

    def __init__(self, cfg):
        if cfg.family != "hybrid":
            raise ValueError(f"no block chain for the {cfg.family!r} family")
        self.cfg = cfg
        self.name = cfg.name
        self.blocks = [("embed", ChainBlock(self._embed)),
                       *((f"layer{i:03d}", ChainBlock(self._layer))
                         for i in range(cfg.n_layers)),
                       ("head", ChainBlock(self._head))]

    def block_params(self, params) -> list:
        """Per-block params from ``build_params``'s stacked layout.  Every
        application of one shared block holds the same arrays."""
        cfg = self.cfg
        at = lambda tree, i: jax.tree.map(lambda w: w[i], tree)
        out: list = [{"table": params["embed"]["table"]}]
        shared = [at(params["shared"], m) for m in range(cfg.n_mem_blocks)]
        for i, a in enumerate(cfg.attn_apps):
            p = {"layer": at(params["layers"], i)}
            if a >= 0:
                p["shared"] = shared[a % cfg.n_mem_blocks]
                p["hybrid"] = at(params["hybrid"], a)
            out.append(p)
        head = {"norm": params["final_norm"], "embed": params["embed"]}
        if "lm_head" in params:
            head["lm_head"] = params["lm_head"]
        out.append(head)
        return out

    def apply(self, params, tokens):
        """The whole chain: block params (``block_params``) → logits."""
        x = tokens
        for (_, blk), p in zip(self.blocks, params):
            x = blk.apply(p, x)
        return x

    def _embed(self, p, tokens):
        x = embed_lookup(p["table"], tokens)
        return {"residual": x, "embed": x}

    def _layer(self, p, state):
        cfg, x, t = self.cfg, state["residual"], None
        if "shared" in p:
            t, _ = mem_block(cfg, p["shared"], p["hybrid"], x,
                             state["embed"], jnp.arange(x.shape[1]))
        y, _ = _ssm_block(cfg, p["layer"], x, t=t)
        return {**state, "residual": y}

    def _head(self, p, state):
        x = state["residual"][:, -1:]
        h = rms_norm(x, p["norm"]["scale"], self.cfg.norm_eps)
        return lm_logits(h, p["embed"], p.get("lm_head"))[:, 0]
