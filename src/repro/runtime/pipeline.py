"""Multi-pod pipeline parallelism — ParetoPipe's split, scaled to pods.

The ``pod`` mesh axis is the pipeline axis: the partitioner
(``repro.core``) assigns a contiguous layer range to each pod (cuts may
be *uneven* — that is the paper's entire point), activations cross pods
over DCN via ``lax.ppermute`` inside a partial-manual ``shard_map``
(manual over ``pod``; ``data``/``model`` stay GSPMD-auto inside each
stage), and training uses GPipe microbatching so the per-step bubble is
(K-1)/(M+K-1).

Uneven stages: per-stage layer stacks are padded to the max stage depth;
pad layers compute-then-passthrough (``where(li < count, y, x)``) so the
program stays SPMD-uniform.  The same repacking implements *elastic*
re-splits: checkpoints store the canonical (L, ...) stacked layout and
``repack_params`` reshapes to any cut vector on load.

Schedule (train, K stages, M microbatches, T = M+K-1 ticks):
  tick t: every pod applies its stage to its buffer; results ppermute to
  the next pod; pod 0 injects microbatch t+1.  Output microbatches are
  collected from the last pod (out_specs P('pod') + host-side slice) —
  exactly Alg. 1's worker→orchestrator return, at pod scale.

Relation to the hop Transport API (``runtime.transport``): here the
"transport" is the ``ppermute`` collective itself — XLA owns the wire,
so per-hop cost is modeled by the DCN ``Link`` in the pod scenarios
rather than recorded per transfer.  Folding these collectives in as a
registered transport (so pod hops emit ``TransferRecord``s too) is the
ROADMAP's "DCN at pod scale" follow-on.
"""
from __future__ import annotations

import dataclasses
from dataclasses import dataclass
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding, PartitionSpec as P

from ..models import lm
from ..models.common import Builder, cross_entropy, embed_lookup, lm_logits
from ..sharding.api import shard
from ..optim import OptConfig, apply_gradients


# --------------------------------------------------------------------------- #
# Stage layout / param repacking
# --------------------------------------------------------------------------- #
@dataclass(frozen=True)
class PipelineConfig:
    n_stages: int
    microbatches: int
    cuts: tuple[int, ...]            # interior layer cuts, len = n_stages-1

    @staticmethod
    def even(n_layers: int, n_stages: int, microbatches: int) -> "PipelineConfig":
        base = n_layers // n_stages
        rem = n_layers % n_stages
        counts = [base + (1 if i < rem else 0) for i in range(n_stages)]
        cuts = tuple(np.cumsum(counts)[:-1].tolist())
        return PipelineConfig(n_stages, microbatches, cuts)

    def layout(self, n_layers: int):
        """→ (starts (K,), counts (K,), l_max)."""
        bounds = (0, *self.cuts, n_layers)
        starts = np.array(bounds[:-1])
        counts = np.diff(bounds)
        if (counts < 0).any():
            raise ValueError(f"bad cuts {self.cuts}")
        return starts, counts, int(counts.max())


class PipelineBuilder(Builder):
    """Declares layer leaves in (n_stages, l_max, ...) layout."""

    def __init__(self, base: Builder, pcfg: PipelineConfig, n_layers: int):
        self.base, self.pcfg = base, pcfg
        _, _, self.l_max = pcfg.layout(n_layers)
        self.dtype = base.dtype

    def leaf(self, path, shape, axes, *, init="normal", scale=None, dtype=None):
        import math
        if init == "normal" and scale is None:
            fan_in = shape[0] if len(shape) >= 2 else shape[-1]
            scale = 1.0 / math.sqrt(max(fan_in, 1))
        if callable(init):
            orig = init
            init = lambda k, s, d: jnp.broadcast_to(orig(k, s[2:], d), s)
        return self.base.leaf(path, (self.pcfg.n_stages, self.l_max, *shape),
                              ("stage", "layers", *axes), init=init,
                              scale=scale, dtype=dtype)


def build_pipeline_params(cfg, b: Builder, pcfg: PipelineConfig) -> dict:
    """Same structure as lm.build_params but layers in pipeline layout."""
    from ..models.common import embed_params
    from ..models.lm import _attn_block_params, _norm_params, layer_params
    embed, head = embed_params(b, cfg)
    params: dict = {"embed": embed,
                    "final_norm": _norm_params(b, "final_norm", cfg.d_model,
                                               cfg.family == "encdec")}
    if head is not None:
        params["lm_head"] = head
    if cfg.family == "encdec":
        # encoder stays replicated (small); decoder layers are pipelined
        from ..models.lm import StackedBuilder
        enc = StackedBuilder(b, cfg.n_enc_layers)
        params["enc_layers"] = _attn_block_params(enc, cfg, "enc",
                                                  bias_norm=True)
        params["enc_final_norm"] = _norm_params(b, "enc_final_norm",
                                                cfg.d_model, True)
        from ..models.attention import attn_params
        pb = PipelineBuilder(b, pcfg, cfg.n_layers)
        params["dec_layers"] = {
            **_attn_block_params(pb, cfg, "dec", bias_norm=True),
            "ln_x": _norm_params(pb, "dec.ln_x", cfg.d_model, True),
            "xattn": attn_params(pb, cfg, "dec.xattn")}
        return params
    pb = PipelineBuilder(b, pcfg, cfg.n_layers)
    params["layers"] = layer_params(cfg, pb)
    if cfg.family == "hybrid":
        params["shared"] = _attn_block_params(b, cfg, "shared")
    return params


def repack_params(stacked_layers, pcfg: PipelineConfig, n_layers: int):
    """(L, ...) canonical → (K, l_max, ...) pipeline layout (zero-padded)."""
    starts, counts, l_max = pcfg.layout(n_layers)

    def repack(leaf):
        out = jnp.zeros((pcfg.n_stages, l_max, *leaf.shape[1:]), leaf.dtype)
        for s in range(pcfg.n_stages):
            blk = leaf[starts[s]:starts[s] + counts[s]]
            out = out.at[s, :counts[s]].set(blk)
        return out
    return jax.tree.map(repack, stacked_layers)


def init_pipeline_state(cfg, pcfg: PipelineConfig, params, mesh) -> dict:
    """Canonical params → the pipelined train state, placed on ``mesh``:
    layer stacks repacked to (K, l_max, ...) and sharded over ``pod``,
    everything else laid out by the sharding rules, and the AdamW
    moments created in place.  No device ever holds the whole state, so
    a model whose optimizer state outgrows one chip still starts."""
    from ..models.common import SpecBuilder
    from ..optim import init_opt_state
    from ..sharding.api import MeshContext
    lk = "dec_layers" if cfg.family == "encdec" else "layers"
    specs = build_pipeline_params(cfg, SpecBuilder(MeshContext(mesh)), pcfg)
    placed = jax.tree.map(lambda s: NamedSharding(mesh, s), specs,
                          is_leaf=lambda s: isinstance(s, P))
    rep = NamedSharding(mesh, P())

    def make(params):
        p = {**params, lk: repack_params(params[lk], pcfg, cfg.n_layers)}
        return {"params": p, "opt": init_opt_state(p),
                "step": jnp.zeros((), jnp.int32)}
    out = {"params": placed,
           "opt": {"m": placed, "v": placed, "count": rep}, "step": rep}
    return jax.jit(make, out_shardings=out)(params)


def unpack_params(pipeline_layers, pcfg: PipelineConfig, n_layers: int):
    """Inverse of repack_params (for elastic resharding / checkpoints)."""
    starts, counts, _ = pcfg.layout(n_layers)

    def unpack(leaf):
        parts = [leaf[s, :counts[s]] for s in range(pcfg.n_stages)]
        return jnp.concatenate(parts, axis=0)
    return jax.tree.map(unpack, pipeline_layers)


# --------------------------------------------------------------------------- #
# Per-stage layer application (generic across families)
# --------------------------------------------------------------------------- #
def _layer_fn_train(cfg, p_i, x, positions, gidx, shared, enc_hidden):
    from ..models.lm import _attn_mlp_block, _moe_block, _ssm_block, _dec_layer
    if cfg.family in ("dense", "vlm"):
        y, _ = _attn_mlp_block(cfg, p_i, x, positions)
        return y
    if cfg.family == "moe":
        y, _, _ = _moe_block(cfg, p_i, x, positions)   # aux dropped (note)
        return y
    if cfg.family == "ssm":
        y, _ = _ssm_block(cfg, p_i, x)
        return y
    if cfg.family == "hybrid":
        def with_attn(t):
            y, _ = _attn_mlp_block(cfg, shared, t, positions)
            return y
        x = jax.lax.cond(gidx % cfg.shared_attn_every == 0, with_attn,
                         lambda t: t, x)
        y, _ = _ssm_block(cfg, p_i, x)
        return y
    if cfg.family == "encdec":
        y, _, _ = _dec_layer(cfg, p_i, x, enc_hidden, positions)
        return y
    raise ValueError(cfg.family)


def _stage_apply(cfg, stage_layers, x, positions, start, count, shared,
                 enc_hidden, l_max):
    """Run this pod's layer slice (padded to l_max) on x."""
    from ..models.lm import _shard_residual

    def body(c, xs):
        p_i, li = xs
        c = _shard_residual(c, cfg)
        y = _layer_fn_train(cfg, p_i, c, positions, start + li, shared,
                            enc_hidden)
        c = jnp.where(li < count, y, c)
        return _shard_residual(c, cfg), None
    fn = jax.checkpoint(body) if cfg.remat else body
    x, _ = jax.lax.scan(fn, x, (stage_layers, jnp.arange(l_max)))
    return x


# --------------------------------------------------------------------------- #
# Pipelined train step
# --------------------------------------------------------------------------- #
def make_pipeline_train_step(cfg, pcfg: PipelineConfig, opt: OptConfig,
                             mesh):
    K, M = pcfg.n_stages, pcfg.microbatches
    starts_np, counts_np, l_max = pcfg.layout(cfg.n_layers)
    T = M + K - 1
    perm = [(p, p + 1) for p in range(K - 1)]

    def loss_fn(params, batch):
        # ---- embedding / frontend (replicated across pods, cheap) ----- #
        inputs = {k: v for k, v in batch.items() if k != "targets"}
        enc_hidden = None
        if cfg.family == "encdec":
            enc_hidden = lm.encode(cfg, params, inputs["frames"])
            x = embed_lookup(params["embed"]["table"], inputs["tokens"])
        else:
            x = lm.embed_inputs(cfg, params, inputs)
        B, S, D = x.shape
        assert B % M == 0, f"batch {B} % microbatches {M}"
        mb = B // M
        positions = jnp.arange(S)
        x_mb = x.reshape(M, mb, S, D)

        layers = params["dec_layers"] if cfg.family == "encdec" \
            else params["layers"]
        starts = jnp.asarray(starts_np)
        counts = jnp.asarray(counts_np)
        dtype = x.dtype

        # Pod-replicated tensors enter the shard_map as fp32: JAX psums
        # their cotangents over the manual 'pod' axis in the boundary
        # dtype, and a bf16 all-reduce trips an XLA:CPU AllReducePromotion
        # crash (add+copy reduction).  fp32 at the boundary sidesteps it
        # and is also numerically safer for gradient accumulation.
        x_mb32 = x_mb.astype(jnp.float32)
        enc_mb32 = None
        if enc_hidden is not None:
            F = enc_hidden.shape[1]
            enc_mb32 = enc_hidden.reshape(M, mb, F, D).astype(jnp.float32)
        shared32 = jax.tree.map(
            lambda a: a.astype(jnp.float32) if a.dtype == jnp.bfloat16 else a,
            params.get("shared"))

        def pipeline(stage_layers, x_mb32, enc_mb32, shared32):
            sid = jax.lax.axis_index("pod")
            stage_layers = jax.tree.map(lambda l: l[0], stage_layers)
            start, count = starts[sid], counts[sid]
            x_mb = x_mb32.astype(dtype)
            shared = jax.tree.map(
                lambda a: a.astype(dtype) if a.dtype == jnp.float32
                and dtype != jnp.float32 else a, shared32) \
                if shared32 is not None else None

            def tick(buf, t):
                buf = shard(buf, "batch", "seq", "embed")
                enc_i = None
                if enc_mb32 is not None:
                    # pod `sid` is processing microbatch (t - sid)
                    mi = jnp.clip(t - sid, 0, M - 1)
                    enc_i = jax.lax.dynamic_index_in_dim(
                        enc_mb32, mi, 0, keepdims=False).astype(dtype)
                y = _stage_apply(cfg, stage_layers, buf, positions, start,
                                 count, shared, enc_i, l_max)
                nxt = jax.lax.ppermute(y, "pod", perm) if K > 1 else y
                idx = jnp.minimum(t + 1, M - 1)
                inj = jax.lax.dynamic_index_in_dim(x_mb, idx, 0,
                                                   keepdims=False)
                buf = jnp.where(sid == 0, inj, nxt)
                return buf, y

            buf0 = jnp.where(sid == 0, x_mb[0], jnp.zeros((mb, S, D), dtype))
            _, ys = jax.lax.scan(tick, buf0, jnp.arange(T))
            return ys[None]                       # (1, T, mb, S, D) per pod

        ys = jax.shard_map(
            pipeline, mesh=mesh,
            in_specs=(P("pod"), P(), P(), P()), out_specs=P("pod"),
            axis_names={"pod"}, check_vma=False,
        )(layers, x_mb32, enc_mb32, shared32)
        # finished microbatches come off the last pod at ticks K-1 .. T-1
        out = ys[K - 1][K - 1:]                    # (M, mb, S, D)
        h = out.reshape(B, S, D)
        h = lm.final_hidden(cfg, params, h)
        from ..models.common import chunked_cross_entropy
        ce = chunked_cross_entropy(h, params["embed"],
                                   params.get("lm_head"),
                                   batch["targets"], cfg.ce_chunk)
        return ce, {"ce": ce}

    def train_step(state, batch):
        (loss, parts), grads = jax.value_and_grad(
            lambda p: loss_fn(p, batch), has_aux=True)(state["params"])
        new_params, opt_state, om = apply_gradients(state["params"], grads,
                                                    state["opt"], opt)
        return ({"params": new_params, "opt": opt_state,
                 "step": state["step"] + 1},
                {"loss": loss, **parts, **om})

    return train_step


# --------------------------------------------------------------------------- #
# Pipelined serving steps (prefill / decode)
# --------------------------------------------------------------------------- #
def make_pipeline_prefill_step(cfg, pcfg: PipelineConfig, mesh,
                               cache_len: int | None = None):
    """Single-shot prefill: the request batch flows stage→stage (K ticks);
    each stage fills its local KV/SSM cache slice.  Returns the pipeline-
    layout cache: leaves (K, l_max, B, ...)."""
    K = pcfg.n_stages
    starts_np, counts_np, l_max = pcfg.layout(cfg.n_layers)
    perm = [(p, p + 1) for p in range(K - 1)]

    def prefill(params, inputs):
        enc_hidden = None
        if cfg.family == "encdec":
            enc_hidden = lm.encode(cfg, params, inputs["frames"])
            x = embed_lookup(params["embed"]["table"], inputs["tokens"])
        else:
            x = lm.embed_inputs(cfg, params, inputs)
        B, S, D = x.shape
        positions = jnp.arange(S)
        clen = cache_len or S
        layers = params["dec_layers"] if cfg.family == "encdec" \
            else params["layers"]
        shared = params.get("shared")
        starts = jnp.asarray(starts_np)
        counts = jnp.asarray(counts_np)

        def pipeline(stage_layers, x):
            sid = jax.lax.axis_index("pod")
            stage_layers = jax.tree.map(lambda l: l[0], stage_layers)
            start, count = starts[sid], counts[sid]

            def tick(carry, t):
                buf, cache = carry
                y, new_cache = _stage_prefill(cfg, stage_layers, buf,
                                              positions, start, count,
                                              shared, enc_hidden, l_max, clen)
                # commit this stage's cache only on its own tick (the tick
                # when its buffer holds real data: t == stage id)
                cache = jax.tree.map(
                    lambda old, new: jnp.where(t == sid, new, old),
                    cache, new_cache)
                nxt = jax.lax.ppermute(y, "pod", perm) if K > 1 else y
                return (nxt, cache), y

            cache0 = _empty_stage_cache(cfg, l_max, B, clen, x.dtype)
            (_, cache), ys = jax.lax.scan(tick, (x, cache0), jnp.arange(K))
            last = ys[K - 1]
            return jax.tree.map(lambda c: c[None], (last, cache))

        last, cache = jax.shard_map(
            pipeline, mesh=mesh, in_specs=(P("pod"), P()),
            out_specs=P("pod"), axis_names={"pod"}, check_vma=False,
        )(layers, x)
        h = lm.final_hidden(cfg, params, last[K - 1])
        logits = lm_logits(h[:, -1:], params["embed"], params.get("lm_head"))
        cache = dict(cache, pos=jnp.int32(S))
        return jnp.argmax(logits, -1).astype(jnp.int32), cache
    return prefill


def make_pipeline_decode_step(cfg, pcfg: PipelineConfig, mesh):
    """One decode tick through the pod pipeline: the (B,1) token embeds on
    pod 0, flows K stages, logits emerge from the last pod.  Cache leaves
    are pipeline-layout (K, l_max, B, ...) sharded P('pod')."""
    K = pcfg.n_stages
    starts_np, counts_np, l_max = pcfg.layout(cfg.n_layers)
    perm = [(p, p + 1) for p in range(K - 1)]

    def decode(params, token, cache):
        pos = cache["pos"]
        x = embed_lookup(params["embed"]["table"], token)
        layers = params["dec_layers"] if cfg.family == "encdec" \
            else params["layers"]
        shared = params.get("shared")
        starts = jnp.asarray(starts_np)
        counts = jnp.asarray(counts_np)
        kv = {k: v for k, v in cache.items() if k != "pos"}

        def pipeline(stage_layers, kv, x):
            sid = jax.lax.axis_index("pod")
            stage_layers = jax.tree.map(lambda l: l[0], stage_layers)
            kv = jax.tree.map(lambda l: l[0], kv)
            start, count = starts[sid], counts[sid]

            def tick(carry, t):
                buf, cache = carry
                y, new_cache = _stage_decode(cfg, stage_layers, buf, cache,
                                             pos, start, count, shared, l_max)
                cache = jax.tree.map(
                    lambda old, new: jnp.where(t == sid, new, old),
                    cache, new_cache)
                nxt = jax.lax.ppermute(y, "pod", perm) if K > 1 else y
                return (nxt, cache), y

            (_, kv), ys = jax.lax.scan(tick, (x, kv), jnp.arange(K))
            return jax.tree.map(lambda c: c[None], (ys[K - 1], kv))

        last, kv = jax.shard_map(
            pipeline, mesh=mesh, in_specs=(P("pod"), P("pod"), P()),
            out_specs=P("pod"), axis_names={"pod"}, check_vma=False,
        )(layers, kv, x)
        h = lm.final_hidden(cfg, params, last[K - 1])
        logits = lm_logits(h, params["embed"], params.get("lm_head"))
        new_cache = dict(kv, pos=pos + 1)
        return jnp.argmax(logits, -1).astype(jnp.int32), new_cache
    return decode


def _stage_decode(cfg, stage_layers, x, cache, pos, start, count, shared,
                  l_max):
    from ..models.lm import _attn_mlp_block, _moe_block, _ssm_block
    positions = pos[None]

    def body(c, xs):
        p_i, cc, li = xs
        gidx = start + li
        if cfg.family in ("dense", "vlm", "moe", "encdec"):
            kvp = (cc["k"], cc["v"])
            if cfg.family == "moe":
                y, (k, v), _ = _moe_block(cfg, p_i, c, positions,
                                          kv_cache=kvp, pos=pos)
            elif cfg.family == "encdec":
                from ..models.lm import _dec_layer
                y, (k, v), _ = _dec_layer(cfg, p_i, c, (cc["ck"], cc["cv"]),
                                          positions, kv_cache=kvp, pos=pos)
            else:
                y, (k, v) = _attn_mlp_block(cfg, p_i, c, positions,
                                            kv_cache=kvp, pos=pos)
            cache_i = dict(cc, k=k, v=v)
        elif cfg.family == "ssm":
            y, nc = _ssm_block(cfg, p_i, c, cache=cc)
            cache_i = nc
        else:
            raise ValueError(cfg.family)
        y = jnp.where(li < count, y, c)
        # pad layers must not clobber their (zero) cache rows — harmless
        return y, cache_i

    if cfg.family == "hybrid":
        return _stage_decode_hybrid(cfg, stage_layers, x, cache, pos, start,
                                    count, shared, l_max)
    x, caches = jax.lax.scan(body, x, (stage_layers, cache,
                                       jnp.arange(l_max)))
    return x, caches


def _stage_decode_hybrid(cfg, stage_layers, x, cache, pos, start, count,
                         shared, l_max):
    from ..models.lm import _attn_mlp_block, _ssm_block
    positions = pos[None]
    every = cfg.shared_attn_every
    ak, av = cache["ak"], cache["av"]
    ssm_cache = {k: cache[k] for k in ("conv", "h")}

    def body(carry, xs):
        c, ak, av = carry
        p_i, cc, li = xs
        gidx = start + li
        slot = gidx // every - start // every

        def with_attn(args):
            c, ak, av = args
            k_i = jax.lax.dynamic_index_in_dim(ak, slot, 0, keepdims=False)
            v_i = jax.lax.dynamic_index_in_dim(av, slot, 0, keepdims=False)
            y, (k, v) = _attn_mlp_block(cfg, shared, c, positions,
                                        kv_cache=(k_i, v_i), pos=pos)
            ak = jax.lax.dynamic_update_slice(ak, k[None], (slot, 0, 0, 0, 0))
            av = jax.lax.dynamic_update_slice(av, v[None], (slot, 0, 0, 0, 0))
            return y, ak, av
        c2, ak, av = jax.lax.cond((gidx % every == 0) & (li < count),
                                  with_attn, lambda a: a, (c, ak, av))
        y, nc = _ssm_block(cfg, p_i, c2, cache=cc)
        y = jnp.where(li < count, y, c)
        return (y, ak, av), nc

    (x, ak, av), new_ssm = jax.lax.scan(body, (x, ak, av),
                                        (stage_layers, ssm_cache,
                                         jnp.arange(l_max)))
    return x, {**new_ssm, "ak": ak, "av": av}


def _empty_stage_cache(cfg, l_max, B, clen, dtype):
    KVh, hd = cfg.n_kv_heads, cfg.hd
    if cfg.family == "encdec":
        z = jnp.zeros((l_max, B, clen, KVh, hd), dtype)
        zc = jnp.zeros((l_max, B, cfg.enc_frames, KVh, hd), dtype)
        return {"k": z, "v": z, "ck": zc, "cv": zc}
    if cfg.family in ("dense", "vlm", "moe"):
        z = jnp.zeros((l_max, B, clen, KVh, hd), dtype)
        return {"k": z, "v": z}
    if cfg.family == "ssm":
        return {"conv": jnp.zeros((l_max, B, cfg.ssm_conv - 1, cfg.d_inner), dtype),
                "h": jnp.zeros((l_max, B, cfg.d_inner, cfg.ssm_state), jnp.float32)}
    if cfg.family == "hybrid":
        d_xbc = cfg.d_inner + 2 * cfg.ssm_state
        ns = n_attn_slots(cfg, l_max)
        return {"conv": jnp.zeros((l_max, B, cfg.ssm_conv - 1, d_xbc), dtype),
                "h": jnp.zeros((l_max, B, cfg.ssm_heads, cfg.ssm_head_dim,
                                cfg.ssm_state), jnp.float32),
                "ak": jnp.zeros((ns, B, clen, KVh, hd), dtype),
                "av": jnp.zeros((ns, B, clen, KVh, hd), dtype)}
    raise ValueError(cfg.family)


def n_attn_slots(cfg, l_max: int) -> int:
    """Shared-attention KV slots per pipeline stage (slot-compressed: one
    per application site, not one per layer)."""
    return l_max // cfg.shared_attn_every + 2


def _stage_prefill(cfg, stage_layers, x, positions, start, count, shared,
                   enc_hidden, l_max, clen):
    """Apply the stage's layers, returning per-layer caches (padded)."""
    from ..models.lm import _attn_mlp_block, _moe_block, _ssm_block, _dec_layer
    B, S, D = x.shape

    def pad_kv(k, v):
        pad = clen - S
        if pad:
            z = jnp.zeros((B, pad, *k.shape[2:]), k.dtype)
            k, v = jnp.concatenate([k, z], 1), jnp.concatenate([v, z], 1)
        return k, v

    def body(c, xs):
        p_i, li = xs
        gidx = start + li
        if cfg.family in ("dense", "vlm"):
            y, (k, v) = _attn_mlp_block(cfg, p_i, c, positions)
            cache_i = dict(zip(("k", "v"), pad_kv(k, v)))
        elif cfg.family == "moe":
            y, (k, v), _ = _moe_block(cfg, p_i, c, positions)
            cache_i = dict(zip(("k", "v"), pad_kv(k, v)))
        elif cfg.family == "encdec":
            y, (k, v), (ck, cv) = _dec_layer(cfg, p_i, c, enc_hidden, positions)
            k, v = pad_kv(k, v)
            cache_i = {"k": k, "v": v, "ck": ck, "cv": cv}
        elif cfg.family == "ssm":
            y, cc = _ssm_block(cfg, p_i, c)
            cache_i = cc
        else:
            raise ValueError(cfg.family)
        y = jnp.where(li < count, y, c)
        return y, cache_i

    if cfg.family == "hybrid":
        return _stage_prefill_hybrid(cfg, stage_layers, x, positions, start,
                                     count, shared, l_max, clen)
    x, caches = jax.lax.scan(body, x, (stage_layers, jnp.arange(l_max)))
    return x, caches


def _stage_prefill_hybrid(cfg, stage_layers, x, positions, start, count,
                          shared, l_max, clen):
    """Hybrid stage prefill with slot-compressed shared-attention caches:
    ak/av hold one (B, clen, KV, hd) slot per application site in this
    stage; ssm caches stay per-layer via scan ys."""
    from ..models.lm import _attn_mlp_block, _ssm_block
    B, S, D = x.shape
    ns = n_attn_slots(cfg, l_max)
    every = cfg.shared_attn_every
    pad = clen - S
    ak = jnp.zeros((ns, B, clen, cfg.n_kv_heads, cfg.hd), x.dtype)
    av = jnp.zeros_like(ak)

    def body(carry, xs):
        c, ak, av = carry
        p_i, li = xs
        gidx = start + li
        slot = gidx // every - start // every

        def with_attn(args):
            c, ak, av = args
            y, (k, v) = _attn_mlp_block(cfg, shared, c, positions)
            if pad:
                z = jnp.zeros((B, pad, *k.shape[2:]), k.dtype)
                k = jnp.concatenate([k, z], 1)
                v = jnp.concatenate([v, z], 1)
            ak = jax.lax.dynamic_update_slice(ak, k[None], (slot, 0, 0, 0, 0))
            av = jax.lax.dynamic_update_slice(av, v[None], (slot, 0, 0, 0, 0))
            return y, ak, av
        c2, ak, av = jax.lax.cond((gidx % every == 0) & (li < count),
                                  with_attn, lambda a: a, (c, ak, av))
        y, cc = _ssm_block(cfg, p_i, c2)
        y = jnp.where(li < count, y, c)
        return (y, ak, av), cc

    (x, ak, av), caches = jax.lax.scan(body, (x, ak, av),
                                       (stage_layers, jnp.arange(l_max)))
    return x, {**caches, "ak": ak, "av": av}
