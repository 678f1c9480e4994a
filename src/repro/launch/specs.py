"""Cell definitions (arch × input-shape) and ShapeDtypeStruct inputs.

The 4 assigned LM shapes; ``long_500k`` is decode-only and runs only for
sub-quadratic archs (SSM/hybrid) — pure full-attention archs skip it
(DESIGN.md §4).  All specs carry NamedShardings so ``jit(...).lower()``
needs no separate in_shardings.
"""
from __future__ import annotations

from dataclasses import dataclass

import jax
import jax.numpy as jnp
import numpy as np

from ..data.pipeline import make_batch_specs
from ..models.config import ArchConfig
from ..runtime.pipeline import PipelineConfig, build_pipeline_params
from ..sharding.api import MeshContext
from ..models import lm
from ..models.common import AbstractBuilder, DTYPES


@dataclass(frozen=True)
class ShapeSpec:
    name: str
    seq: int
    batch: int
    kind: str          # "train" | "prefill" | "decode"


SHAPES: dict[str, ShapeSpec] = {
    "train_4k": ShapeSpec("train_4k", 4096, 256, "train"),
    "prefill_32k": ShapeSpec("prefill_32k", 32768, 32, "prefill"),
    "decode_32k": ShapeSpec("decode_32k", 32768, 128, "decode"),
    "long_500k": ShapeSpec("long_500k", 524288, 1, "decode"),
}


def cell_supported(cfg: ArchConfig, shape: str) -> tuple[bool, str]:
    if shape == "long_500k" and not cfg.supports_long_context:
        return False, ("skip: pure full-attention arch at 524k context "
                       "(sub-quadratic required; see DESIGN.md §4)")
    return True, ""


# --------------------------------------------------------------------------- #
# Param / optimizer / cache specs
# --------------------------------------------------------------------------- #
def param_specs(cfg: ArchConfig, ctx: MeshContext | None,
                pcfg: PipelineConfig | None = None):
    b = AbstractBuilder(ctx, DTYPES[cfg.dtype])
    if pcfg is not None:
        return build_pipeline_params(cfg, b, pcfg)
    return lm.build_params(cfg, b)


def train_state_specs(cfg: ArchConfig, ctx: MeshContext | None,
                      pcfg: PipelineConfig | None = None):
    from ..sharding.api import zero1_spec
    from jax.sharding import NamedSharding
    params = param_specs(cfg, ctx, pcfg)

    def f32_zero1(s):
        """Optimizer moments: fp32, param sharding + 'data' (ZeRO-1)."""
        sh = getattr(s, "sharding", None)
        if ctx is not None and sh is not None:
            sh = NamedSharding(ctx.mesh, zero1_spec(sh.spec, s.shape))
        return jax.ShapeDtypeStruct(s.shape, jnp.float32, sharding=sh)

    scalar = jax.ShapeDtypeStruct((), jnp.int32,
                                  sharding=None if ctx is None
                                  else ctx.sharding(()))
    return {"params": params,
            "opt": {"m": jax.tree.map(f32_zero1, params),
                    "v": jax.tree.map(f32_zero1, params),
                    "count": scalar},
            "step": scalar}


def _sds(ctx, shape, dtype, axes):
    if ctx is None:
        return jax.ShapeDtypeStruct(shape, dtype)
    return jax.ShapeDtypeStruct(shape, dtype,
                                sharding=ctx.sharding(axes, shape))


def cache_specs(cfg: ArchConfig, B: int, S: int, ctx: MeshContext | None,
                pcfg: PipelineConfig | None = None):
    """Decode-input cache pytree (matches trunk_prefill / pipeline prefill
    output layouts)."""
    dt = DTYPES[cfg.dtype]
    KV, hd = cfg.n_kv_heads, cfg.hd

    if pcfg is None:
        lead, lead_ax = (cfg.n_layers,), ("layers",)
    else:
        _, _, l_max = pcfg.layout(cfg.n_layers)
        lead, lead_ax = (pcfg.n_stages, l_max), ("stage", "layers")

    def kv_axes():
        if ctx is not None and KV and KV % max(ctx.size("model"), 1) != 0:
            return (*lead_ax, "batch", "seq_model", "kv_heads", "head_dim")
        return (*lead_ax, "batch", "seq", "kv_heads", "head_dim")

    pos = _sds(ctx, (), jnp.int32, ())
    if cfg.family in ("dense", "vlm", "moe"):
        kshape = (*lead, B, S, KV, hd)
        return {"k": _sds(ctx, kshape, dt, kv_axes()),
                "v": _sds(ctx, kshape, dt, kv_axes()), "pos": pos}
    if cfg.family == "encdec":
        kshape = (*lead, B, S, KV, hd)
        cshape = (*lead, B, cfg.enc_frames, KV, hd)
        cax = (*lead_ax, "batch", "frames", "kv_heads", "head_dim")
        return {"k": _sds(ctx, kshape, dt, kv_axes()),
                "v": _sds(ctx, kshape, dt, kv_axes()),
                "ck": _sds(ctx, cshape, dt, cax),
                "cv": _sds(ctx, cshape, dt, cax), "pos": pos}
    if cfg.family == "ssm":
        return {"conv": _sds(ctx, (*lead, B, cfg.ssm_conv - 1, cfg.d_inner), dt,
                             (*lead_ax, "batch", "kernel", "d_inner")),
                "h": _sds(ctx, (*lead, B, cfg.d_inner, cfg.ssm_state),
                          jnp.float32,
                          (*lead_ax, "batch", "d_inner", "state")),
                "pos": pos}
    if cfg.family == "hybrid":
        from ..runtime.pipeline import n_attn_slots
        d_xbc = cfg.d_inner + 2 * cfg.ssm_ngroups * cfg.ssm_state
        if pcfg is None:
            a_lead, a_lead_ax = (cfg.n_attn_apps,), ("layers",)
        else:
            a_lead = (pcfg.n_stages, n_attn_slots(cfg, pcfg))
            a_lead_ax = ("stage", "layers")
        return {"conv": _sds(ctx, (*lead, B, cfg.ssm_conv - 1, d_xbc), dt,
                             (*lead_ax, "batch", "kernel", "conv_dim")),
                "h": _sds(ctx, (*lead, B, cfg.ssm_heads, cfg.ssm_head_dim,
                                cfg.ssm_state), jnp.float32,
                          (*lead_ax, "batch", "ssm_heads", "head_dim", "state")),
                "ak": _sds(ctx, (*a_lead, B, S, KV, hd), dt,
                           (*a_lead_ax, "batch", "seq", "kv_heads", "head_dim")),
                "av": _sds(ctx, (*a_lead, B, S, KV, hd), dt,
                           (*a_lead_ax, "batch", "seq", "kv_heads", "head_dim")),
                "pos": pos}
    raise ValueError(cfg.family)


def input_specs(cfg: ArchConfig, shape_name: str, ctx: MeshContext | None,
                pcfg: PipelineConfig | None = None) -> dict:
    """All jit inputs for the cell's step function, as ShapeDtypeStructs.

    train  → {"state": ..., "batch": ...}
    prefill→ {"params": ..., "inputs": ...}
    decode → {"params": ..., "token": ..., "cache": ...}
    """
    sh = SHAPES[shape_name]
    if sh.kind == "train":
        return {"state": train_state_specs(cfg, ctx, pcfg),
                "batch": make_batch_specs(cfg, sh.batch, sh.seq, ctx, "train")}
    if sh.kind == "prefill":
        return {"params": param_specs(cfg, ctx, pcfg),
                "inputs": make_batch_specs(cfg, sh.batch, sh.seq, ctx,
                                           "prefill")}
    # decode: one new token against a cache of sh.seq
    tok = _sds(ctx, (sh.batch, 1), jnp.int32, ("batch", "seq"))
    return {"params": param_specs(cfg, ctx, pcfg),
            "token": tok,
            "cache": cache_specs(cfg, sh.batch, sh.seq, ctx, pcfg)}
