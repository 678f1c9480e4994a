"""Logical-axis sharding: one rules table, applied to weights and activations.

Tensors are annotated with *logical* axis names; a ``MeshContext`` maps
them onto physical mesh axes with a divisibility guard (a dim that does
not divide by the mesh-axis size is replicated rather than unevenly
sharded — keeps HLO clean and the roofline honest).  The same mapping
builds ``in_shardings`` for jit (from the param defs) and
``with_sharding_constraint`` annotations inside the step functions, so
they can never disagree.
"""
from __future__ import annotations

import threading
from dataclasses import dataclass

import jax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

# logical axis -> preferred mesh axis (None = replicate)
RULES: dict[str, str | None] = {
    "batch": "data",
    "moe_group": "data",
    "stage": "pod",
    # tensor-parallel axes
    "vocab": "model",
    "heads": "model",
    "kv_heads": "model",
    "ff": "model",
    "experts": "model",
    "d_inner": "model",
    "conv_dim": "model",
    "ssm_heads": "model",
    # replicated / unsharded
    "embed": None,
    "seq": None,
    "frames": None,
    "head_dim": None,
    "state": None,
    "kernel": None,
    "capacity": None,
    "layers": None,
    "dt_rank": None,
    "patches": None,
    "expert_ff": None,   # ff inside an expert: 'model' is taken by experts

    # fallback sequence sharding (used by cache helpers)
    "seq_model": "model",
    # sequence-parallel residual stream (train/prefill layer boundaries)
    "seq_sp": "model",
    # row-parallel attention projections (archs whose head count does not
    # divide the TP axis): shard the contraction dim instead of heads
    "embed_rp": "model",
    "head_dim_rp": "model",
}


@dataclass
class MeshContext:
    mesh: Mesh

    @property
    def axis_sizes(self) -> dict[str, int]:
        return dict(zip(self.mesh.axis_names, self.mesh.devices.shape))

    def size(self, mesh_axis: str) -> int:
        return self.axis_sizes.get(mesh_axis, 1)

    # ------------------------------------------------------------------ #
    def spec(self, logical: tuple[str | None, ...],
             shape: tuple[int, ...] | None = None) -> P:
        """Map logical names to a PartitionSpec, replicating any dim that
        is absent from the mesh or not divisible."""
        out = []
        for i, name in enumerate(logical):
            axis = RULES.get(name) if name else None
            if axis is None or axis not in self.mesh.axis_names:
                out.append(None)
                continue
            if shape is not None and shape[i] % self.size(axis) != 0:
                out.append(None)
                continue
            out.append(axis)
        return P(*out)

    def sharding(self, logical: tuple[str | None, ...],
                 shape: tuple[int, ...] | None = None) -> NamedSharding:
        return NamedSharding(self.mesh, self.spec(logical, shape))


_tls = threading.local()


def set_context(ctx: MeshContext | None):
    _tls.ctx = ctx


def get_context() -> MeshContext | None:
    return getattr(_tls, "ctx", None)


class use_mesh_context:
    """``with use_mesh_context(mesh): ...`` — enables logical sharding
    annotations (and jax.set_mesh) for everything inside."""

    def __init__(self, mesh: Mesh | None):
        self.mesh = mesh
        self._jax_ctx = None

    def __enter__(self):
        if self.mesh is not None:
            set_context(MeshContext(self.mesh))
            self._jax_ctx = jax.set_mesh(self.mesh)
            self._jax_ctx.__enter__()
        return get_context()

    def __exit__(self, *exc):
        if self._jax_ctx is not None:
            self._jax_ctx.__exit__(*exc)
        set_context(None)
        return False


def shard(x, *logical: str | None):
    """Annotate an activation with logical axes (no-op outside a mesh)."""
    ctx = get_context()
    if ctx is None:
        return x
    if len(logical) != x.ndim:
        raise ValueError(f"{len(logical)} names for rank-{x.ndim} tensor")
    return jax.lax.with_sharding_constraint(x, ctx.spec(tuple(logical), x.shape))


def zero1_spec(spec: P, shape: tuple[int, ...]) -> P:
    """ZeRO-1: extend a param PartitionSpec with 'data' on the first
    still-unsharded, divisible dim — optimizer moments and gradient
    accumulators shard over data×model instead of replicating over data.
    GSPMD then turns the DP gradient all-reduce into reduce-scatter +
    (at the param update) all-gather, which is exactly ZeRO-1."""
    ctx = get_context()
    if ctx is None or "data" not in ctx.mesh.axis_names:
        return spec
    used = set(a for a in spec if a is not None)
    if "data" in used:
        return spec
    dp = ctx.size("data")
    parts = list(spec) + [None] * (len(shape) - len(spec))
    for i, (ax, dim) in enumerate(zip(parts, shape)):
        if ax is None and dim % dp == 0 and dim >= dp:
            parts[i] = "data"
            return P(*parts)
    return spec


def shard_zero1(x, spec: P):
    """In-jit constraint applying zero1_spec to a gradient/moment leaf."""
    ctx = get_context()
    if ctx is None:
        return x
    return jax.lax.with_sharding_constraint(x, zero1_spec(spec, x.shape))


def attn_q_names(n_heads: int) -> tuple[str, ...]:
    """q activations: shard heads over 'model' when divisible (classic
    TP); otherwise shard the *query sequence* (context parallelism) so
    replicated-head archs (36H/48H on 16-way TP) don't blow up the
    attention workspace and FLOPs by the TP degree."""
    ctx = get_context()
    if ctx is not None and n_heads % max(ctx.size("model"), 1) != 0:
        return ("batch", "seq_sp", "heads", "head_dim")
    return ("batch", "seq", "heads", "head_dim")


def kv_cache_names(kv_heads: int, hd: int) -> tuple[str, ...]:
    """Cache (layers, batch, seq, kv, hd): shard kv heads over 'model'
    when divisible, else shard the sequence (flash-decoding style) —
    resolved at trace time against the active mesh."""
    ctx = get_context()
    if ctx is not None and kv_heads % max(ctx.size("model"), 1) != 0:
        return ("layers", "batch", "seq_model", "kv_heads", "head_dim")
    return ("layers", "batch", "seq", "kv_heads", "head_dim")
