"""Dense MLP (gated SwiGLU / plain GELU) and capacity-based MoE.

The MoE is the sort-based, capacity-dropped formulation (tokens sorted
by expert id, scattered into an (experts, capacity) buffer, batched
expert matmuls, gathered back) — O(T·k·cf) expert FLOPs like the active
parameter count, no dense all-experts waste, and no O(T·E·C) one-hot
dispatch einsum.  Expert weights are sharded over the ``model`` axis
(expert parallelism); GSPMD inserts the dispatch/combine collectives.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from ..sharding.api import shard
from .common import Builder, gelu, silu


# --------------------------------------------------------------------------- #
# Dense MLP
# --------------------------------------------------------------------------- #
def mlp_params(b: Builder, cfg, prefix: str, d_ff: int | None = None) -> dict:
    D, F = cfg.d_model, d_ff or cfg.d_ff
    p = {"w_up": b.leaf(f"{prefix}.w_up", (D, F), ("embed", "ff")),
         "w_down": b.leaf(f"{prefix}.w_down", (F, D), ("ff", "embed"))}
    if cfg.gated_mlp:
        p["w_gate"] = b.leaf(f"{prefix}.w_gate", (D, F), ("embed", "ff"))
    return p


def mlp(cfg, p, x, adapter=None):
    """``adapter`` (Zamba2): a rank-r term ``(x @ ad_in) @ ad_gate``/``ad_up``
    added to the gate and up projections."""
    up = jnp.einsum("bsd,df->bsf", x, p["w_up"])
    if adapter is not None:
        lo = jnp.einsum("bsd,dr->bsr", x, adapter["ad_in"])
        up = up + jnp.einsum("bsr,rf->bsf", lo, adapter["ad_up"])
    up = shard(up, "batch", "seq", "ff")
    if cfg.gated_mlp:
        gate = jnp.einsum("bsd,df->bsf", x, p["w_gate"])
        if adapter is not None:
            gate = gate + jnp.einsum("bsr,rf->bsf", lo, adapter["ad_gate"])
        act = (jax.nn.gelu(gate, approximate=False) if cfg.mlp_act == "gelu"
               else silu(gate))
        h = act * up
    else:
        h = gelu(up)
    y = jnp.einsum("bsf,fd->bsd", h, p["w_down"])
    return shard(y, "batch", "seq", "embed")


# --------------------------------------------------------------------------- #
# MoE
# --------------------------------------------------------------------------- #
def moe_params(b: Builder, cfg, prefix: str) -> dict:
    D, F, E = cfg.d_model, cfg.d_ff, cfg.n_experts
    if cfg.moe_shard == "etp":
        # expert-TP: every expert's FFN split over 'model' (F axis);
        # the expert axis itself stays unsharded
        wg_axes = (None, "embed", "ff")
        wd_axes = (None, "ff", "embed")
    else:
        # expert parallelism: experts themselves split over 'model'
        wg_axes = ("experts", "embed", "expert_ff")
        wd_axes = ("experts", "expert_ff", "embed")
    return {
        # router stays replicated (D×E is tiny); sharding its E axis makes
        # GSPMD reduce along a sharded top_k axis, which both costs a
        # collective per layer and trips an SPMD-partitioner abort inside
        # partial-manual shard_map (pipeline mode).
        "router": b.leaf(f"{prefix}.router", (D, E), ("embed", None),
                         dtype=jnp.float32),
        "w_gate": b.leaf(f"{prefix}.w_gate", (E, D, F), wg_axes),
        "w_up": b.leaf(f"{prefix}.w_up", (E, D, F), wg_axes),
        "w_down": b.leaf(f"{prefix}.w_down", (E, F, D), wd_axes),
    }


def _capacity(tokens_per_group: int, cfg) -> int:
    c = int(tokens_per_group * cfg.top_k / cfg.n_experts * cfg.capacity_factor)
    return max(-(-c // 4) * 4, 4)


def moe_mlp_gshard(cfg, p, x):
    """GShard-style one-hot dispatch/combine (einsum only — no gather,
    sort, or scatter ops anywhere).

    Used in pipeline mode: XLA's SPMD gather partitioner hard-aborts when
    evaluating gather strategies inside a partial-manual mesh, so the
    sort-based path (cheaper) is unusable there.  Cost: the dispatch and
    combine einsums add ≈2·Tg·E·C·D FLOPs per group (~6–20 % of expert
    FLOPs at the default group size), which the analytic roofline model
    accounts for.
    """
    B, S, D = x.shape
    E, K = cfg.n_experts, cfg.top_k
    T = B * S
    # dispatch/combine einsums are O(Tg²·k·cf·D) — keep groups small
    Tg = min(cfg.moe_gshard_group, T)
    G = T // Tg
    xg = x.reshape(G, Tg, D)
    xg = shard(xg, "moe_group", "seq", "embed")

    logits = jnp.einsum("gtd,de->gte", xg.astype(jnp.float32), p["router"])
    probs = jax.nn.softmax(logits, axis=-1)
    top_w, top_e = jax.lax.top_k(probs, K)
    top_w = top_w / jnp.sum(top_w, axis=-1, keepdims=True)

    density = jnp.mean(jax.nn.one_hot(top_e[..., 0], E), axis=1)
    aux = jnp.mean(density * jnp.mean(probs, axis=1)) * E * E

    C = _capacity(Tg, cfg)
    # position of each (token, k) within its expert: running count over
    # the flattened (t, k) choice order — pure cumsum, no sorts.
    onehots = jax.nn.one_hot(top_e, E, dtype=jnp.float32)    # (G, Tg, K, E)
    flat = onehots.reshape(G, Tg * K, E)
    pos = jnp.cumsum(flat, axis=1) - flat                    # (G, TgK, E)
    pos = jnp.sum(pos * flat, axis=-1).reshape(G, Tg, K)     # per choice
    keep = pos < C

    pos_oh = jax.nn.one_hot(pos, C, dtype=jnp.float32)       # (G, Tg, K, C)
    disp = jnp.einsum("gtke,gtkc->gtec",
                      onehots * keep[..., None], pos_oh)     # (G,Tg,E,C)
    comb = jnp.einsum("gtk,gtke,gtkc->gtec",
                      top_w * keep, onehots, pos_oh)

    etp = cfg.moe_shard == "etp"
    e_ax, f_ax = (None, "ff") if etp else ("experts", "expert_ff")
    buf = jnp.einsum("gtec,gtd->gecd", disp.astype(x.dtype), xg)
    buf = shard(buf, "moe_group", e_ax, "capacity", "embed")
    gate = jnp.einsum("gecd,edf->gecf", buf, p["w_gate"])
    up = jnp.einsum("gecd,edf->gecf", buf, p["w_up"])
    h = silu(gate) * up
    h = shard(h, "moe_group", e_ax, "capacity", f_ax)
    ybuf = jnp.einsum("gecf,efd->gecd", h, p["w_down"])
    ybuf = shard(ybuf, "moe_group", e_ax, "capacity", "embed")
    yg = jnp.einsum("gtec,gecd->gtd", comb.astype(x.dtype), ybuf)
    yg = shard(yg, "moe_group", "seq", "embed")
    return yg.reshape(B, S, D), aux


def moe_mlp(cfg, p, x):
    """x: (B, S, D) → (B, S, D).  Returns (y, aux) with load-balance loss."""
    B, S, D = x.shape
    E, K = cfg.n_experts, cfg.top_k
    T = B * S
    flat = x.reshape(T, D)
    Tg = min(cfg.moe_group_size, T)
    G = T // Tg
    xg = flat.reshape(G, Tg, D)
    xg = shard(xg, "moe_group", "seq", "embed")

    # --- routing -------------------------------------------------------- #
    logits = jnp.einsum("gtd,de->gte", xg.astype(jnp.float32), p["router"])
    probs = jax.nn.softmax(logits, axis=-1)
    top_w, top_e = jax.lax.top_k(probs, K)                 # (G, Tg, K)
    top_w = top_w / jnp.sum(top_w, axis=-1, keepdims=True)

    # load-balance aux loss (Switch-style)
    density = jnp.mean(jax.nn.one_hot(top_e[..., 0], E), axis=1)   # (G, E)
    mean_prob = jnp.mean(probs, axis=1)                            # (G, E)
    aux = jnp.mean(density * mean_prob) * E * E

    # --- dispatch (sort + gather, capacity-dropped; NO scatters) --------- #
    # Scatter-based dispatch makes GSPMD materialize/all-gather the full
    # buffer (and trips an SPMD-partitioner abort under partial-manual
    # shard_map); the gather formulation keeps everything local-gatherable.
    C = _capacity(Tg, cfg)
    TK = Tg * K
    e_flat = top_e.reshape(G, TK)                          # expert per slot
    w_flat = top_w.reshape(G, TK).astype(x.dtype)
    tok_of_slot = jnp.repeat(jnp.arange(Tg), K)[None].repeat(G, 0)

    order = jnp.argsort(e_flat, axis=-1, stable=True)      # (G, TK)
    e_sorted = jnp.take_along_axis(e_flat, order, axis=-1)
    tok_sorted = jnp.take_along_axis(tok_of_slot, order, axis=-1)
    # first sorted index of each expert → (G, E)
    starts = jax.vmap(lambda es: jnp.searchsorted(es, jnp.arange(E)))(e_sorted)

    # buffer slot (e, c) is filled by sorted slot j = starts[e] + c
    j_idx = starts[:, :, None] + jnp.arange(C)[None, None, :]   # (G, E, C)
    nxt = jnp.concatenate([starts[:, 1:], jnp.full((G, 1), TK)], axis=1)
    valid = j_idx < nxt[:, :, None]                             # c < count_e
    j_safe = jnp.minimum(j_idx, TK - 1).reshape(G, E * C)
    tok_src = jnp.take_along_axis(tok_sorted, j_safe, axis=-1)  # (G, E*C)
    buf = jnp.take_along_axis(xg, tok_src[..., None], axis=1)   # (G, E*C, D)
    buf = buf.reshape(G, E, C, D) * valid[..., None].astype(x.dtype)
    etp = cfg.moe_shard == "etp"
    e_ax, f_ax = (None, "ff") if etp else ("experts", "expert_ff")
    buf = shard(buf, "moe_group", e_ax, "capacity", "embed")

    # --- expert compute --------------------------------------------------- #
    gate = jnp.einsum("gecd,edf->gecf", buf, p["w_gate"])
    up = jnp.einsum("gecd,edf->gecf", buf, p["w_up"])
    h = silu(gate) * up
    h = shard(h, "moe_group", e_ax, "capacity", f_ax)
    ybuf = jnp.einsum("gecf,efd->gecd", h, p["w_down"])
    ybuf = shard(ybuf, "moe_group", e_ax, "capacity", "embed")

    # --- combine (pure gathers) ------------------------------------------- #
    inv_order = jnp.argsort(order, axis=-1)                    # unsort map
    pos_sorted = jnp.arange(TK)[None] - jnp.take_along_axis(
        starts, e_sorted, axis=-1)                             # (G, TK)
    pos_unsorted = jnp.take_along_axis(pos_sorted, inv_order, axis=-1)
    flat_idx = e_flat * C + pos_unsorted                       # (G, TK)
    kept = pos_unsorted < C
    flat_safe = jnp.where(kept, flat_idx, 0)
    y_slot = jnp.take_along_axis(ybuf.reshape(G, E * C, D),
                                 flat_safe[..., None], axis=1)
    y_slot = y_slot * (kept & True)[..., None].astype(x.dtype) \
        * w_flat[..., None]
    yg = jnp.sum(y_slot.reshape(G, Tg, K, D), axis=2)
    yg = shard(yg, "moe_group", "seq", "embed")
    return yg.reshape(B, S, D), aux
