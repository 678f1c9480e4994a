"""ArchConfig → ParetoPipe BlockGraph.

This is the bridge that makes the paper's partitioner a first-class
feature of the LM framework: every architecture becomes a chain of
blocks (its layers, plus embed/head endpoints) with per-block FLOPs,
weight bytes, and inter-block activation bytes — exactly what
``core.partitioner`` needs to choose pod-level pipeline cuts.

Costs come from the same formulas as the dry-run's analytic model
(``launch.analytic``), so the partitioner and the roofline agree.
"""
from __future__ import annotations

from ..core.blocks import Block, BlockGraph
from ..launch.analytic import (_layer_fwd_flops, _logit_flops,
                               _shared_block_flops)
from .config import ArchConfig


def _layer_weight_bytes(cfg: ArchConfig) -> int:
    n = cfg.param_count()
    head = 0 if cfg.tie_embeddings else cfg.d_model * cfg.vocab
    trunk = n - cfg.vocab * cfg.d_model - head
    return int(trunk / cfg.n_layers * 2)


def arch_block_graph(cfg: ArchConfig, seq: int, *, train: bool = False,
                     per_sample: bool = True) -> BlockGraph:
    """Per-sample block graph at sequence length ``seq``.

    Blocks: [embed] + n_layers × [layer] + [head], as ``LMChain`` runs
    them.  Inference (``train`` False) is a prefill: the head computes
    the last position's logits only, in float32, as ``forward_prefill``
    and ``LMChain`` do; training charges every position's.  For the
    hybrid family a cut carries the residual stream and the original
    embeddings, and each layer that applies a memory block charges that
    application's work, holds its adapter and linear, and names the
    block's ``shared_group`` (its weights held once per stage).
    """
    ctx = (seq + cfg.attn_chunk) / 2 if seq > cfg.attn_chunk else (seq + 1) / 2
    act = seq * cfg.d_model * 2              # bf16 inter-layer activation
    if cfg.family == "hybrid":
        act *= 2                             # + the original embeddings
    mult = 3.0 + (1.0 if (train and cfg.remat) else 0.0) if train else 1.0

    blocks = [Block("embed", flops=seq * cfg.d_model * mult,
                    weight_bytes=cfg.vocab * cfg.d_model * 2,
                    out_bytes=act, act_bytes=act * 2)]
    per_layer = _layer_fwd_flops(cfg, ctx) * seq * mult
    lw, app_w, shared_w, app_flops = _layer_weight_bytes(cfg), 0, 0, 0.0
    apps = (-1,) * cfg.n_layers
    if cfg.family == "hybrid":
        lw = cfg.mamba2_layer_params() * 2
        app_w = cfg.app_params() * 2
        shared_w = cfg.mem_block_params() * 2
        app_flops = _shared_block_flops(cfg, ctx) * seq * mult
        apps = cfg.attn_apps
    for i, a in enumerate(apps):
        hyb = a >= 0
        blocks.append(Block(
            f"layer{i:03d}", flops=per_layer + (app_flops if hyb else 0.0),
            weight_bytes=lw + (app_w if hyb else 0), out_bytes=act,
            act_bytes=act * 4,
            shared_group=f"mem_block{a % cfg.n_mem_blocks}" if hyb else None,
            shared_bytes=shared_w if hyb else 0))
    head_w = (0 if cfg.tie_embeddings else cfg.d_model * cfg.vocab * 2) \
        + (cfg.d_model * 2 if cfg.family == "hybrid" else 0)
    positions = seq if train else 1
    out = seq * 4 if train else cfg.vocab * 4
    blocks.append(Block("head",
                        flops=_logit_flops(cfg, positions) * (3 if train else 1),
                        weight_bytes=head_w, out_bytes=out,
                        act_bytes=positions * cfg.vocab * 4))
    return BlockGraph(name=cfg.name, blocks=tuple(blocks),
                      input_bytes=seq * 4, output_bytes=out)


def choose_pipeline_cuts(cfg: ArchConfig, seq: int, n_pods: int,
                         chips_per_pod: int = 256, batch: int = 1,
                         train: bool = True,
                         objective: str = "throughput"):
    """ParetoPipe-driven stage assignment: solve the k-way partition over
    the arch's block graph on the pod chain, return layer cut indices
    usable by ``PipelineConfig`` (embed/head pinned to first/last pod)."""
    from ..core import dp_front_kway, best_latency, best_throughput
    from ..core.scenarios import pods

    graph = arch_block_graph(cfg, seq, train=train)
    scen = pods(n_pods, chips_per_pod)
    front = dp_front_kway(graph, scen.devices, scen.links, batch=batch)
    pick = best_throughput(front) if objective == "throughput" \
        else best_latency(front)
    # block index → layer index (block 0 is embed)
    cuts = tuple(min(max(c - 1, 1), cfg.n_layers - 1) for c in pick.partition)
    return cuts, pick, front
