"""Block-wise profiling (paper Sec. IV-D / Fig. 2).

Three cost sources, all feeding the same ``CostTable``:

  * ``profile_wallclock`` — run each block's jitted function on this host
    and measure it (the paper's psutil/wall-clock methodology).
  * ``profile_analytic``  — per-block FLOPs / device effective rate.
  * ``costs_from_hlo``    — per-block FLOPs taken from compiled-HLO
    ``cost_analysis`` of the real jitted block (the dry-run-native
    equivalent for the TPU target, where wall-clock is unavailable).
"""
from __future__ import annotations

import time
from typing import Callable, Sequence

import jax

from .blocks import BlockGraph
from .costmodel import CostTable
from .devices import DeviceProfile


def profile_wallclock(
    device_name: str,
    block_fns: Sequence[Callable],
    block_names: Sequence[str],
    make_input: Callable[[int], object],
    repeats: int = 5,
    warmup: int = 1,
    table: CostTable | None = None,
) -> CostTable:
    """Measure each block on the current host.

    ``block_fns[i]`` maps the activation produced by block i-1 to block
    i's output; ``make_input(0)`` builds the model input.  Each config is
    run ``repeats`` times and averaged, mirroring the paper's 5-run mean.
    """
    table = table or CostTable()
    x = make_input(0)
    for name, fn in zip(block_names, block_fns):
        jfn = jax.jit(fn)
        for _ in range(warmup):
            y = jfn(x)
        jax.block_until_ready(y)
        t0 = time.perf_counter()
        for _ in range(repeats):
            y = jfn(x)
        jax.block_until_ready(y)
        dt = (time.perf_counter() - t0) / repeats
        table.set(device_name, name, dt)
        x = y
    return table


def profile_analytic(graph: BlockGraph, device: DeviceProfile, batch: int = 1,
                     table: CostTable | None = None) -> CostTable:
    table = table or CostTable()
    per_block_overhead = device.stage_overhead_s / max(graph.n_blocks, 1)
    for b in graph.blocks:
        table.set(device.name, b.name,
                  b.flops * batch / device.flops_per_s + per_block_overhead)
    return table


def costs_from_hlo(
    device: DeviceProfile,
    block_fns: Sequence[Callable],
    block_names: Sequence[str],
    example_inputs: Sequence,
    table: CostTable | None = None,
) -> CostTable:
    """Per-block cost from XLA's own flop count: lower+compile each block
    (no execution) and convert cost_analysis FLOPs to seconds with the
    device's effective rate, max'ed with the memory-bandwidth term."""
    table = table or CostTable()
    for name, fn, x in zip(block_names, block_fns, example_inputs):
        compiled = jax.jit(fn).lower(x).compile()
        ca = compiled.cost_analysis() or {}
        flops = float(ca.get("flops", 0.0))
        nbytes = float(ca.get("bytes accessed", 0.0))
        table.set(device.name, name, device.compute_time(flops, nbytes))
    return table


def coefficient_of_variation(times: Sequence[float]) -> float:
    """Used to validate Fig 2's finding: block costs are heterogeneous."""
    import math
    n = len(times)
    if n == 0:
        return 0.0
    mu = sum(times) / n
    if mu == 0:
        return 0.0
    var = sum((t - mu) ** 2 for t in times) / n
    return math.sqrt(var) / mu
