"""Compile-only checks for one chip of a described v5e:2x2.

Nothing runs here: the TPU compiler, installed with jaxlib, compiles for
a chip that is described and not attached, and refuses what the chip
could not run or hold (block layouts off its tiling, kernels that
overflow fast memory, programs larger than its HBM).  Interpret-mode
tests cannot see any of that.

Covered: the wire codec kernels of the split-inference path at real
activation widths (they must lower to Mosaic, ``tpu_custom_call``), and
every stage program resnet50 runs as under the solver's ``pi_pi_gpu``
cuts, at batch 8 and 224x224.

The topology is described inside a module fixture, never at import: only
one process at a time may load the TPU library, and every test worker
imports this file.
"""
import math
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import SingleDeviceSharding

from repro.kernels import codec_pack

BATCH, HW = 8, 224
# resnet50 at 224x224, batch 8: the widest activation (after conv1, the
# solver's pi_pi_gpu cut), the narrowest (after the last bottleneck), and
# an odd shape that needs padding
ACTIVATIONS = [(BATCH, 112, 112, 64), (BATCH, 7, 7, 2048), (3, 7, 7, 5)]


@pytest.fixture(scope="module")
def chip():
    """One described v5e chip, with the persistent compilation cache off
    (it cannot read back what it wrote for an absent chip)."""
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache

    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:                  # noqa: BLE001 — reported as skip
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


def _spec(shape, dtype, sharding):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def _kernel_args(kernel: str, shape, chip):
    n = math.prod(shape)
    x = _spec(shape, jnp.float32, chip)
    scale = _spec((), jnp.float32, chip)
    if kernel == "int8_unpack":
        return (_spec((n,), jnp.int8, chip), scale), {}
    if kernel == "fp8_unpack":
        return (_spec((n,), jnp.float8_e4m3fn, chip), scale), {}
    if kernel == "topk_select":
        return (x,), {"k": -(-n // 8)}
    return (x,), {}


@pytest.mark.parametrize("shape", ACTIVATIONS, ids=lambda s: "x".join(map(str, s)))
@pytest.mark.parametrize("kernel", ["int8_pack", "int8_unpack", "fp8_pack",
                                    "fp8_unpack", "topk_select"])
def test_codec_kernel_compiles_to_mosaic(chip, kernel, shape):
    args, kw = _kernel_args(kernel, shape, chip)
    fn = getattr(codec_pack, kernel)
    compiled = fn.lower(*args, interpret=False, **kw).compile()
    assert "tpu_custom_call" in compiled.as_text()


@pytest.fixture(scope="module")
def resnet50_stages(chip):
    """resnet50 at the solver's pi_pi_gpu cuts, as chip_smoke.py runs
    it → [(Worker, input spec)] per stage, params on the host."""
    from repro.core import scenarios, solve
    from repro.core.partitioner import best_throughput
    from repro.models.cnn import zoo
    from repro.runtime.edge import Worker

    m = zoo.get("resnet50")
    params = m.init(jax.random.PRNGKey(0))
    cuts = best_throughput(solve(m.block_graph(), scenarios.get("pi_pi_gpu"),
                                 batch=BATCH)).partition
    bounds = (0, *cuts, len(m.blocks))
    x = jax.ShapeDtypeStruct((BATCH, HW, HW, 3), jnp.float32)
    stages = []
    for lo, hi in zip(bounds, bounds[1:]):
        stages.append((Worker(f"stage{lo}", m, params, lo, hi,
                              "lightweight"), x))
        x = jax.eval_shape(lambda a, lo=lo, hi=hi:
                           m.apply_range(params, a, lo, hi), x)
    return stages


@pytest.mark.parametrize("stage", [0, 1, 2])
def test_resnet50_stage_compiles_for_one_chip(chip, resnet50_stages, stage):
    worker, x = resnet50_stages[stage]
    params = jax.tree.map(lambda a: _spec(a.shape, a.dtype, chip),
                          worker.params)
    compiled = worker.fn.lower(params, _spec(x.shape, x.dtype, chip)).compile()
    mem = compiled.memory_analysis()
    used = (mem.argument_size_in_bytes + mem.output_size_in_bytes
            + mem.temp_size_in_bytes)
    assert 0 < used < 16e9
    out = jax.eval_shape(worker.fn, worker.params, x)
    assert out.shape[0] == BATCH and np.dtype(out.dtype) == np.float32


def test_zamba2_hybrid_layer_compiles_for_one_chip(chip):
    """One hybrid layer of Zamba2-7B (a shared attention+MLP block over
    the 7168-wide concat, then a Mamba-2 layer) at its published widths,
    as the split runtime runs it on 4 prompts of 2048 tokens."""
    import repro.configs as configs
    from repro.models import lm
    from repro.models.common import InitBuilder

    cfg = configs.get("zamba2-7b").replace(n_layers=7)
    chain = lm.LMChain(cfg)
    shapes = jax.eval_shape(lambda k: chain.block_params(
        lm.build_params(cfg, InitBuilder(k, jnp.bfloat16))),
        jax.random.PRNGKey(0))
    i = 1 + cfg.hybrid_layer_ids[0]                 # after the embed block
    p = jax.tree.map(lambda a: _spec(a.shape, a.dtype, chip), shapes[i])
    assert "shared" in p
    act = _spec((4, 2048, cfg.d_model), jnp.bfloat16, chip)
    compiled = jax.jit(chain.blocks[i][1].apply).lower(
        p, {"residual": act, "embed": act}).compile()
    mem = compiled.memory_analysis()
    assert 0 < mem.temp_size_in_bytes + mem.argument_size_in_bytes < 16e9
