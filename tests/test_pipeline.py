"""Multi-pod pipeline: numerical equivalence to the plain model, uneven
ParetoPipe cuts, repack/unpack roundtrip, pipelined serving."""
import os

import pytest

pytestmark = [
    pytest.mark.skipif("XLA_FLAGS" in os.environ,
                       reason="needs default device config"),
    pytest.mark.slow,                  # multi-pod GPipe drills, ~40s
]

import jax  # noqa: E402

if jax.device_count() == 1:
    # a tiny in-process multi-device mesh via the CPU collectives path is
    # unavailable once jax is initialized with 1 device; these tests run
    # in a subprocess with forced host devices instead.
    import subprocess
    import sys

    def _run_sub(code: str):
        env = dict(os.environ,
                   XLA_FLAGS="--xla_force_host_platform_device_count=8",
                   PYTHONPATH=os.path.join(os.path.dirname(__file__), "..",
                                           "src"))
        cp = subprocess.run([sys.executable, "-c", code], env=env,
                            capture_output=True, text=True, timeout=900)
        assert cp.returncode == 0, cp.stdout + "\n" + cp.stderr

    def test_pipeline_train_matches_plain():
        _run_sub("""
import jax, jax.numpy as jnp
import repro.configs as configs
from repro.models import lm
from repro.models.common import InitBuilder, cross_entropy
from repro.data.pipeline import SyntheticLM, DataConfig
from repro.runtime.pipeline import (PipelineConfig, init_pipeline_state,
                                    repack_params, make_pipeline_train_step)
from repro.optim import OptConfig, init_opt_state
from repro.launch.mesh import make_host_mesh
from repro.sharding.api import use_mesh_context

mesh = make_host_mesh(2, 2, 2)
for name in ["qwen3-1.7b", "zamba2-7b", "qwen3-moe-30b-a3b", "whisper-small", "falcon-mamba-7b"]:
    cfg = configs.reduced(name)
    params = lm.build_params(cfg, InitBuilder(jax.random.PRNGKey(0), jnp.float32))
    data = SyntheticLM(cfg, DataConfig(batch=4, seq=32))
    batch = next(data)
    logits, _ = lm.forward_train(cfg, params, {k: v for k, v in batch.items() if k != "targets"})
    ref_ce = float(cross_entropy(logits, batch["targets"]))
    pcfg = PipelineConfig.even(cfg.n_layers, 2, 2)
    key = "dec_layers" if cfg.family == "encdec" else "layers"
    pparams = dict(params); pparams[key] = repack_params(params[key], pcfg, cfg.n_layers)
    with use_mesh_context(mesh):
        state = {"params": pparams, "opt": init_opt_state(pparams), "step": jnp.int32(0)}
        step = jax.jit(make_pipeline_train_step(cfg, pcfg, OptConfig(lr=1e-3), mesh))
        state, m = step(state, batch)
        # the launcher's initializer: same weights, placed on the mesh
        placed = init_pipeline_state(cfg, pcfg, params, mesh)
        same = jax.tree.map(jnp.array_equal, placed["params"], pparams)
        assert all(map(bool, jax.tree.leaves(same))), name
        leaf = jax.tree.leaves(placed["params"][key])[0]
        assert leaf.sharding.spec[0] == "pod", (name, leaf.sharding)
        _, m2 = step(placed, batch)
    diff = abs(float(m["ce"]) - ref_ce)
    assert diff < 5e-4, (name, diff)
    assert abs(float(m2["ce"]) - ref_ce) < 5e-4, (name, float(m2["ce"]))
print("OK")
""")

    def test_pipeline_uneven_cuts_and_serving():
        _run_sub("""
import jax, jax.numpy as jnp
import repro.configs as configs
from repro.models import lm
from repro.models.common import InitBuilder
from repro.data.pipeline import SyntheticLM, DataConfig
from repro.runtime.pipeline import (PipelineConfig, repack_params,
                                    make_pipeline_prefill_step, make_pipeline_decode_step)
from repro.launch.mesh import make_host_mesh
from repro.sharding.api import use_mesh_context

mesh = make_host_mesh(2, 2, 2)
cfg = configs.reduced("qwen3-1.7b").replace(n_layers=5)   # odd → uneven
params = lm.build_params(cfg, InitBuilder(jax.random.PRNGKey(0), jnp.float32))
data = SyntheticLM(cfg, DataConfig(batch=4, seq=16))
inputs = {k: v for k, v in next(data).items() if k != "targets"}
_, ref_cache = lm.forward_prefill(cfg, params, inputs, cache_len=18)
nxt = inputs["tokens"][:, :1]
ref_lg, _ = lm.forward_decode(cfg, params, nxt, ref_cache)
for cuts in [(2,), (1,), (4,)]:               # ParetoPipe uneven splits
    pcfg = PipelineConfig(2, 2, cuts)
    pparams = dict(params)
    pparams["layers"] = repack_params(params["layers"], pcfg, cfg.n_layers)
    with use_mesh_context(mesh):
        pre = jax.jit(make_pipeline_prefill_step(cfg, pcfg, mesh, cache_len=18))
        dec = jax.jit(make_pipeline_decode_step(cfg, pcfg, mesh))
        tok, cache = pre(pparams, inputs)
        tok2, cache = dec(pparams, nxt, cache)
    assert bool(jnp.array_equal(tok2[:, 0], jnp.argmax(ref_lg[:, 0], -1))), cuts
print("OK")
""")


def test_repack_unpack_roundtrip():
    import jax.numpy as jnp
    import numpy as np
    from repro.runtime.pipeline import (PipelineConfig, repack_params,
                                        unpack_params)
    tree = {"w": jnp.arange(7 * 3 * 2, dtype=jnp.float32).reshape(7, 3, 2),
            "b": jnp.arange(7, dtype=jnp.float32)}
    for cuts in [(3,), (2,), (5,), (1, 4)]:
        pcfg = PipelineConfig(len(cuts) + 1, 2, cuts)
        packed = repack_params(tree, pcfg, 7)
        back = unpack_params(packed, pcfg, 7)
        for k in tree:
            assert np.array_equal(np.asarray(tree[k]), np.asarray(back[k])), \
                (k, cuts)


def test_stage_layout():
    from repro.runtime.pipeline import PipelineConfig
    pcfg = PipelineConfig.even(81, 2, 8)
    starts, counts, l_max = pcfg.layout(81)
    assert counts.sum() == 81 and l_max == 41
    pcfg = PipelineConfig(2, 4, (10,))        # uneven ParetoPipe cut
    starts, counts, l_max = pcfg.layout(81)
    assert list(counts) == [10, 71] and l_max == 71
