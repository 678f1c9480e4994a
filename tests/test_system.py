"""End-to-end behaviour of the paper's system.

The paper's pipeline (Alg. 1) at both scales: the edge emulation with
real execution, and the launcher drivers with fault injection —
including the two headline properties: (1) partitioning never changes
model outputs; (2) a crashed run resumes bit-exact.
"""
import os
import subprocess
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

pytestmark = pytest.mark.slow          # subprocess train/serve drills

SRC = os.path.join(os.path.dirname(__file__), "..", "src")


def _run(args, timeout=600, env_extra=None):
    env = dict(os.environ, PYTHONPATH=SRC)
    env.pop("XLA_FLAGS", None)
    if env_extra:
        env.update(env_extra)
    return subprocess.run([sys.executable, "-m", *args], env=env,
                          capture_output=True, text=True, timeout=timeout)


def test_end_to_end_paper_pipeline():
    """ParetoPipe, start to finish: profile → sweep → front → deploy the
    chosen split on the executable pipeline → outputs match the
    unpartitioned model."""
    from repro.core import best_throughput, pareto_front, sweep_2way
    from repro.core import scenarios
    from repro.core.devices import Link
    from repro.models.cnn import zoo
    from repro.runtime.edge import EdgePipeline

    m = zoo.get("mobilenetv2")
    params = m.init(jax.random.PRNGKey(0))
    graph = m.block_graph()
    scen = scenarios.get("pi_to_pi")
    pts = sweep_2way(graph, scen.devices, scen.links[0], batch=8)
    front = pareto_front(pts)
    assert 2 <= len(front) <= len(pts)
    pick = best_throughput(pts)

    x = jax.random.normal(jax.random.PRNGKey(1), (4, 64, 64, 3))
    ref = m.apply(params, x)
    pipe = EdgePipeline(m, params, p=pick.partition[0],
                        link=Link("fast", 1e-5, 1e12))
    y, latency, _ = pipe.run_one(x)
    assert jnp.allclose(ref, y, atol=1e-5)
    assert latency > 0


def test_train_crash_restart_cli(tmp_path):
    """The launcher drill: run with fault injection, resume, finish."""
    ckpt = str(tmp_path / "ck")
    args = ["repro.launch.train", "--arch", "qwen3-1.7b", "--reduced",
            "--steps", "16", "--batch", "2", "--seq", "32",
            "--ckpt-dir", ckpt, "--ckpt-every", "5", "--log-every", "5"]
    crashed = _run(args + ["--fail-at-step", "9"])
    assert crashed.returncode == 42, crashed.stdout + crashed.stderr
    resumed = _run(args)
    assert resumed.returncode == 0, resumed.stdout + resumed.stderr
    assert "[resume] step 5" in resumed.stdout
    assert "[done] 16 steps" in resumed.stdout


def test_serve_cli():
    cp = _run(["repro.launch.serve", "--arch", "qwen3-1.7b", "--reduced",
               "--batch", "2", "--prompt-len", "16", "--new-tokens", "4"])
    assert cp.returncode == 0, cp.stdout + cp.stderr
    assert "decode:" in cp.stdout


def test_train_pipeline_cli_with_auto_partition():
    """Multi-pod GPipe on forced host devices + ParetoPipe-chosen cuts."""
    cp = _run(["repro.launch.train", "--arch", "qwen3-1.7b", "--reduced",
               "--steps", "3", "--batch", "4", "--seq", "32",
               "--pods", "2", "--data-par", "2", "--model-par", "2",
               "--microbatches", "2", "--auto-partition", "--log-every", "1"],
              env_extra={"REPRO_HOST_DEVICES": "8"})
    assert cp.returncode == 0, cp.stdout + cp.stderr
    assert "[paretopipe] cuts=" in cp.stdout
    assert "step     2" in cp.stdout


def test_loss_decreases_on_learnable_task():
    """Repeated steps on a fixed batch (memorization) — loss must drop
    substantially (end-to-end learning sanity)."""
    import repro.configs as configs
    from repro.data.pipeline import DataConfig, SyntheticLM
    from repro.optim import OptConfig
    from repro.runtime.steps import init_train_state, make_train_step

    cfg = configs.reduced("qwen3-1.7b").replace(n_layers=2, d_model=64,
                                                vocab=64)
    state = init_train_state(cfg, jax.random.PRNGKey(0), OptConfig())
    data = SyntheticLM(cfg, DataConfig(batch=4, seq=32))
    batch = next(data)                       # memorize one batch
    step = jax.jit(make_train_step(cfg, OptConfig(lr=3e-3)))
    first = None
    for i in range(60):
        state, metrics = step(state, batch)
        if first is None:
            first = float(metrics["loss"])
    last = float(metrics["loss"])
    assert last < first * 0.7, (first, last)


def test_compile_cache_dir(monkeypatch, tmp_path):
    """The entry points' compile cache: JAX_COMPILATION_CACHE_DIR wins
    and is left to JAX; otherwise a fixed, git-ignored <repo>/.jax_cache."""
    from pathlib import Path

    from repro.launch.compile_cache import REPO_CACHE_DIR, enable_compile_cache

    repo = Path(__file__).resolve().parents[1]
    assert REPO_CACHE_DIR == repo / ".jax_cache"
    assert ".jax_cache/" in (repo / ".gitignore").read_text().splitlines()
    was = jax.config.jax_compilation_cache_dir
    try:
        monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
        assert enable_compile_cache() == str(REPO_CACHE_DIR)
        assert jax.config.jax_compilation_cache_dir == str(REPO_CACHE_DIR)
        jax.config.update("jax_compilation_cache_dir", was)
        monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
        assert enable_compile_cache() == str(tmp_path)
        assert jax.config.jax_compilation_cache_dir == was
    finally:
        jax.config.update("jax_compilation_cache_dir", was)


def test_chip_smoke_refuses_without_a_chip():
    """chip_smoke.py exits non-zero before any work, and prints no
    result, when JAX has no TPU."""
    root = os.path.join(os.path.dirname(__file__), "..")
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("XLA_FLAGS", None)
    cp = subprocess.run([sys.executable, "chip_smoke.py"], cwd=root, env=env,
                        capture_output=True, text=True, timeout=120)
    assert cp.returncode != 0, cp.stdout + cp.stderr
    assert cp.stdout == ""
    assert "needs 1 TPU chip" in cp.stderr
