"""Per-architecture smoke tests (assignment requirement): a REDUCED
same-family config runs one forward/train step on CPU with correct
output shapes and no NaNs.  The FULL configs are exercised only via the
dry-run."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

pytestmark = pytest.mark.slow          # one jitted train step per arch

import repro.configs as configs
from repro.data.pipeline import DataConfig, SyntheticLM
from repro.models import lm
from repro.models.common import InitBuilder
from repro.optim import OptConfig
from repro.runtime.steps import init_train_state, make_train_step


@pytest.mark.parametrize("name", configs.ARCH_NAMES)
def test_reduced_forward_shapes_and_finite(name):
    cfg = configs.reduced(name)
    params = lm.build_params(cfg, InitBuilder(jax.random.PRNGKey(0),
                                              jnp.float32))
    data = SyntheticLM(cfg, DataConfig(batch=2, seq=32))
    inputs = {k: v for k, v in next(data).items() if k != "targets"}
    logits, aux = lm.forward_train(cfg, params, inputs)
    S = 32
    assert logits.shape == (2, S, cfg.vocab)
    assert bool(jnp.all(jnp.isfinite(logits)))
    assert np.isfinite(float(aux))


@pytest.mark.parametrize("name", configs.ARCH_NAMES)
def test_reduced_train_step(name):
    cfg = configs.reduced(name)
    state = init_train_state(cfg, jax.random.PRNGKey(0), OptConfig())
    data = SyntheticLM(cfg, DataConfig(batch=2, seq=32))
    step = jax.jit(make_train_step(cfg, OptConfig(lr=1e-3)))
    state, m = step(state, next(data))
    assert np.isfinite(float(m["loss"]))
    assert np.isfinite(float(m["grad_norm"]))
    assert int(state["step"]) == 1


def test_full_configs_match_published_param_counts():
    """Analytic N vs published totals (±7%, ±12% for whisper — publications
    round and our whisper variant simplifies positional details)."""
    published = {
        "phi-3-vision-4.2b": 3.8e9,       # backbone (phi3-mini) only
        "falcon-mamba-7b": 7.3e9,
        "starcoder2-3b": 3.0e9,
        "qwen3-1.7b": 1.7e9,
        "granite-20b": 20e9,
        "starcoder2-7b": 7.2e9,
        "whisper-small": 0.244e9,
        "qwen3-moe-30b-a3b": 30.5e9,
        "phi3.5-moe-42b-a6.6b": 42e9,
        "zamba2-7b": 7.4e9,
    }
    loose = {"whisper-small": 0.12}
    for name, target in published.items():
        n = configs.get(name).param_count()
        tol = loose.get(name, 0.07)
        assert abs(n - target) / target < tol, (name, f"{n:,}", target)


def test_moe_active_params():
    qwen = configs.get("qwen3-moe-30b-a3b")
    assert 2.5e9 < qwen.active_param_count() < 4.0e9      # "a3b"
    phi = configs.get("phi3.5-moe-42b-a6.6b")
    assert 5.5e9 < phi.active_param_count() < 7.7e9       # "a6.6b"


def test_long_context_support_flags():
    assert configs.get("falcon-mamba-7b").supports_long_context
    assert configs.get("zamba2-7b").supports_long_context
    for name in ("qwen3-1.7b", "granite-20b", "whisper-small",
                 "phi-3-vision-4.2b", "qwen3-moe-30b-a3b"):
        assert not configs.get(name).supports_long_context
