"""The comparison that decides ``correct`` for the serving cell: a run
through the harness (its look for a chip skipped, at a small image
size) is correct on the sound path and not correct with the timed path
broken underneath; and the control, the reference computed in bfloat16,
fails the limit."""
import time

import numpy as np
import pytest

from chipbench import run as R

CELL = "resnet50.paper_split.poisson"
SMALL = {"config": {"image_size": 32}, "cell": {"rate_per_s": 20.0},
         "traffic": {"pool_images": 16}}


def _broken_last_stage(monkeypatch, fault: str) -> None:
    import jax.numpy as jnp
    from repro.models.cnn import zoo
    from repro.runtime import edge
    n_blocks = len(zoo.resnet50().blocks)
    run = edge.Worker.run

    def broken(self, x):
        y = run(self, x)
        if self.hi != n_blocks:
            return y
        if fault == "half_batch":          # half the rows never computed
            h = y.shape[0] // 2
            return jnp.concatenate([y[:h], y[:y.shape[0] - h]])
        if fault == "answer_altered":      # one logit of one row changed
            return y.at[0, 0].add(0.1 * jnp.max(jnp.abs(y[0])))
        raise ValueError(fault)
    monkeypatch.setattr(edge.Worker, "run", broken)


@pytest.mark.parametrize("fault", [None, "half_batch", "answer_altered"])
def test_correct_only_on_the_sound_path(monkeypatch, fault):
    if fault is not None:
        _broken_last_stage(monkeypatch, fault)
    result, checks = R.run(CELL, 20260917, 2.0, False, time.time(),
                           require_chip=False, cell_overrides=SMALL)
    assert result["correct"] is (fault is None), checks
    assert list(result["checks"]) == [n for n, _, _ in checks]


def test_control_fails_the_limit():
    from chipbench.systems import split_serving
    spec = R.load_cell(CELL)
    images = np.random.default_rng(3).standard_normal(
        (8, 64, 64, 3)).astype(np.float32)
    (name, reading, limit), = split_serving.control_reading(
        spec["config"], 3, images, spec["cell"]["limits"])
    assert reading > limit, (name, reading, limit)
