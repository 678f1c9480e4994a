"""The Zamba2 serving cell at a small size on the CPU: its prompt traffic
offers the same work on every seed from one pool, a run through the
harness is correct on the sound path and not correct with weights
rounded to float8, the last stage broken, a hop that zeroes the
original embeddings, or shared blocks that ignore them, and the hop
counter reads both tensors of every cut."""
import json
import subprocess
import sys
import time
from pathlib import Path

import numpy as np
import pytest

from chipbench import run as R
from chipbench.traffic import open_loop, open_loop_prompts

BENCH = Path(__file__).parent
CELL = "zamba2-7b-l24.split3.prompt2k.poisson"
# width 512 and eight layers: at the published init a smaller model
# moves its logits too little under float8 weights for the cell's limit
SMALL = {"config": {"hidden_size": 512, "num_hidden_layers": 8,
                    "vocab_size": 1024, "n_mamba_heads": 16,
                    "mamba_headdim": 64, "chunk_size": 16,
                    "num_attention_heads": 8, "num_key_value_heads": 8,
                    "attention_head_dim": 128, "intermediate_size": 1024,
                    "hybrid_layer_ids": [1, 3, 5, 7]},
         "cell": {"cuts": [3, 6], "rate_per_s": 4.0},
         "traffic": {"pool_images": 8, "prompt_tokens": 32}}


def test_prompt_traffic_is_open_loops_over_a_pool_of_prompts():
    traffic = json.loads((BENCH / "traffic" / "poisson_prompts.json")
                         .read_text())
    cell = json.loads((BENCH / "cells" / f"{CELL}.json").read_text())
    a = open_loop_prompts.schedule(traffic, cell, 2**31 + 9, 51.0)
    b = open_loop.schedule(traffic, cell, 2**31 + 9, 51.0)
    assert all(np.array_equal(a[k], b[k]) for k in a)
    assert set(a["rows"]) == {1, 2}
    assert np.all(a["first"] + a["rows"] <= traffic["pool_images"])
    p = open_loop_prompts.prompt_pool(2**31 + 9, 32, 2048, 32000)
    q = open_loop_prompts.prompt_pool(2**31 + 9, 32, 2048, 32000)
    assert p.shape == (32, 2048) and p.dtype == np.int32
    assert np.array_equal(p, q) and 0 <= p.min() and p.max() < 32000


def _run(fault=None, monkeypatch=None):
    import jax.numpy as jnp
    from chipbench.reference import zamba2 as zref
    from chipbench.systems import lm_split_serving as lss
    from repro.models import lm
    from repro.runtime import edge, transport
    if fault == "hop_zeroes_embed":
        hop = transport.EmulatedChannel._roundtrip_one

        def zeroing(self, payload, tensor):
            wire, raw, out = hop(self, payload, tensor)
            return wire, raw, (jnp.zeros_like(out) if tensor == "embed"
                               else out)
        monkeypatch.setattr(transport.EmulatedChannel, "_roundtrip_one",
                            zeroing)
    elif fault == "shared_blocks_skip_embed":
        block = lm.mem_block
        monkeypatch.setattr(lm, "mem_block", lambda cfg, sp, ap, x, emb, *a,
                            **kw: block(cfg, sp, ap, x, jnp.zeros_like(emb),
                                        *a, **kw))
    elif fault == "float8_weights":
        orig = zref.program_params
        monkeypatch.setattr(zref, "program_params",
                            lambda w, c: lss.quantized_weights(orig(w, c)))
    elif fault == "last_stage_rows":
        run = edge.Worker.run

        def broken(self, x):
            y = run(self, x)
            if self.hi != SMALL["config"]["num_hidden_layers"] + 2:
                return y
            return jnp.concatenate([y[:1], y[:-1]])   # rows shifted
        monkeypatch.setattr(edge.Worker, "run", broken)
    return R.run(CELL, 2**31 + 11, 2.0, False, time.time(),
                 require_chip=False, cell_overrides=SMALL)


@pytest.mark.parametrize("fault,caught_by", [
    (None, None), ("float8_weights", "row_rel_err"),
    ("last_stage_rows", "row_rel_err"), ("hop_zeroes_embed", "cut_embed_err"),
    ("shared_blocks_skip_embed", "embed_effect_lost")])
def test_correct_only_on_the_sound_path(monkeypatch, fault, caught_by):
    result, checks = _run(fault, monkeypatch)
    assert result["correct"] is (fault is None), checks
    assert result["failed"] == 0
    over = {n for n, v, lim in checks if not v <= lim}
    assert (caught_by in over) if fault else not over, checks


def test_hop_counter_reads_both_tensors_of_every_cut():
    from chipbench.systems.lm_split_serving import hop_mb_per_batch
    before = [({"residual": 10, "embed": 10}, 1)] * 2
    after = [({"residual": 30, "embed": 30}, 3)] * 2
    assert hop_mb_per_batch(before, after) == pytest.approx(40 / 1e6)
    assert hop_mb_per_batch(before, [({}, 3), ({}, 3)]) is None
    assert hop_mb_per_batch([], []) is None


def test_parent_without_the_model_fails_cleanly():
    """Without a TPU the runner exits non-zero and prints no result line,
    for this cell too."""
    out = subprocess.run(
        [sys.executable, str(BENCH / "run.py"), "--workload", CELL,
         "--seed", str(2**31 + 7), "--seconds", "1", "--trace", "0"],
        env={**__import__("os").environ, "JAX_PLATFORMS": "cpu"},
        capture_output=True, text=True, timeout=300)
    assert out.returncode != 0 and out.stdout.strip() == ""
