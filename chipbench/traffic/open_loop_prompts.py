"""Open-loop serving traffic of whole prompts: ``open_loop``'s schedule,
window, end-to-end metrics and check, over a pool of token-id prompts.

The mix's file gives the tenants, their SLO, the prompts per request
(``rows_min``..``rows_max``), the pool's size (``pool_images``, the key
the schedule reads) and the prompt length (``prompt_tokens``).  The
pool is drawn from ``--seed`` on the device, uniform over the model's
vocabulary, so the reference computes each prompt once.
"""
from __future__ import annotations

import jax
import numpy as np

from chipbench.traffic import open_loop
from chipbench.traffic.open_loop import check, drive, end_to_end, schedule

__all__ = ["check", "drive", "end_to_end", "schedule", "prepare",
           "layer_context", "prompt_pool"]


def prompt_pool(seed: int, n: int, seq: int, vocab: int) -> np.ndarray:
    """``n`` prompts of ``seq`` token ids, uniform over ``[0, vocab)``."""
    key = jax.random.fold_in(jax.random.PRNGKey(seed), 2)
    return np.asarray(jax.jit(lambda k: jax.random.randint(
        k, (n, seq), 0, vocab, dtype=jax.numpy.int32))(key))


def prepare(sut, spec: dict, seed: int, seconds: float) -> None:
    """Set-up: the prompt pool from the seed, then one request of every
    prompt count through the gateway, so the window's first requests
    find every path warm, with each stage's input copied to the host
    for the check (``StageInputs``); then the hop counters' starting
    reading."""
    traffic = spec["traffic"]
    sut.pool = prompt_pool(seed, int(traffic["pool_images"]),
                           int(traffic["prompt_tokens"]), sut.vocab)
    lo, hi = int(traffic["rows_min"]), int(traffic["rows_max"])
    sut.taps.armed = True
    for rows in range(lo, hi + 1):
        sut.submit(sut.tenant_names[rows % len(sut.tenant_names)],
                   sut.pool[:rows])
    while sut.pending:
        sut.poll(block=True)
    sut.taps.armed = False
    sut.drain_qos()
    sut.hops_at_start = sut.hop_counts()


def layer_context(sut, win, spec: dict, tracer, summary,
                  peaks: dict) -> dict:
    """``open_loop``'s context, and the MB carried across every cut per
    micro-batch over the window (the program's per-tensor counter)."""
    from chipbench.systems.lm_split_serving import hop_mb_per_batch
    ctx = open_loop.layer_context(sut, win, spec, tracer, summary, peaks)
    ctx["hop_mb_per_batch"] = hop_mb_per_batch(sut.hops_at_start,
                                               sut.hop_counts())
    return ctx
