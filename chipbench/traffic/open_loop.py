"""Open-loop serving traffic: independent users at a fixed offered rate.

One general generator for every serving mix.  The mix's file gives the
tenants, their SLO and the rows per request; the cell gives the offered
rate.  Everything is drawn from ``--seed`` before the window opens:

* the number of requests is ``round(rate x seconds)``, the same for
  every seed, and the gaps between their due times are the quantiles of
  the exponential distribution at that rate, in a seeded order: Poisson
  arrivals with the same set of gaps on every seed;
* rows per request run evenly over ``rows_min..rows_max`` and tenants
  evenly over the tenants, each as a fixed multiset in a seeded order,
  so every seed offers the same work in another order;
* each request reads consecutive images of one pool made from the seed
  on the device, so that the reference computes each image once.

The loop is the gateway's cooperative one, in one thread: submit what
is due, and while batches are in flight block in ``poll`` for the next.
A request is timed from its due time to its delivery by ``poll``; one
not delivered by the end of a bounded drain has failed.
"""
from __future__ import annotations

import math
import time

import jax
import numpy as np

DRAIN_S = 60.0


def schedule(traffic: dict, cell: dict, seed: int, seconds: float) -> dict:
    """The requests of one run, as arrays in due order."""
    rng = np.random.default_rng(seed)
    n = max(int(round(float(cell["rate_per_s"]) * seconds)), 1)
    gaps = -np.log1p(-(np.arange(n) + 0.5) / n)     # unit-mean quantiles
    gaps *= seconds / gaps.sum()
    due = np.cumsum(rng.permutation(gaps)) - gaps.min() / 2
    lo, hi = int(traffic["rows_min"]), int(traffic["rows_max"])
    rows = rng.permutation(np.resize(np.arange(lo, hi + 1), n))
    tenants = rng.permutation(np.resize(np.arange(int(traffic["tenants"])),
                                        n))
    pool_n = int(traffic["pool_images"])
    first = rng.integers(0, pool_n - rows + 1)
    return {"due": due, "rows": rows, "tenant": tenants, "first": first}


def image_pool(seed: int, n: int, hw: int) -> np.ndarray:
    """``n`` images of ``hw`` x ``hw`` x 3, standard normal, from the
    seed: made on the device, copied to the host once."""
    key = jax.random.fold_in(jax.random.PRNGKey(seed), 1)
    return np.asarray(jax.jit(lambda k: jax.random.normal(
        k, (n, hw, hw, 3)), static_argnums=())(key))


class Window:
    """What one run's window left for the metrics and the check."""

    def __init__(self, sched, t0, seconds):
        self.sched, self.t0, self.seconds = sched, t0, seconds
        n = len(sched["due"])
        self.submitted = np.full(n, np.nan)      # s after t0
        self.delivered = np.full(n, np.nan)      # s after t0
        self.values: list = [None] * n
        self.qos: list = []
        self.epoch = 0.0
        self.longest_turn = 0.0                  # s, one turn of the loop


def prepare(sut, spec: dict, seed: int, seconds: float) -> None:
    """Set-up: the image pool from the seed, then one request of every
    row count through the gateway, so the window's first requests find
    every path warm."""
    traffic = spec["traffic"]
    sut.pool = image_pool(seed, int(traffic["pool_images"]), sut.hw)
    lo, hi = int(traffic["rows_min"]), int(traffic["rows_max"])
    for rows in range(lo, hi + 1):
        sut.submit(sut.tenant_names[rows % len(sut.tenant_names)],
                   sut.pool[:rows])
    while sut.pending:
        sut.poll(block=True)
    sut.drain_qos()


def drive(sut, spec: dict, seed: int, seconds: float, tracer,
          annotate) -> Window:
    """Run the window; → its Window.  ``tracer.tick(t)`` starts and stops
    the profiler; ``annotate(name)`` is a host span around each call."""
    sched = schedule(spec["traffic"], spec["cell"], seed, seconds)
    pool = sut.pool
    due, rows, tenant, first = (sched["due"], sched["rows"],
                                sched["tenant"], sched["first"])
    names = sut.tenant_names
    n = len(due)
    index: dict[tuple[str, int], int] = {}
    t0 = time.perf_counter()
    win = Window(sched, t0, seconds)
    i = 0

    def deliver(out):
        now = time.perf_counter() - t0
        for name, req_id, value in out:
            k = index.pop((name, req_id))
            win.delivered[k] = now
            win.values[k] = value

    last = 0.0
    while True:
        now = time.perf_counter() - t0
        win.longest_turn = max(win.longest_turn, now - last)
        last = now
        tracer.tick(now)
        if i < n and due[i] <= now:
            name = names[tenant[i]]
            x = pool[first[i]:first[i] + rows[i]]
            with annotate("bench.submit"):
                rid = sut.submit(name, x)
            index[(name, rid)] = i
            win.submitted[i] = time.perf_counter() - t0
            i += 1
            continue
        if i >= n:
            break
        if sut.in_flight:
            with annotate("bench.poll"):
                out = sut.poll(block=True)
            deliver(out)
        elif sut.pending:                      # queued, batch not ripe
            with annotate("bench.wait"):
                time.sleep(max(min(due[i] - now, sut.batch_window_s), 0.0))
            with annotate("bench.poll"):
                out = sut.poll(block=False)
            deliver(out)
        else:
            with annotate("bench.wait"):
                time.sleep(max(due[i] - now, 0.0))
    deadline = time.perf_counter() + DRAIN_S
    while sut.pending and time.perf_counter() < deadline:
        now = time.perf_counter() - t0
        tracer.tick(now)
        with annotate("bench.poll"):
            out = sut.poll(block=True)
        deliver(out)
    tracer.tick(float("inf"))
    win.qos = sut.drain_qos()
    win.epoch = sut.epoch()
    return win


def _quantile(lat: np.ndarray, q: float) -> float:
    """The ``q`` percentile, linear between order statistics; +inf once
    it reaches a missing request."""
    finite = np.sort(lat)
    pos = (len(finite) - 1) * q / 100.0
    lo, hi = int(np.floor(pos)), int(np.ceil(pos))
    if not np.isfinite(finite[hi]):
        return math.inf
    return float(finite[lo] + (finite[hi] - finite[lo]) * (pos - lo))


def end_to_end(win: Window) -> dict:
    """p50 over every request due in the window, a missing one as +inf;
    also attempted/failed, and as notes the p95 and the generator's
    lateness."""
    lat = win.delivered - win.sched["due"]
    lat = np.where(np.isnan(lat), np.inf, lat)
    late = win.submitted - win.sched["due"]
    late = late[np.isfinite(late)]
    failed = int(np.sum(~np.isfinite(lat)))
    return {
        "p50_latency_ms": _quantile(lat, 50) * 1e3,
        "attempted": len(lat), "failed": failed,
        "notes": {
            "p95_latency_ms": _quantile(lat, 95) * 1e3,
            "generator_late_p50_ms": float(np.percentile(late, 50)) * 1e3,
            "generator_late_p95_ms": float(np.percentile(late, 95)) * 1e3,
            "generator_late_max_ms": float(np.max(late)) * 1e3,
            "longest_loop_turn_ms": win.longest_turn * 1e3,
            "requests": len(lat),
            "rows": int(np.sum(win.sched["rows"])),
        },
    }


def layer_context(sut, win: Window, spec: dict, tracer, summary,
                  peaks: dict) -> dict:
    """What the per-layer readers read: the QoS records of the window's
    requests completed before the profiler started; the micro-batches
    and rows completed in the traced stretch, beside that stretch's
    trace summary; the FLOPs the system charges per image and the
    chip's peaks."""
    done: dict[int, int] = {}
    untraced = []
    for r in win.qos:
        t = win.epoch + r.t_s
        if t < tracer.untraced_until:
            untraced.append(r)
        if tracer.t_start <= t <= tracer.t_stop:
            done[r.seq] = done.get(r.seq, 0) + r.rows
    return {
        "qos": untraced,
        "traced_batches": len(done),
        "traced_rows": sum(done.values()),
        "trace": summary,
        "flops_per_item": sut.flops_per_item,
        "peaks": peaks,
    }


def check(sut, win: Window, spec: dict) -> list:
    """Every delivered answer against the reference on its images."""
    answers = [(int(win.sched["first"][k]), v)
               for k, v in enumerate(win.values) if v is not None]
    return sut.check(sut.pool, answers, spec["cell"]["limits"])
