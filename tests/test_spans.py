"""The host spans of the split-serving path, read back from a profiler
trace taken on the CPU, and the names of the stage programs.

A few micro-batches of a small CNN go through ``Gateway`` over a
3-stage ``EdgePipeline`` on the thread engine with the profiler on:
every micro-batch must leave exactly its spans, each carrying its seq.
"""
import glob
import math
import os
import threading
from collections import Counter, defaultdict

import jax
import numpy as np
import pytest
from jax.profiler import ProfileData

from repro.core.devices import Link
from repro.core.scenarios import TenantSpec
from repro.runtime import EdgePipeline, Gateway, Worker
from repro.runtime import spans

MAX_BATCH = 4
N_REQS = 6                                    # 2 rows each: 3 micro-batches
FREE = [Link(f"free{i}", rtt_s=0.0, bw_bytes_per_s=math.inf)
        for i in range(2)]


def _tiny_model():
    from repro.models.cnn.layers import (Conv2D, Flatten, Linear, Pool,
                                         ReLU, Sequential)
    from repro.models.cnn.zoo import CNNModel
    blocks = [
        ("conv0", Sequential([Conv2D(3, 8, 3, 1, 1), ReLU()])),
        ("conv1", Sequential([Conv2D(8, 8, 3, 1, 1), ReLU()])),
        ("pool", Pool("max", 2, 2)),
        ("conv2", Sequential([Conv2D(8, 16, 3, 1, 1), ReLU()])),
        ("head", Sequential([Flatten(), Linear(16 * 8 * 8, 10)])),
    ]
    return CNNModel("tinycnn", blocks, input_hw=16)


@pytest.fixture(scope="module")
def tiny():
    m = _tiny_model()
    return m, m.init(jax.random.PRNGKey(0))


def _host_spans(trace_dir) -> list[tuple[str, dict]]:
    """(name, metadata) of every span of ``spans``'s table in the trace."""
    (path,) = glob.glob(os.path.join(trace_dir, "plugins", "profile", "*",
                                     "*.xplane.pb"))
    names = {"gateway.admit", "gateway.deliver", "gateway.fetch",
             "session.result_wait", "stage.recv_wait", "stage.dispatch",
             "stage.sync_wait", "hop.d2h", "hop.encode", "hop.decode",
             "hop.put_wait"}
    out = []
    for plane in ProfileData.from_file(path).planes:
        if plane.name != "/host:CPU":
            continue
        for line in plane.lines:
            for ev in line.events:
                if ev.name in names:
                    out.append((ev.name, dict(ev.stats)))
    return out


@pytest.mark.parametrize("codec", ["none", "int8"])
def test_every_micro_batch_leaves_its_spans_under_its_seq(tiny, tmp_path,
                                                           codec):
    """An uncoded hop copies nothing to the host, so it leaves no
    ``hop.d2h``; a coded hop copies, packs and unpacks once per batch."""
    m, params = tiny
    pipe = EdgePipeline(m, params, (1, 3), FREE, codec=codec)
    rows = np.asarray(jax.random.normal(jax.random.PRNGKey(1),
                                        (2 * N_REQS, 16, 16, 3)))
    pipe.warmup(rows[:MAX_BATCH])
    tenants = [TenantSpec("a"), TenantSpec("b")]
    with Gateway(pipe, tenants, max_batch=MAX_BATCH,
                 batch_window_s=0.0) as gw:
        with jax.profiler.trace(str(tmp_path)):
            for j in range(N_REQS):
                gw.submit(tenants[j % 2].name, rows[2 * j:2 * j + 2])
            out = gw.drain()
        coalesced = {r.seq: r.coalesced for r in gw.drain_qos()}
    pipe.close()
    assert sum(len(v) for v in out.values()) == N_REQS

    by_seq: dict[int, Counter] = defaultdict(Counter)
    where: dict[tuple[str, int], set] = defaultdict(set)
    served: dict[int, list[int]] = defaultdict(list)
    for name, ids in _host_spans(str(tmp_path)):
        by_seq[ids["seq"]][name] += 1
        if name == "gateway.fetch":
            served[ids["seq"]].append(ids["requests"])
        if "stage" in ids:
            where[(name, ids["seq"])].add(ids["stage"])
        if "hop" in ids:
            where[(name, ids["seq"])].add(ids["hop"])
    seqs = sorted(s for s in by_seq if by_seq[s]["gateway.admit"])
    assert len(seqs) >= N_REQS * 2 // MAX_BATCH
    assert seqs == list(range(len(seqs)))
    stages = {"worker1", "worker2", "worker3"}
    for s in seqs:
        n = by_seq[s]
        assert (n["gateway.admit"], n["gateway.deliver"],
                n["gateway.fetch"]) == (1, 1, 1), (s, n)
        assert served[s] == [coalesced[s]], (s, served[s])
        assert n["stage.dispatch"] == n["stage.sync_wait"] == 3, (s, n)
        copies = 2 if codec != "none" else 0
        assert n["hop.put_wait"] == 2, (s, n)
        assert n["hop.d2h"] == n["hop.encode"] == n["hop.decode"] == copies, \
            (s, n)
        assert n["session.result_wait"] >= 1, (s, n)
        assert where[("stage.dispatch", s)] == stages
        assert where[("stage.sync_wait", s)] == stages
        assert where[("hop.put_wait", s)] == {0, 1}
        for name in ("hop.d2h", "hop.encode", "hop.decode"):
            assert where[(name, s)] == ({0, 1} if copies else set())
    # no stage or hop span ran outside a micro-batch
    assert not {k for k in by_seq[-1]} & {"stage.dispatch", "hop.d2h",
                                          "hop.put_wait"}


def test_seq_is_per_thread():
    spans.set_seq(7)
    seen = []
    t = threading.Thread(target=lambda: seen.append(spans.current_seq()))
    t.start()
    t.join()
    assert (spans.current_seq(), seen) == (7, [-1])
    spans.set_seq(-1)


@pytest.mark.parametrize("backend", ["lightweight", "rpc"])
def test_stage_programs_are_named_after_their_worker(tiny, backend):
    """The stage's one program, and under ``rpc`` each block's, lowers
    to a module named after the worker and its blocks."""
    m, params = tiny
    w = Worker("worker2", m, params, 1, 4, backend)
    x = jax.ShapeDtypeStruct((MAX_BATCH, 16, 16, 8), np.float32)
    assert w.fn.lower(w.params, x).as_text().startswith(
        "module @jit_worker2_blocks_1_4 ")
    names = []
    for fn, p in w._calls:
        text = fn.lower(p, x).as_text()
        names.append(text.split(" ", 2)[1])
        x = jax.eval_shape(fn, p, x)
    assert names == (["@jit_worker2_blocks_1_4"] if backend == "lightweight"
                     else [f"@jit_worker2_blocks_{j}_{j + 1}"
                           for j in range(1, 4)])
