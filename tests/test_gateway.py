"""ServeGate: the multi-tenant serving gateway.

The fairness/ordering matrix — {socket, shmem} x {2, 8 tenants} x
{uniform, bursty} — asserts the gateway's core contract: every tenant's
results come back in per-tenant submit order, **bit-identical** to a
solo run of the same requests (the gateway pads every micro-batch to
``max_batch`` rows, which is what makes coalesced compute row-position
invariant), with zero cross-tenant leakage and zero sanitizer
violations.  On top of the matrix: a chaos worker-kill proving
per-tenant replay isolation, the AIMD admission window under SLO
pressure, fleet-objective aggregation, QoS decomposition, cancellation
through the CANCEL fence, the demux's one host copy per micro-batch,
and the deep-sanitize tier end to end.
"""
import time

import jax
import numpy as np
import pytest

from repro.core import scenarios
from repro.core.autosplit import AdaptiveSplitter
from repro.core.devices import LAN_PI_GPU
from repro.runtime import (EdgePipeline, FaultPlan, FleetController,
                           Gateway, QoSRecord, drain_qos, drain_recoveries,
                           drain_violations)
from repro.runtime.serve import _Member, _Req

MAX_BATCH = 8
N_REQS = 3                                    # requests per tenant
NAMES = [f"tenant{i}" for i in range(8)]


def _tiny_model():
    from repro.models.cnn.layers import (Conv2D, Flatten, Linear, Pool,
                                         ReLU, Sequential)
    from repro.models.cnn.zoo import CNNModel
    blocks = [
        ("conv0", Sequential([Conv2D(3, 8, 3, 1, 1), ReLU()])),
        ("conv1", Sequential([Conv2D(8, 8, 3, 1, 1), ReLU()])),
        ("pool", Pool("max", 2, 2)),
        ("conv2", Sequential([Conv2D(8, 16, 3, 1, 1), ReLU()])),
        ("head", Sequential([Flatten(), Linear(16 * 16 * 16, 10)])),
    ]
    return CNNModel("tinycnn", blocks, input_hw=32)


@pytest.fixture(scope="module")
def tiny():
    m = _tiny_model()
    return m, m.init(jax.random.PRNGKey(0))


def _requests():
    """The same per-tenant request tensors for every run — distinct
    per (tenant, req) so leakage or reordering shows up in the bits."""
    return {n: [np.asarray(jax.random.normal(
                    jax.random.PRNGKey(1000 + 10 * i + j), (1, 32, 32, 3)))
                for j in range(N_REQS)]
            for i, n in enumerate(NAMES)}


@pytest.fixture(scope="module")
def solo_refs(tiny):
    """Each tenant served *alone* through its own gateway (emulated),
    with the same ``max_batch`` padding as every mixed run — the
    bit-identity baseline for the whole matrix."""
    m, params = tiny
    reqs = _requests()
    pipe = EdgePipeline(m, params, 2, [LAN_PI_GPU], sanitize=True)
    pipe.warmup(reqs[NAMES[0]][0])
    refs = {}
    for n in NAMES:
        with Gateway(pipe, [scenarios.TenantSpec(n)], max_batch=MAX_BATCH,
                     batch_window_s=0.0) as gw:
            c = gw.client(n)
            for x in reqs[n]:
                c.submit(x)
            refs[n] = c.drain()
        assert [r for r, _ in refs[n]] == list(range(N_REQS))
    assert drain_violations() == []
    drain_qos()
    pipe.close()
    return reqs, refs


def _run_mixed(tiny, transport, mix_name, reqs):
    """One mixed run: every tenant in the mix submits its requests
    (interleaved for uniform mixes, per-tenant bursts for bursty ones),
    then the gateway drains.  Returns per-tenant results + QoS."""
    m, params = tiny
    mix = scenarios.get_tenant_mix(mix_name)
    names = [t.name for t in mix.tenants]
    pipe = EdgePipeline(m, params, 2, [LAN_PI_GPU], transport=transport,
                        sanitize=True, timeout_s=120)
    with pipe:
        pipe.warmup(reqs[names[0]][0])
        with Gateway(pipe, mix, max_batch=MAX_BATCH,
                     batch_window_s=0.005) as gw:
            clients = {n: gw.client(n) for n in names}
            if mix.arrival == "bursty":
                for n in names:               # whole burst back-to-back
                    for x in reqs[n]:
                        clients[n].submit(x)
            else:
                for j in range(N_REQS):       # round-robin interleave
                    for n in names:
                        clients[n].submit(reqs[n][j])
            got = {n: clients[n].drain() for n in names}
            qos = gw.drain_qos()
    assert drain_violations() == []
    return names, got, qos


# --------------------------------------------------------------------------- #
# the fairness/ordering matrix
# --------------------------------------------------------------------------- #
@pytest.mark.parametrize("transport", ["socket", "shmem"])
@pytest.mark.parametrize("mix_name", ["duo_uniform", "duo_bursty",
                                      "octet_uniform", "octet_bursty"])
def test_gateway_matrix_bit_identical_to_solo(tiny, solo_refs, transport,
                                              mix_name):
    reqs, refs = solo_refs
    names, got, qos = _run_mixed(tiny, transport, mix_name, reqs)
    for n in names:
        # per-tenant submit order, nothing lost, nothing duplicated
        assert [r for r, _ in got[n]] == list(range(N_REQS))
        # zero leakage: every value bit-identical to the solo run
        for (_, y), (_, ref) in zip(got[n], refs[n]):
            assert np.array_equal(np.asarray(y), np.asarray(ref)), \
                f"tenant {n} leaked or corrupted under {mix_name}"
    # every request is accounted for in QoS, attributed to its tenant
    assert sorted((r.tenant, r.req_id) for r in qos) == \
        sorted((n, j) for n in names for j in range(N_REQS))
    if len(names) == 8:                       # octet: coalescing happened
        assert max(r.coalesced for r in qos) >= 2


# --------------------------------------------------------------------------- #
# chaos: worker kill mid-stream, per-tenant replay isolation
# --------------------------------------------------------------------------- #
def test_gateway_survives_worker_kill_bit_identical(tiny, solo_refs):
    """A SIGKILLed stage mid-stream: supervised recovery replays the
    retained (padded) micro-batches, and every tenant still gets its
    full result stream bit-identical to solo — a fault on a shared
    batch never bleeds across the tenants riding it."""
    reqs, refs = solo_refs
    m, params = tiny
    drain_recoveries()
    mix = scenarios.get_tenant_mix("duo_uniform")
    names = [t.name for t in mix.tenants]
    plan = FaultPlan().kill_worker(stage=1, at_seq=2)
    pipe = EdgePipeline(m, params, 2, [LAN_PI_GPU], transport="shmem",
                        fault_plan=plan, stall_timeout_s=2.0,
                        timeout_s=120, sanitize=True)
    with pipe:
        pipe.warmup(reqs[names[0]][0])
        with Gateway(pipe, mix, max_batch=MAX_BATCH,
                     batch_window_s=0.0) as gw:
            clients = {n: gw.client(n) for n in names}
            for j in range(N_REQS):
                for n in names:
                    clients[n].submit(reqs[n][j])
            got = {n: clients[n].drain() for n in names}
    assert [r.kind for r in drain_recoveries()] == ["restart"]
    assert drain_violations() == []
    for n in names:
        assert [r for r, _ in got[n]] == list(range(N_REQS))
        for (_, y), (_, ref) in zip(got[n], refs[n]):
            assert np.array_equal(np.asarray(y), np.asarray(ref))


# --------------------------------------------------------------------------- #
# QoS decomposition
# --------------------------------------------------------------------------- #
def test_qos_records_decompose_latency(tiny):
    m, params = tiny
    reqs = _requests()
    drain_qos()
    pipe = EdgePipeline(m, params, 2, [LAN_PI_GPU], sanitize=True)
    pipe.warmup(reqs[NAMES[0]][0])
    mix = scenarios.get_tenant_mix("duo_uniform")
    with Gateway(pipe, mix, max_batch=MAX_BATCH, batch_window_s=0.0) as gw:
        for j in range(N_REQS):
            for t in mix.tenants:
                gw.submit(t.name, reqs[t.name][j])
        gw.drain()
        qos = gw.drain_qos()
    assert len(qos) == 2 * N_REQS
    for r in qos:
        assert isinstance(r, QoSRecord)
        assert r.queue_s >= 0 and r.service_s > 0
        assert r.latency_s == pytest.approx(r.queue_s + r.service_s)
        assert r.rows == 1 and 1 <= r.coalesced <= MAX_BATCH
        assert 0 < r.occupancy <= 1
        assert r.slo_s == gw.tenants[r.tenant].slo_s
        assert r.violated == (r.latency_s > r.slo_s)
    # gateway-scoped drain already claimed them: the global log is clean
    assert drain_qos() == []
    assert drain_violations() == []
    pipe.close()


# --------------------------------------------------------------------------- #
# SLO-aware AIMD admission
# --------------------------------------------------------------------------- #
def test_aimd_window_throttles_then_recovers(tiny):
    """An SLO-violating tenant drives multiplicative decrease down to a
    1-batch window; clean traffic afterwards grows it back additively."""
    m, params = tiny
    reqs = _requests()
    pipe = EdgePipeline(m, params, 2, [LAN_PI_GPU], sanitize=True)
    pipe.warmup(reqs[NAMES[0]][0])
    tenants = [scenarios.TenantSpec("hot", slo_s=1e-9),   # always violates
               scenarios.TenantSpec("cool", slo_s=30.0)]  # never does
    with Gateway(pipe, tenants, max_batch=MAX_BATCH, batch_window_s=0.0,
                 inflight=4, ai_every=1) as gw:
        cap = gw.inflight_window
        assert cap >= 2
        for j in range(N_REQS):               # phase 1: violations
            gw.submit("hot", reqs[NAMES[0]][j])
            gw.drain()
        assert gw.inflight_window == 1        # halved to the floor
        assert gw.session.inflight == 1       # applied to the session
        for j in range(N_REQS * 2):           # phase 2: clean traffic
            gw.submit("cool", reqs[NAMES[1]][j % N_REQS])
            gw.drain()
        assert gw.inflight_window > 1         # additive recovery
        assert gw.inflight_window <= cap
        # history records both directions of the excursion
        wins = [w for _, w in gw.window_history]
        assert min(wins) == 1 and wins[0] == cap and wins[-1] > 1
        qos = gw.drain_qos()
        assert all(r.violated for r in qos if r.tenant == "hot")
        assert not any(r.violated for r in qos if r.tenant == "cool")
    assert drain_violations() == []
    pipe.close()


# --------------------------------------------------------------------------- #
# fleet-level objectives
# --------------------------------------------------------------------------- #
def test_fleet_controller_aggregates_and_steers(tiny):
    m, params = tiny
    reqs = _requests()
    scen = scenarios.get("pi_pi_gpu")
    graph = m.block_graph(input_hw=32)
    # hysteresis ~1: the fleet axis steers the policy, but no migration
    # fires — delivery determinism is owned by the matrix tests above
    splitter = AdaptiveSplitter(graph, scen, batch=MAX_BATCH,
                                policy="throughput", hysteresis=0.99)
    splitter.current = splitter.solve()
    ctrl = FleetController(splitter, check_every=2, probe=False)
    pipe = EdgePipeline(m, params, splitter.current.partition, scen,
                        sanitize=True)
    pipe.warmup(reqs[NAMES[0]][0])
    mix = scenarios.get_tenant_mix("octet_mixed_slo")
    with Gateway(pipe, mix, controller=ctrl, max_batch=MAX_BATCH,
                 batch_window_s=0.005) as gw:
        for j in range(N_REQS):
            for t in mix.tenants:
                gw.submit(t.name, reqs[t.name][j])
        gw.drain()
        obj = ctrl.fleet_objectives()
        assert obj is not None
        assert obj.n == len(gw.qos_recent)
        assert obj.p99_s >= obj.p50_s > 0
        assert obj.aggregate_ips > 0
        assert obj.j_per_request >= 0
        assert 0 <= obj.violation_rate <= 1
        assert obj.strictest_slo_s == min(t.slo_s for t in mix.tenants)
        assert obj.policy in ("latency", "throughput")
        assert obj.policy == splitter.policy  # the steer was applied
        assert ctrl.fleet_history             # one per control decision
        gw.drain_qos()
    assert drain_violations() == []
    pipe.close()


# --------------------------------------------------------------------------- #
# cancellation through the gateway
# --------------------------------------------------------------------------- #
def test_gateway_cancel_resubmit_and_skip(tiny, solo_refs):
    reqs, refs = solo_refs
    m, params = tiny
    pipe = EdgePipeline(m, params, 2, [LAN_PI_GPU], sanitize=True)
    pipe.warmup(reqs[NAMES[0]][0])
    mix = scenarios.get_tenant_mix("duo_uniform")
    names = [t.name for t in mix.tenants]
    with Gateway(pipe, mix, max_batch=4, batch_window_s=0.0) as gw:
        clients = {n: gw.client(n) for n in names}
        for j in range(N_REQS):
            for n in names:
                clients[n].submit(reqs[n][j])
        flushed = gw.cancel_inflight(action="resubmit")
        got = {n: clients[n].drain() for n in names}
        # every flushed request redelivered, in order, bit-identical
        for n in names:
            assert [r for r, _ in got[n]] == list(range(N_REQS))
            for (_, y), (_, ref) in zip(got[n], refs[n]):
                assert np.array_equal(np.asarray(y), np.asarray(ref))
        # skip: flushed requests surface as (req_id, None) placeholders
        for n in names:
            clients[n].submit(reqs[n][0])
        flushed2 = gw.cancel_inflight(action="skip")
        got2 = {n: clients[n].drain() for n in names}
        skipped = [rv for n in names for rv in got2[n] if rv[1] is None]
        assert len(skipped) == flushed2
        assert flushed >= 0 and flushed2 >= 0
        # the fence is async: pump the discarded arrivals home, then
        # every CancelRecord must show its batch flushed
        gw.session.drain()
        cancels = gw.session.drain_cancels()
        assert all(c.flushed for c in cancels)
    assert drain_violations() == []
    pipe.close()


# --------------------------------------------------------------------------- #
# deep sanitize tier, end to end
# --------------------------------------------------------------------------- #
def test_gateway_clean_under_deep_sanitize(tiny, solo_refs, monkeypatch):
    """``REPRO_SANITIZE_DEEP=1``: full-payload crc32 fingerprints on
    every sanitized hop.  A clean mixed run must stay silent — and still
    be bit-identical to solo."""
    reqs, refs = solo_refs
    m, params = tiny
    monkeypatch.setenv("REPRO_SANITIZE_DEEP", "1")
    pipe = EdgePipeline(m, params, 2, [LAN_PI_GPU], sanitize=True)
    pipe.warmup(reqs[NAMES[0]][0])
    mix = scenarios.get_tenant_mix("duo_uniform")
    names = [t.name for t in mix.tenants]
    with Gateway(pipe, mix, max_batch=MAX_BATCH, batch_window_s=0.0) as gw:
        clients = {n: gw.client(n) for n in names}
        for j in range(N_REQS):
            for n in names:
                clients[n].submit(reqs[n][j])
        got = {n: clients[n].drain() for n in names}
    for n in names:
        for (_, y), (_, ref) in zip(got[n], refs[n]):
            assert np.array_equal(np.asarray(y), np.asarray(ref))
    assert drain_violations() == []
    pipe.close()


# --------------------------------------------------------------------------- #
# the demux: one host copy per micro-batch
# --------------------------------------------------------------------------- #
class _FetchOnly:
    """A micro-batch output that counts its copies to the host and
    refuses to be sliced where it lies."""

    def __init__(self, buf: np.ndarray):
        self.buf = buf
        self.copies = 0

    def __array__(self, dtype=None, copy=None):
        self.copies += 1
        return self.buf

    def __getitem__(self, key):
        raise AssertionError("the output was sliced before its copy")


@pytest.mark.parametrize("deterministic", [True, False])
@pytest.mark.parametrize("rows", [[3], [2, 1], [3, 1, 4]])
def test_deliver_copies_each_micro_batch_once(tiny, rows, deterministic):
    m, params = tiny
    pipe = EdgePipeline(m, params, 2, [LAN_PI_GPU])
    tenants = [scenarios.TenantSpec("a", slo_s=30.0),
               scenarios.TenantSpec("b", slo_s=30.0)]
    with Gateway(pipe, tenants, max_batch=MAX_BATCH,
                 deterministic=deterministic) as gw:
        members, row0 = [], 0
        for j, r in enumerate(rows):
            req = _Req(j, np.zeros((r, 32, 32, 3), np.float32),
                       time.perf_counter())
            members.append(_Member(tenants[j % 2].name, req, row0))
            row0 += r
        n_out = MAX_BATCH if deterministic else row0
        buf = np.asarray(jax.random.normal(jax.random.PRNGKey(len(rows)),
                                           (n_out, 10)))
        buf.flags.writeable = False           # as a device array reads
        out = _FetchOnly(buf)
        gw._members[0] = members
        gw._deliver(0, out, time.perf_counter())
        assert out.copies == 1
        got = [gw._results[mb.tenant].popleft()[1] for mb in members]
        qos = gw.drain_qos()
    pipe.close()
    assert [q.rows for q in qos] == rows
    for mb, y in zip(members, got):
        ref = buf[mb.row0:mb.row1]
        assert y.dtype == ref.dtype
        assert np.array_equal(y.view(np.uint32), ref.view(np.uint32))
        assert y.flags.owndata and not np.shares_memory(y, buf)
    for i, y in enumerate(got):
        for z in got[i + 1:]:
            assert not np.shares_memory(y, z)


# --------------------------------------------------------------------------- #
# tenant-mix specs
# --------------------------------------------------------------------------- #
def test_tenant_mix_registry_and_validation():
    for name in ("duo_uniform", "duo_bursty", "octet_uniform",
                 "octet_bursty", "octet_mixed_slo"):
        mix = scenarios.get_tenant_mix(name)
        assert mix.n_tenants in (2, 8)
        assert len({t.name for t in mix.tenants}) == mix.n_tenants
        assert all(t.slo_s > 0 and t.weight > 0 and t.burst >= 1
                   for t in mix.tenants)
    with pytest.raises(KeyError):
        scenarios.get_tenant_mix("nope")
    with pytest.raises(ValueError):
        scenarios.TenantSpec("t", slo_s=-1.0)
    mix = scenarios.get_tenant_mix("octet_mixed_slo")
    assert mix.spec("tenant0").slo_s != mix.spec("tenant7").slo_s


def test_gateway_rejects_bad_requests(tiny):
    m, params = tiny
    pipe = EdgePipeline(m, params, 2, [LAN_PI_GPU])
    with Gateway(pipe, [scenarios.TenantSpec("a")], max_batch=2) as gw:
        with pytest.raises(KeyError, match="unknown tenant"):
            gw.submit("nope", np.zeros((1, 32, 32, 3), np.float32))
        with pytest.raises(ValueError, match="exceeds"):
            gw.submit("a", np.zeros((3, 32, 32, 3), np.float32))
        with pytest.raises(ValueError, match="batched"):
            gw.submit("a", np.float32(1.0))
    with pytest.raises(ValueError, match="at least one tenant"):
        Gateway(pipe, [])
    pipe.close()
