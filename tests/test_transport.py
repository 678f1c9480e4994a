"""Pluggable Transport API: emulated / socket / shmem parity, measured
TransferRecords, failure propagation, the LSQ link fit, and the trace
recorder.

The acceptance surface of the transport redesign: the same model + cuts
+ scenario must produce identical outputs and sane metrics whether the
hops are modeled sleeps between threads or real TCP / shared-memory
channels between OS processes — and the measured records must drive the
closed adaptive loop to a migration.
"""
import time

import jax
import numpy as np
import pytest

from repro.core import Scenario, scenarios
from repro.core.autosplit import LinkEstimator
from repro.core.devices import DURESS, LOOPBACK, DeviceProfile, Link
from repro.models.cnn import zoo
from repro.runtime.adaptive import AdaptiveRuntime
from repro.runtime.edge import EdgePipeline
from repro.runtime.transport import (BATCH, PROBE, HopSpec, ShmemChannel,
                                     SocketChannel, TransferRecord,
                                     TransportError, as_jax, get_transport,
                                     record_trace)


@pytest.fixture(scope="module")
def mobilenet():
    m = zoo.get("mobilenetv2")
    return m, m.init(jax.random.PRNGKey(0))


def _x(batch=2, hw=32):
    return jax.random.normal(jax.random.PRNGKey(1), (batch, hw, hw, 3))


# --------------------------------------------------------------------------- #
# Channel level: wire format, records, slot growth (in-process, cheap)
# --------------------------------------------------------------------------- #
@pytest.mark.parametrize("name", ["socket", "shmem"])
def test_channel_roundtrip_and_records(name):
    chan = get_transport(name).open(HopSpec(index=0, link=LOOPBACK))
    try:
        x = np.arange(24, dtype=np.float32).reshape(2, 3, 4)
        chan.send(x, kind=BATCH)
        kind, y = chan.recv(timeout=5.0)
        assert kind == BATCH and np.array_equal(x, y)   # raw bytes: exact
        chan.send(kind=PROBE)
        kind, _ = chan.recv(timeout=5.0)
        assert kind == PROBE
        recs = chan.drain_records()
        assert len(recs) == 2
        assert recs[0].nbytes == x.nbytes and recs[0].elapsed_s > 0
        assert recs[1].nbytes == 0                      # header-only probe
        assert chan.drain_records() == []               # drained
        assert chan.total_bytes == x.nbytes             # lifetime counter
    finally:
        chan.close()


def test_channel_pickle_framing_roundtrip():
    hop = HopSpec(index=0, link=LOOPBACK, framing="pickle")
    chan = get_transport("socket").open(hop)
    try:
        x = np.ones((4, 5), dtype=np.float32)
        chan.send(x, kind=BATCH)
        _, y = chan.recv(timeout=5.0)
        assert np.array_equal(x, y)
        (rec,) = chan.drain_records()
        assert rec.nbytes > x.nbytes                    # pickle framing pays
    finally:
        chan.close()


def test_shmem_slot_growth():
    chan = get_transport("shmem").open(HopSpec(index=0, link=LOOPBACK))
    try:
        small = np.zeros(16, dtype=np.float32)
        big = np.zeros(1 << 18, dtype=np.float32)       # > initial 64 KiB slot
        for payload in (small, big, small, big):
            chan.send(payload, kind=BATCH)
            _, y = chan.recv(timeout=5.0)
            assert y.nbytes == payload.nbytes
    finally:
        chan.close()


def test_unknown_transport_rejected():
    with pytest.raises(KeyError, match="unknown transport"):
        get_transport("carrier-pigeon")


def test_as_jax_aliases_what_dlpack_exports_and_nothing_is_swallowed():
    x = np.arange(6, dtype=np.float32)
    y = as_jax(x)
    assert isinstance(y, jax.Array) and np.array_equal(np.asarray(y), x)
    ro = np.frombuffer(x.tobytes(), np.float32)   # read-only: not exportable
    assert as_jax(ro) is ro
    with pytest.raises(jax.errors.JaxRuntimeError):   # non-compact view
        as_jax(np.zeros((4, 4), np.float32)[:, ::2])


# --------------------------------------------------------------------------- #
# Scenario-level transport declarations
# --------------------------------------------------------------------------- #
def test_scenario_transports_declared_and_validated():
    scen = scenarios.get("pi_pi_gpu").with_transport("socket")
    assert scen.transports == ("socket", "socket")
    # preserved through link surgery and snapshots
    assert scen.with_link(0, DURESS).transports == ("socket", "socket")
    assert scen.at(0.0).transports == ("socket", "socket")
    with pytest.raises(ValueError, match="one transport per link"):
        Scenario("bad", scen.devices, scen.links, transports=("socket",))
    assert scenarios.get("local3_socket").transports == ("socket", "socket")
    assert scenarios.get("pi_pi_gpu_socket").n_stages == 3


def test_mixed_emulated_and_process_transports_rejected(mobilenet):
    m, params = mobilenet
    with pytest.raises(ValueError, match="mix"):
        EdgePipeline(m, params, (5, 12), scenarios.get("pi_pi_gpu"),
                     transport=("emulated", "socket"))


# --------------------------------------------------------------------------- #
# Transport parity: same model + cuts + scenario across all three backends
# --------------------------------------------------------------------------- #
def test_transport_parity(mobilenet):
    m, params = mobilenet
    scen = scenarios.get("pi_pi_gpu")
    x = _x()
    ref = np.asarray(m.apply(params, x))
    outs, results = {}, {}
    for name in ("emulated", "socket", "shmem"):
        with EdgePipeline(m, params, (5, 12), scen, transport=name) as pipe:
            assert pipe.transport == name
            pipe.warmup(x)
            y, lat, hops = pipe.run_one(x)
            assert lat > 0 and len(hops) == 2 and all(h > 0 for h in hops)
            outs[name] = np.asarray(y)
            res = pipe.measure(lambda: x, n_batches=4)
            results[name] = res
            assert res.transport == name
            assert res.partition == (5, 12)
            assert res.throughput > 0 and res.latency_s > 0
            assert len(res.stage_exe_s) == 3 and len(res.hop_net_s) == 2
            # per-worker CPU accounting (not one host-wide broadcast);
            # tiny stages can read 0 where the CPU clock is coarse, but
            # the readings must be per-stage, not one broadcast value
            assert len(res.cpu_pct) == 3 and all(c >= 0 for c in res.cpu_pct)
            assert len(set(res.cpu_pct)) > 1 and max(res.cpu_pct) > 0
            # raw framing moves exactly the activation bytes on every hop
            assert pipe.nets[0].total_bytes % (x.shape[0] * 4) == 0
    # identical outputs across modeled and measured hops
    assert np.allclose(outs["emulated"], ref, atol=1e-5)
    for name in ("socket", "shmem"):
        assert np.allclose(outs[name], outs["emulated"], rtol=0, atol=1e-6), \
            f"{name} diverged from emulated"
    # emulated is deterministic: a second thread-backed run is bit-identical
    pipe = EdgePipeline(m, params, (5, 12), scen)
    y2, _, _ = pipe.run_one(x)
    assert np.array_equal(outs["emulated"], np.asarray(y2))


@pytest.mark.parametrize("name", ["emulated", "socket"])
def test_worker_platforms_reported(mobilenet, name):
    """Thread stages compute on this process's device; a spawned worker
    pins its JAX to the host CPU before touching a backend and reports
    the platform it came up on."""
    m, params = mobilenet
    with EdgePipeline(m, params, (5, 12), scenarios.get("pi_pi_gpu"),
                      transport=name) as pipe:
        want = jax.devices()[0].platform if name == "emulated" else "cpu"
        assert pipe.worker_platforms == [want] * 3


def test_socket_pipeline_migrates_and_records(mobilenet):
    """A 3-stage pipeline across real OS processes: live RECONFIG keeps
    outputs correct, probes give nbytes=0 RTT samples, and every hop's
    TransferRecords are measured wall-clock."""
    m, params = mobilenet
    x = _x()
    ref = np.asarray(m.apply(params, x))
    with EdgePipeline(m, params, (5, 12), scenarios.get("pi_pi_gpu"),
                      transport="socket") as pipe:
        pipe.warmup(x)
        pipe.run_one(x)
        for net in pipe.nets:
            (rec,) = [r for r in net.drain_observations() if r.nbytes > 0]
            assert rec.elapsed_s > 0 and rec.nbytes > 0
        pipe.probe()
        for net in pipe.nets:
            probes = [r for r in net.drain_observations() if r.nbytes == 0]
            assert len(probes) == 1 and probes[0].elapsed_s > 0
        pipe.migrate((3, 17), cost_s=0.0)
        assert pipe.cuts == (3, 17)
        y, _, _ = pipe.run_one(x)
        assert np.allclose(ref, y, atol=1e-5)
        assert len(pipe.migrations) == 1


def test_linktrace_rejected_on_process_transports(mobilenet):
    """A measured channel cannot replay a schedule: a LinkTrace hop
    under socket/shmem must be rejected loudly, not silently ignored."""
    m, params = mobilenet
    with pytest.raises(ValueError, match="LinkTrace"):
        EdgePipeline(m, params, (5, 12),
                     scenarios.get("pi_pi_gpu_wan_ramp"), transport="socket")


@pytest.mark.parametrize("transport", ["socket", "shmem"])
def test_worker_process_death_raises_not_hangs(mobilenet, transport):
    """A worker process dying mid-stream must surface as TransportError
    within the liveness window, not hang the orchestrator — on the
    socket path (EOF + liveness) and the shmem path (no EOF: liveness
    polling and the bounded slot wait are all there is)."""
    m, params = mobilenet
    x = _x()
    pipe = EdgePipeline(m, params, (5, 12), scenarios.get("pi_pi_gpu"),
                        transport=transport)
    try:
        pipe.warmup(x)
        pipe._engine._procs[1].terminate()
        pipe._engine._procs[1].join(5.0)
        t0 = time.perf_counter()
        with pytest.raises(TransportError, match="died|closed|gone"):
            pipe.stream(x, n_batches=6)
        assert time.perf_counter() - t0 < 30.0
    finally:
        pipe.close()


def test_adaptive_loop_closes_over_measured_socket_costs(mobilenet):
    """Acceptance: nominal planning says every hop is under duress; the
    *measured* loopback TransferRecords say otherwise, and the closed
    loop migrates the cut vector on real worker processes."""
    m, params = mobilenet
    x = _x()
    scen = (scenarios.get("pi_pi_gpu").with_link(0, DURESS)
            .with_link(1, DURESS).with_transport("socket"))
    with AdaptiveRuntime(m, params, scen, graph=m.block_graph(input_hw=32),
                         batch=x.shape[0], policy="throughput",
                         check_every=2, migration_cost_s=0.01,
                         alpha=0.8) as rt:
        recs = rt.run(lambda: x, n_batches=10)
        assert len(recs) == 10
        assert any(r.migrated for r in recs)
        assert len(rt.pipe.migrations) >= 1
        # estimates moved off the duress prior toward the measured wire
        assert rt.estimators[0].rtt_s < DURESS.rtt_s / 2
        assert rt.estimators[0].bw_bytes_per_s > DURESS.bw_bytes_per_s
        # and outputs stay correct on the migrated process pipeline
        y, _, _ = rt.pipe.run_one(x)
        assert np.allclose(np.asarray(m.apply(params, x)), y, atol=1e-5)


# --------------------------------------------------------------------------- #
# LinkEstimator: joint (rtt, overhead, bw) least-squares fit
# --------------------------------------------------------------------------- #
def test_estimator_joint_fit_recovers_overhead_and_bw():
    truth = Link("truth", rtt_s=20e-3, bw_bytes_per_s=1e8,
                 per_msg_overhead_s=2e-3)
    est = LinkEstimator(rtt_s=1e-3, bw_bytes_per_s=1e9, alpha=0.5)
    naive = LinkEstimator(rtt_s=1e-3, bw_bytes_per_s=1e9, alpha=0.5,
                          min_fit_samples=10**9)   # EWMA fallback forever
    sizes = [1e4, 1e5, 1e6]
    for _ in range(15):
        est.observe(0, truth.rtt_s, is_rtt_probe=True)
        naive.observe(0, truth.rtt_s, is_rtt_probe=True)
        for n in sizes:
            est.observe(n, truth.transfer_time(n))
            naive.observe(n, truth.transfer_time(n))
    assert est.rtt_s == pytest.approx(truth.rtt_s, rel=0.05)
    assert est.bw_bytes_per_s == pytest.approx(truth.bw_bytes_per_s, rel=0.15)
    assert est.per_msg_overhead_s == pytest.approx(truth.per_msg_overhead_s,
                                                   rel=0.35)
    # the EWMA mis-attributes the fixed per-message cost of the small
    # transfers to bandwidth; the joint fit must be strictly closer
    assert (abs(est.bw_bytes_per_s - truth.bw_bytes_per_s)
            < abs(naive.bw_bytes_per_s - truth.bw_bytes_per_s))
    link = est.as_link()
    assert link.per_msg_overhead_s == pytest.approx(est.per_msg_overhead_s)


def test_estimator_single_size_falls_back_to_ewma():
    est = LinkEstimator(rtt_s=DURESS.rtt_s, bw_bytes_per_s=1e9, alpha=0.5)
    for _ in range(30):
        est.observe(1e6, DURESS.transfer_time(1e6))
    assert est.bw_bytes_per_s < 3 * DURESS.bw_bytes_per_s


# --------------------------------------------------------------------------- #
# Trace recorder: measured records → replayable LinkTrace
# --------------------------------------------------------------------------- #
def _synth_records(link: Link, t0: float, t1: float, n: int = 12):
    recs, sizes = [], [1e4, 1e5, 1e6]
    for i in range(n):
        t = t0 + (t1 - t0) * i / max(n - 1, 1)
        if i % 4 == 0:
            recs.append(TransferRecord(0, link.rtt_s / 2.0, t))
        else:
            nb = sizes[i % len(sizes)]
            recs.append(TransferRecord(int(nb), link.transfer_time(nb), t))
    return recs


def test_record_trace_recovers_two_phase_link():
    fast = Link("fast", rtt_s=2e-3, bw_bytes_per_s=1e8,
                per_msg_overhead_s=0.5e-3)
    slow = Link("slow", rtt_s=100e-3, bw_bytes_per_s=1e6,
                per_msg_overhead_s=0.5e-3)
    recs = _synth_records(fast, 0.0, 4.0) + _synth_records(slow, 5.0, 9.0)
    trace = record_trace(recs, name="measured", bucket_s=5.0)
    early, late = trace.at(1.0), trace.at(8.0)
    assert early.rtt_s == pytest.approx(fast.rtt_s, rel=0.1)
    assert early.bw_bytes_per_s == pytest.approx(fast.bw_bytes_per_s, rel=0.3)
    assert late.rtt_s == pytest.approx(slow.rtt_s, rel=0.1)
    assert late.bw_bytes_per_s == pytest.approx(slow.bw_bytes_per_s, rel=0.3)
    # replayable: a scenario can carry the recorded trace on a hop
    scen = scenarios.get("pi_to_gpu").with_link(0, trace)
    assert scen.time_varying and scen.at(8.0).links[0].rtt_s > early.rtt_s


def test_record_trace_from_real_channel():
    chan = get_transport("socket").open(HopSpec(index=0, link=LOOPBACK))
    try:
        for nb in (10_000, 200_000, 10_000, 200_000, 1_000_000):
            chan.send(np.zeros(nb // 4, dtype=np.float32), kind=BATCH)
            chan.recv(timeout=5.0)
        chan.send(kind=PROBE)
        chan.recv(timeout=5.0)
        trace = record_trace(chan, name="loopback_measured", bucket_s=60.0)
    finally:
        chan.close()
    snap = trace.at(0.0)
    assert snap.bw_bytes_per_s > 0 and snap.rtt_s >= 0
    assert trace.transfer_time(1e6) > 0


def test_record_trace_rejects_empty():
    with pytest.raises(ValueError, match="no records"):
        record_trace([])
