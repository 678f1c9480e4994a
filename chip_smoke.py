#!/usr/bin/env python3
"""Chip smoke test: the split-inference main path, end to end, on a TPU.

One chip (no arguments):

  1. the wire codec kernels compile to Mosaic (``tpu_custom_call``), not
     to the Pallas interpreter;
  2. resnet50 at its published 224x224 input, weights from ``--seed``,
     cut by ``repro.core.solve`` (``best_throughput``) for the 3-stage
     ``pi_pi_gpu`` chain and served through Gateway -> Session ->
     EdgePipeline on the in-process engine, every stage on the chip:
     two tenants, mixed request sizes; every answered row is compared
     with the unsplit forward pass on the same chip;
  3. the same traffic through ``pi_pi_gpu_int8`` (the int8 codec on both
     hops), judged by top-1 agreement with the unsplit pass;
  4. a 3-stage ``socket`` pipeline of worker processes on a small model:
     the workers run on the host CPU, answer, and close.

Four chips (``--four-chips``): only the pod-pipeline train step of
qwen3-1.7b at published widths on a (pod=4, data=1, model=1) mesh,
against the unpipelined forward pass on one chip.

    python3 chip_smoke.py [--seed N] [--four-chips]

Exits non-zero, printing no result, when JAX finds no TPU or any phase
fails.  The last line of standard output is one JSON object naming the
device.
"""
from __future__ import annotations

import argparse
import json
import math
import sys
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parent

# split phase
SPLIT_MODEL, SPLIT_HW = "resnet50", 224        # the published input
SCENARIO = "pi_pi_gpu"
MAX_BATCH = 8
TENANTS = ("tenant-a", "tenant-b")
REQUESTS_PER_TENANT = 6
MAX_ROWS = 4
# Stage outputs against the unsplit program, both on the chip: float32
# convolutions and matmuls run as bfloat16 products with float32
# accumulation (unit roundoff 2**-9 ~ 2e-3), and the split changes where
# the compiler fuses and rounds.  Ten roundoffs of the output's scale.
SPLIT_RTOL = 2e-2
# int8 on both hops quantizes each activation to 255 levels of its
# largest magnitude: judged by top-1 agreement with the unsplit pass.
INT8_MIN_TOP1 = 0.9
# socket phase: small model, workers on the host CPU, compared with the
# unsplit program on the host CPU (same backend, different programs)
SOCKET_MODEL = "mobilenetv2"
SOCKET_HW = 32
SOCKET_RTOL = 1e-4
SOCKET_CLOSE_S = 60.0
# four-chip phase
LM_ARCH = "qwen3-1.7b"
LM_PODS, LM_BATCH, LM_SEQ, LM_MICRO, LM_STEPS = 4, 8, 512, 4, 3
# Depth cut 28 -> 23 layers, forced by memory: the ParetoPipe cuts leave
# the last pod most of the layers, every pod holds that many layer slots
# (weights and both AdamW moments), and at 24 layers the compiled step
# asks for 15.96G of a v5e's 15.75G of HBM.
LM_LAYERS = 23
# first-step CE of the pipelined step against the unpipelined forward:
# bfloat16 activations, rounded at different fusion boundaries, and a
# seq-chunked CE against a dense one
LM_CE_ATOL = 2e-2


def log(msg: str) -> None:
    print(msg, flush=True)


class SmokeFailure(RuntimeError):
    """A phase produced a wrong or missing result."""


def check(ok: bool, what: str) -> None:
    if not ok:
        raise SmokeFailure(what)


def require_tpu(n_chips: int):
    """The devices, or exit non-zero before any work when JAX has no
    TPU (or fewer chips than the phase needs)."""
    import jax
    devices = jax.devices()
    if devices[0].platform != "tpu" or len(devices) < n_chips:
        print(f"chip_smoke: needs {n_chips} TPU chip(s); JAX found "
              f"{len(devices)} {devices[0].platform} device(s)",
              file=sys.stderr)
        raise SystemExit(2)
    return devices


# --------------------------------------------------------------------------- #
# phase 1: the codec kernels compile for the chip
# --------------------------------------------------------------------------- #
def codec_kernels_compiled(shape) -> None:
    import jax
    import jax.numpy as jnp
    from repro.kernels import ops

    check(not ops._default_interpret(),
          "Pallas kernels would run in interpret mode on this backend")
    x = jax.ShapeDtypeStruct(shape, jnp.float32)
    q = jax.ShapeDtypeStruct((math.prod(shape),), jnp.int8)
    s = jax.ShapeDtypeStruct((), jnp.float32)
    for name, fn, args in (("int8_pack", ops.int8_pack, (x,)),
                           ("int8_unpack", ops.int8_unpack, (q, s))):
        text = jax.jit(fn).lower(*args).as_text()
        check("tpu_custom_call" in text,
              f"{name} did not lower to a tpu_custom_call")
        log(f"[codec] {name} {shape}: tpu_custom_call, interpret=False")


# --------------------------------------------------------------------------- #
# phase 2-3: resnet50 split across three stages behind the gateway
# --------------------------------------------------------------------------- #
def make_traffic(seed: int, hw: int):
    """{tenant: [request rows]}: 1..MAX_ROWS rows each, from ``seed``."""
    import numpy as np
    rng = np.random.default_rng(seed)
    return {t: [rng.standard_normal((int(rng.integers(1, MAX_ROWS + 1)),
                                     hw, hw, 3), dtype=np.float32)
                for _ in range(REQUESTS_PER_TENANT)]
            for t in TENANTS}


def serve(pipe, traffic, *, solo: bool = False):
    """Serve ``traffic`` through a Gateway over ``pipe``; → ({tenant:
    [outputs]}, rows that shared a micro-batch with another tenant).
    ``solo`` serves each request alone in its micro-batch."""
    from repro.core.scenarios import TenantSpec
    from repro.runtime import Gateway

    specs = [TenantSpec(t, slo_s=60.0) for t in TENANTS]
    out: dict[str, list] = {t: [] for t in TENANTS}
    with Gateway(pipe, specs, max_batch=MAX_BATCH) as gw:
        for i in range(REQUESTS_PER_TENANT):
            for t in TENANTS:
                gw.submit(t, traffic[t][i])
                if solo:
                    out[t].extend(v for _, v in gw.drain()[t])
        if not solo:
            for t, vals in gw.drain().items():
                out[t] = [v for _, v in vals]
        recs = gw.drain_qos()
    batch_tenants: dict[int, set] = {}
    for r in recs:
        batch_tenants.setdefault(r.seq, set()).add(r.tenant)
    mixed = sum(r.rows for r in recs if len(batch_tenants[r.seq]) > 1)
    for t in TENANTS:
        check(len(out[t]) == REQUESTS_PER_TENANT,
              f"{t}: {len(out[t])} of {REQUESTS_PER_TENANT} requests answered")
    return out, mixed


def split_phase(seed: int) -> None:
    import jax
    import numpy as np
    from repro.core import scenarios, solve
    from repro.core.partitioner import best_throughput
    from repro.models.cnn import zoo
    from repro.runtime import EdgePipeline

    chip = jax.devices()[0]
    m, hw = zoo.get(SPLIT_MODEL), SPLIT_HW
    params = m.init(jax.random.PRNGKey(seed))
    graph = m.block_graph(input_hw=hw)
    traffic = make_traffic(seed, hw)
    n_rows = sum(x.shape[0] for xs in traffic.values() for x in xs)
    log(f"[split] {SPLIT_MODEL} {hw}x{hw}, {len(m.blocks)} blocks; traffic "
        f"{len(TENANTS)} tenants x {REQUESTS_PER_TENANT} requests, "
        f"{n_rows} rows, max_batch={MAX_BATCH}")

    # the unsplit reference: one program on the same chip, at the padded
    # micro-batch shape the pipeline computes at
    ref_fn = jax.jit(m.apply)
    t0 = time.perf_counter()
    jax.block_until_ready(ref_fn(params, np.zeros((MAX_BATCH, hw, hw, 3),
                                                  np.float32)))
    log(f"[split] unsplit reference compiled in "
        f"{time.perf_counter() - t0:.1f}s")

    def reference(x):
        pad = np.zeros((MAX_BATCH - x.shape[0],) + x.shape[1:], x.dtype)
        return np.asarray(ref_fn(params, np.concatenate([x, pad])))[:len(x)]
    refs = {t: [reference(x) for x in traffic[t]] for t in TENANTS}

    for scen_name in (SCENARIO, f"{SCENARIO}_int8"):
        scen = scenarios.get(scen_name)
        cuts = best_throughput(solve(graph, scen, batch=MAX_BATCH)).partition
        with EdgePipeline(m, params, cuts, scen) as pipe:
            log(f"[split:{scen_name}] cuts={tuple(cuts)} bounds="
                f"{pipe.bounds()} codecs={pipe.codecs}")
            x = np.zeros((MAX_BATCH, hw, hw, 3), np.float32)
            t0 = time.perf_counter()
            for w in pipe.workers:            # compiles each stage
                x = w.warmup(x)
                check(w.device == chip and x.devices() == {chip},
                      f"{w.name} ran on {x.devices()}, not {chip}")
                log(f"[split:{scen_name}] {w.name} blocks [{w.lo},{w.hi}) "
                    f"-> jax.Array on {sorted(map(str, x.devices()))}")
            log(f"[split:{scen_name}] stages compiled in "
                f"{time.perf_counter() - t0:.1f}s; worker platforms "
                f"{pipe.worker_platforms}")
            if any(c != "none" for c in pipe.codecs):
                act = pipe.workers[0].warmup(
                    np.zeros((MAX_BATCH, hw, hw, 3), np.float32))
                codec_kernels_compiled(tuple(act.shape))

            t0 = time.perf_counter()
            out, mixed = serve(pipe, traffic)
            log(f"[split:{scen_name}] served {n_rows} rows in "
                f"{time.perf_counter() - t0:.2f}s; {mixed} rows shared a "
                f"micro-batch with the other tenant")
            errs, agree, rows = [], 0, 0
            for t in TENANTS:
                for y, r in zip(out[t], refs[t]):
                    check(y.shape == r.shape and np.isfinite(y).all(),
                          f"{t}: got {y.shape}, want finite {r.shape}")
                    errs.append(float(np.max(np.abs(y - r))
                                      / max(float(np.max(np.abs(r))), 1e-30)))
                    agree += int(np.sum(y.argmax(-1) == r.argmax(-1)))
                    rows += len(y)
            top1 = agree / rows
            log(f"[split:{scen_name}] vs unsplit: max error "
                f"{max(errs):.3e} of the output scale, top-1 agreement "
                f"{top1:.4f} ({agree}/{rows})")
            if scen_name == SCENARIO:
                check(max(errs) <= SPLIT_RTOL,
                      f"split output off by {max(errs):.3e} > {SPLIT_RTOL}")
                solo, _ = serve(pipe, traffic, solo=True)
                same = all(np.array_equal(a, b) for t in TENANTS
                           for a, b in zip(out[t], solo[t]))
                log(f"[split:{scen_name}] solo and mixed-tenant rows "
                    f"bit-identical: {same}")
            else:
                check(top1 >= INT8_MIN_TOP1,
                      f"int8 top-1 agreement {top1:.4f} < {INT8_MIN_TOP1}")


# --------------------------------------------------------------------------- #
# phase 4: worker processes stay off the chip
# --------------------------------------------------------------------------- #
def socket_phase(seed: int) -> None:
    import jax
    import numpy as np
    from repro.core import scenarios, solve
    from repro.core.partitioner import best_throughput
    from repro.models.cnn import zoo
    from repro.runtime import EdgePipeline

    m = zoo.get(SOCKET_MODEL)
    params = m.init(jax.random.PRNGKey(seed))
    scen = scenarios.get("local3_socket")
    cuts = best_throughput(solve(m.block_graph(input_hw=SOCKET_HW),
                                 scen)).partition
    x = np.random.default_rng(seed).standard_normal(
        (2, SOCKET_HW, SOCKET_HW, 3), dtype=np.float32)
    cpu = jax.devices("cpu")[0]
    ref = np.asarray(jax.jit(m.apply)(jax.device_put(params, cpu),
                                      jax.device_put(x, cpu)))
    t0 = time.perf_counter()
    pipe = EdgePipeline(m, params, cuts, scen, timeout_s=120.0)
    try:
        log(f"[socket] {SOCKET_MODEL} {SOCKET_HW}x{SOCKET_HW} cuts="
            f"{tuple(cuts)}: workers up in {time.perf_counter() - t0:.1f}s "
            f"on {pipe.worker_platforms}")
        check(pipe.worker_platforms == ["cpu"] * 3,
              f"socket workers on {pipe.worker_platforms}, want cpu")
        with pipe.session() as s:
            s.submit(x)
            (y,) = s.drain()
    finally:
        t0 = time.perf_counter()
        pipe.close()
        close_s = time.perf_counter() - t0
    err = float(np.max(np.abs(y - ref)) / max(float(np.max(np.abs(ref))),
                                              1e-30))
    log(f"[socket] answered {y.shape}, max error {err:.3e} of the output "
        f"scale vs the unsplit program on the host CPU; closed in "
        f"{close_s:.2f}s")
    check(close_s < SOCKET_CLOSE_S, f"close took {close_s:.1f}s")
    check(err <= SOCKET_RTOL, f"socket output off by {err:.3e}")


# --------------------------------------------------------------------------- #
# --four-chips: the pod-pipeline train step across four chips
# --------------------------------------------------------------------------- #
def four_chip_phase(seed: int) -> None:
    import jax
    from repro import configs
    from repro.data.pipeline import DataConfig, SyntheticLM
    from repro.launch.mesh import make_host_mesh
    from repro.models import lm
    from repro.models.blocks_adapter import choose_pipeline_cuts
    from repro.models.common import DTYPES, InitBuilder, cross_entropy
    from repro.optim import OptConfig, cosine_schedule
    from repro.runtime.pipeline import (PipelineConfig, init_pipeline_state,
                                        make_pipeline_train_step)
    from repro.sharding.api import use_mesh_context

    cfg = configs.get(LM_ARCH).replace(n_layers=LM_LAYERS)
    cuts, _, _ = choose_pipeline_cuts(cfg, LM_SEQ, LM_PODS, batch=LM_BATCH)
    pcfg = PipelineConfig(LM_PODS, LM_MICRO, cuts)
    log(f"[pods] {cfg.name}: {cfg.n_layers} layers, d_model {cfg.d_model}, "
        f"vocab {cfg.vocab}; cuts={cuts} over {LM_PODS} pods; batch {LM_BATCH} "
        f"x seq {LM_SEQ}, {LM_MICRO} microbatches")
    data = SyntheticLM(cfg, DataConfig(LM_BATCH, LM_SEQ, seed))
    params = lm.build_params(cfg, InitBuilder(jax.random.PRNGKey(seed),
                                              DTYPES[cfg.dtype]))

    def plain_ce(p, b):
        logits, _ = lm.forward_train(
            cfg, p, {k: v for k, v in b.items() if k != "targets"})
        return cross_entropy(logits, b["targets"])
    t0 = time.perf_counter()
    ref_ce = float(jax.jit(plain_ce)(params, data.batch_at(0)))
    log(f"[pods] unpipelined CE on one chip ({jax.devices()[0]}): "
        f"{ref_ce:.6f} ({time.perf_counter() - t0:.1f}s with compile)")

    mesh = make_host_mesh(LM_PODS, 1, 1)
    opt = OptConfig(lr=cosine_schedule(3e-4, 1, LM_STEPS))
    with use_mesh_context(mesh):
        state = init_pipeline_state(cfg, pcfg, params, mesh)
        del params
        step = jax.jit(make_pipeline_train_step(cfg, pcfg, opt, mesh),
                       donate_argnums=(0,))
        ces = []
        for i in range(LM_STEPS):
            t0 = time.perf_counter()
            state, metrics = step(state, data.batch_at(i))
            ces.append(float(metrics["ce"]))
            log(f"[pods] step {i}: ce {ces[-1]:.6f} "
                f"({time.perf_counter() - t0:.2f}s)")
    diff = abs(ces[0] - ref_ce)
    log(f"[pods] first-step CE {ces[0]:.6f} vs unpipelined {ref_ce:.6f}: "
        f"|diff| {diff:.3e} (limit {LM_CE_ATOL})")
    check(all(map(math.isfinite, ces)), f"non-finite CE {ces}")
    check(diff <= LM_CE_ATOL, f"pipelined CE off by {diff:.3e}")


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--four-chips", action="store_true",
                    help="run only the pod-pipeline train step on 4 chips")
    args = ap.parse_args(argv)

    devices = require_tpu(4 if args.four_chips else 1)
    if not (ROOT / "src" / "repro").is_dir():
        print(f"chip_smoke: no repro package under {ROOT / 'src'}",
              file=sys.stderr)
        return 2
    sys.path.insert(0, str(ROOT / "src"))
    from repro.launch.compile_cache import enable_compile_cache

    log(f"[device] {len(devices)} x {devices[0].device_kind} "
        f"({devices[0].platform}); compile cache {enable_compile_cache()}")
    if args.four_chips:
        four_chip_phase(args.seed)
    else:
        split_phase(args.seed)
        socket_phase(args.seed)
    print(json.dumps({"ok": True, "device": {
        "platform": devices[0].platform, "kind": devices[0].device_kind,
        "count": len(devices)}}), flush=True)
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
