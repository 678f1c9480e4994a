#!/usr/bin/env python3
"""Run one benchmark cell once, on the chip, and print its result line.

    python3 chipbench/run.py --workload <cell> --seed <n> --seconds <s> \
        --trace <0|1>

Everything a cell is made of is found by name: its entry in
``BENCHMARK.json``; its configuration (the file that entry names);
``chipbench/cells/<cell>.json`` (the deployment: which system module
builds it, and its settings); ``chipbench/traffic/<traffic>.json`` (the
mix, and which generator module drives it); one reader per per-layer
metric, ``chipbench/metrics/<metric>.py``; and the peak table
``chipbench/peaks.json``.  No code here knows a cell by name.

One run: set-up (weights from the seed on the device, the deployment
built, every shape the traffic uses compiled or read from JAX's
persistent compilation cache, a warm-up), then the measured window of
``--seconds``, then the check of what the window produced against the
plain reference, after the system's state is freed.  ``--trace 1``
profiles the last seconds of the window and prints the per-layer
metrics instead of the end-to-end ones.  JAX's persistent
compilation cache is ``chipbench/.out/jax_cache`` in the checkout.

The process re-executes itself once with ``PYTHONHASHSEED`` fixed, so
that nothing in the program that hashes strings differs between runs
of one seed.  It exits non-zero and prints no result line when JAX finds
no TPU or fewer chips than the cell needs, when the device is not in the
peak table, or when the program is not beside it.
"""
from __future__ import annotations

import os
import sys
import time

HASH_SEED = "0"
_CHILD = "CHIPBENCH_REEXEC_PID"
_T0 = "CHIPBENCH_T0"


def _reexec() -> float:
    """→ the wall time at which this run's first process started."""
    if (os.environ.get(_CHILD) == str(os.getpid())
            and os.environ.get("PYTHONHASHSEED") == HASH_SEED):
        return float(os.environ[_T0])
    t0 = time.time()
    env = dict(os.environ, PYTHONHASHSEED=HASH_SEED,
               **{_CHILD: str(os.getpid()), _T0: repr(t0)})
    sys.stdout.flush()
    sys.stderr.flush()
    os.execve(sys.executable, [sys.executable, *sys.argv], env)
    raise AssertionError("unreachable")


if __name__ == "__main__":
    T_START = _reexec()

import argparse            # noqa: E402
import gc                  # noqa: E402
import importlib           # noqa: E402
import importlib.util      # noqa: E402
import json                # noqa: E402
import math                # noqa: E402
import shutil              # noqa: E402
from pathlib import Path   # noqa: E402

BENCH_DIR = Path(__file__).resolve().parent
ROOT = BENCH_DIR.parent
OUT_DIR = BENCH_DIR / ".out"
TRACE_SECONDS = 3.0    # the traced stretch of a --trace 1 run
TRACE_LEAD_S = 1.0     # room for the profiler's start before the close
CACHE_DIR = OUT_DIR / "jax_cache"


class Fail(RuntimeError):
    """The run cannot produce a result: exit non-zero, no result line."""


def log(msg: str) -> None:
    print(f"[chipbench] {msg}", file=sys.stderr, flush=True)


# --------------------------------------------------------------------------- #
# the cell, by name
# --------------------------------------------------------------------------- #
def load_json(path: Path) -> dict:
    with open(path) as f:
        return json.load(f)


def load_cell(name: str, bench_path: Path | None = None) -> dict:
    """Everything one cell is made of, read from its files."""
    bench = load_json(bench_path or ROOT / "BENCHMARK.json")
    wl = {w["name"]: w for w in bench["workloads"]}.get(name)
    if wl is None:
        raise Fail(f"no workload {name!r} in BENCHMARK.json")
    configs = {c["name"]: c for c in bench["configs"]}
    cfg_entry = configs[wl["config"]]
    per_layer = [m for m in bench["per_layer"]
                 if name in m.get("workloads", [name])]
    end_to_end = [m for m in bench["end_to_end"]
                  if name in m.get("workloads", [name])]
    return {
        "workload": wl,
        "config": load_json(ROOT / cfg_entry["file"]),
        "cell": load_json(BENCH_DIR / "cells" / f"{name}.json"),
        "traffic": load_json(BENCH_DIR / "traffic" / f"{wl['traffic']}.json"),
        "end_to_end": end_to_end,
        "per_layer": per_layer,
    }


def load_reader(metric: str):
    """The module ``chipbench/metrics/<metric>.py``."""
    path = BENCH_DIR / "metrics" / f"{metric}.py"
    spec = importlib.util.spec_from_file_location(
        "chipbench.metrics." + metric.replace(".", "_"), path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def peaks_for(kind: str) -> dict:
    table = load_json(BENCH_DIR / "peaks.json")["kinds"]
    if kind not in table:
        raise Fail(f"device kind {kind!r} is not in chipbench/peaks.json")
    return table[kind]


# --------------------------------------------------------------------------- #
# device, tracing, compiles
# --------------------------------------------------------------------------- #
def require_chips(n: int):
    import jax
    devices = jax.devices()
    if devices[0].platform != "tpu" or len(devices) < n:
        raise Fail(f"the cell needs {n} TPU chip(s); JAX found "
                   f"{len(devices)} {devices[0].platform} device(s)")
    return devices


class Tracer:
    """The profiler for a ``--trace 1`` run, over a stretch at the end of
    the window: it starts ``TRACE_SECONDS + TRACE_LEAD_S`` before the
    window closes, and the stretch, marked by the host span
    ``bench.traced``, runs from the moment the start returns for
    ``TRACE_SECONDS`` or to the window's close, where the profiler
    stops.  Stopping it stalls the host for seconds: that falls after
    the stretch, on requests that no per-layer reading counts.  The
    requests completed before the start (``untraced_until``, a
    perf_counter()) saw no profiler; ``t_start``/``t_stop`` bound the
    stretch.  The Python tracer is off: it would slow every Python call
    of the host path that the window measures."""

    def __init__(self, on: bool, seconds: float, out_dir: Path):
        self.on, self.seconds, self.out_dir = on, seconds, out_dir
        self.begin_at = max(seconds - TRACE_SECONDS - TRACE_LEAD_S, 0.0)
        self.state = 0
        self.untraced_until = math.nan if on else math.inf
        self.t_start = self.t_stop = math.nan
        self._span = self._stop_at = None

    def tick(self, now: float) -> None:
        """``now``: seconds into the window."""
        if not self.on:
            return
        import jax
        if self.state == 0 and now >= self.begin_at:
            self.untraced_until = time.perf_counter()
            options = jax.profiler.ProfileOptions()
            options.python_tracer_level = 0
            jax.profiler.start_trace(str(self.out_dir),
                                     profiler_options=options)
            self._span = jax.profiler.TraceAnnotation("bench.traced")
            self._span.__enter__()
            self.t_start = time.perf_counter()
            self._stop_at = now + (self.t_start - self.untraced_until) \
                + TRACE_SECONDS
            self.state = 1
        if self.state == 1 and now >= min(self._stop_at, self.seconds):
            self.t_stop = time.perf_counter()
            self._span.__exit__(None, None, None)
            jax.profiler.stop_trace()
            self.state = 2


def enable_cache() -> str:
    """JAX's persistent compilation cache at a fixed place inside the
    checkout, given to the program (which then sets none of its own);
    every program is cached, however fast it compiled."""
    import jax
    os.environ["JAX_COMPILATION_CACHE_DIR"] = str(CACHE_DIR)
    jax.config.update("jax_compilation_cache_dir", str(CACHE_DIR))
    from repro.launch.compile_cache import enable_compile_cache
    cache = enable_compile_cache()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    return cache


class CompileCounter:
    """Counts the programs lowered while ``active``: every compilation,
    whether XLA compiles it or JAX's persistent cache supplies it."""

    def __init__(self):
        import jax
        self.active = False
        self.count = 0
        jax.monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, event: str, duration: float, **kw) -> None:
        if self.active and event == \
                "/jax/core/compile/jaxpr_to_mlir_module_duration":
            self.count += 1


# --------------------------------------------------------------------------- #
# one run
# --------------------------------------------------------------------------- #
def run(name: str, seed: int, seconds: float, trace: bool,
        t_start: float, require_chip: bool = True,
        bench_path: Path | None = None, cell_overrides: dict | None = None):
    """One run of cell ``name``; → the result object to print, and the
    numbers compared as ``[(name, reading, limit)]``."""
    spec = load_cell(name, bench_path)
    if cell_overrides:
        for part, over in cell_overrides.items():
            spec[part] = {**spec[part], **over}
    wl = spec["workload"]
    import jax
    if require_chip:
        devices = require_chips(int(wl["chips"]))
        peaks = peaks_for(devices[0].device_kind)
    else:
        devices = jax.devices()
        peaks = load_json(BENCH_DIR / "peaks.json")["kinds"]["TPU v5 lite"]
    if not (ROOT / "src" / "repro").is_dir():
        raise Fail(f"the program is not beside the benchmark ({ROOT / 'src'})")
    if str(ROOT / "src") not in sys.path:
        sys.path.insert(0, str(ROOT / "src"))
    cache = enable_cache()
    log(f"{name}: seed {seed}, {seconds} s, trace {int(trace)}; "
        f"{len(devices)} x {devices[0].device_kind}; compile cache {cache}")

    system = importlib.import_module(
        f"chipbench.systems.{spec['cell']['system']}")
    gen = importlib.import_module(
        f"chipbench.traffic.{spec['traffic']['generator']}")
    compiles = CompileCounter()

    t_jax = time.time()
    sut = system.System(spec["config"], spec["cell"], spec["traffic"], seed)
    t_built = time.time()
    gen.prepare(sut, spec, seed, seconds)
    trace_dir = OUT_DIR / "trace" / name
    shutil.rmtree(trace_dir, ignore_errors=True)
    tracer = Tracer(trace, seconds, trace_dir)
    # the window scans no object set-up made
    gc.collect()
    gc.freeze()
    setup_s = time.time() - t_start
    compiles.active = True
    win = gen.drive(sut, spec, seed, seconds, tracer,
                       jax.profiler.TraceAnnotation)
    compiles.active = False
    e2e = gen.end_to_end(win)
    memory_peak = max((d.memory_stats() or {}).get("peak_bytes_in_use", 0)
                      for d in devices[:int(wl["chips"])])
    sut.assert_nothing_emulated()
    notes = dict(e2e.pop("notes"), compiles_in_window=compiles.count,
                 setup_to_jax_s=t_jax - t_start,
                 setup_build_s=t_built - t_jax,
                 setup_prepare_s=setup_s - (t_built - t_start))

    summary = None
    if trace:
        from chipbench import trace as tr
        summary = tr.summarize(tr.find_xplane(str(trace_dir)))
        shutil.rmtree(trace_dir, ignore_errors=True)   # tens of MB
    ctx = gen.layer_context(sut, win, spec, tracer, summary, peaks)
    sut.close()
    checks = gen.check(sut, win, spec)

    device = {"platform": devices[0].platform, "kind": devices[0].device_kind,
              "count": len(devices), "memory_peak_bytes": int(memory_peak)}
    units = {m["name"]: m["unit"] for m in spec["end_to_end"]
             + spec["per_layer"]}
    metrics: dict = {}
    if trace:
        device["busy_s"] = summary.busy_s_mean
        device["window_s"] = summary.window_s
        for m in spec["per_layer"]:
            value = load_reader(m["name"]).read(ctx)
            if value is not None:
                metrics[m["name"]] = {"value": value, "unit": units[m["name"]]}
    else:
        values = dict(e2e, setup_s=setup_s)
        for m in spec["end_to_end"]:
            v = values[m["name"]]
            metrics[m["name"]] = {"value": v if math.isfinite(v) else None,
                                  "unit": units[m["name"]]}
    ok = all(v <= lim for _, v, lim in checks) and e2e["failed"] == 0
    result = {"correct": bool(ok), "attempted": e2e["attempted"],
              "failed": e2e["failed"], "metrics": metrics, "device": device}
    if summary is not None:
        result["breakdown"] = {"device_ops": summary.top_ops(10),
                               "idle_gaps": summary.top_idle(10)}
    for k, v in notes.items():
        log(f"{k}: {v}")
    result["checks"] = {n: {"value": v, "limit": lim} for n, v, lim in checks}
    return result, checks


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    sys.path.insert(0, str(ROOT))
    try:
        result, checks = run(args.workload, args.seed, args.seconds,
                             bool(args.trace), T_START)
    except Fail as e:
        print(f"chipbench: {e}", file=sys.stderr, flush=True)
        return 2
    for n, v, lim in checks:
        print(f"check {n}: {v!r} (limit {lim!r})", file=sys.stderr)
    sys.stderr.flush()
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
