import os
os.environ.setdefault("XLA_FLAGS", "--xla_force_host_platform_device_count=512")

"""Multi-pod dry-run: lower + compile every (arch × shape × mesh) cell.

For each cell this harness
  1. builds the production mesh (16×16 single-pod or 2×16×16 multi-pod),
  2. constructs ShapeDtypeStruct inputs (weak-type-correct, sharded, no
     allocation — params are never materialized),
  3. ``jit(step).lower(...).compile()`` — any sharding mismatch, OOM at
     compile, or unsupported collective fails the cell,
  4. records ``memory_analysis`` / ``cost_analysis`` / the collective
     inventory parsed from optimized HLO, and the roofline terms,
  5. writes a JSON manifest per cell (resumable; EXPERIMENTS.md is
     generated from these).

Usage:
  python -m repro.launch.dryrun --arch qwen3-1.7b --shape train_4k --mesh both
  python -m repro.launch.dryrun --all [--force] [--out runs/dryrun]
"""
import argparse
import json
import time
import traceback
from pathlib import Path


GRAD_ACCUM = 4       # §Perf iteration 4: 4× smaller activation working set
TRAIN_ATTN_CHUNK = 1024   # §Perf iteration 3: flash block size for train


def _build_step(cfg, shape, mesh, multi_pod: bool, microbatches: int):
    from ..optim import OptConfig
    from ..runtime import steps as S
    from ..runtime.pipeline import (PipelineConfig,
                                    make_pipeline_decode_step,
                                    make_pipeline_prefill_step,
                                    make_pipeline_train_step)
    if multi_pod:
        n_pods = mesh.devices.shape[0]
        mb = microbatches if shape.kind == "train" else 1
        pcfg = PipelineConfig.even(cfg.n_layers, n_pods, mb)
        if shape.kind == "train":
            return make_pipeline_train_step(cfg, pcfg, OptConfig(), mesh), pcfg
        if shape.kind == "prefill":
            return make_pipeline_prefill_step(cfg, pcfg, mesh), pcfg
        return make_pipeline_decode_step(cfg, pcfg, mesh), pcfg
    if shape.kind == "train":
        return S.make_train_step(cfg, OptConfig(),
                                 grad_accum=GRAD_ACCUM), None
    if shape.kind == "prefill":
        return S.make_prefill_step(cfg), None
    return S.make_decode_step(cfg), None


def run_cell(arch: str, shape_name: str, multi_pod: bool,
             microbatches: int = 8, donate: bool = True) -> dict:
    import jax
    from .. import configs
    from ..sharding.api import MeshContext, use_mesh_context
    from . import specs as SP
    from .hlo_analysis import parse_collectives
    from .mesh import make_production_mesh
    from .roofline import model_flops, roofline_from

    cfg = configs.get(arch)
    shape = SP.SHAPES[shape_name]
    if shape.kind == "train":
        cfg = cfg.replace(attn_chunk=TRAIN_ATTN_CHUNK)
    if multi_pod and cfg.family == "moe":
        # GSPMD's gather partitioner hard-aborts evaluating gather
        # strategies under manual meshes → pipeline mode uses the
        # einsum-only GShard dispatch with expert parallelism.
        cfg = cfg.replace(moe_impl="gshard")
    mesh_name = "2x16x16" if multi_pod else "16x16"
    rec: dict = {"arch": arch, "shape": shape_name, "mesh": mesh_name,
                 "family": cfg.family, "kind": shape.kind}

    ok, why = SP.cell_supported(cfg, shape_name)
    if not ok:
        rec.update(status="skipped", reason=why)
        return rec

    t0 = time.time()
    try:
        mesh = make_production_mesh(multi_pod=multi_pod)
        n_chips = mesh.devices.size
        pod_size = (mesh.devices.size // mesh.devices.shape[0]
                    if multi_pod else mesh.devices.size)
        with use_mesh_context(mesh) as ctx:
            step, pcfg = _build_step(cfg, shape, mesh, multi_pod, microbatches)
            cell = SP.input_specs(cfg, shape_name, ctx, pcfg)
            if shape.kind == "train":
                jf = jax.jit(step, donate_argnums=(0,) if donate else ())
                lowered = jf.lower(cell["state"], cell["batch"])
            elif shape.kind == "prefill":
                lowered = jax.jit(step).lower(cell["params"], cell["inputs"])
            else:
                jf = jax.jit(step, donate_argnums=(2,) if donate else ())
                lowered = jf.lower(cell["params"], cell["token"],
                                   cell["cache"])
            t_lower = time.time() - t0
            compiled = lowered.compile()
            t_compile = time.time() - t0 - t_lower

        ca = compiled.cost_analysis() or {}
        ma = compiled.memory_analysis()
        hlo = compiled.as_text()
        coll = parse_collectives(hlo, pod_size)

        # Analytic executed-cost model (XLA cost_analysis counts while-loop
        # bodies once → useless under scanned layers; see launch/analytic.py)
        from .analytic import cell_cost
        axes = dict(zip(mesh.axis_names, mesh.devices.shape))
        cost = cell_cost(cfg, shape, n_chips=n_chips,
                         dp=axes.get("data", 1), tp=axes.get("model", 1),
                         multi_pod=multi_pod, pcfg=pcfg)
        mflops = model_flops(cfg, shape)
        rl = roofline_from(cost.flops_total / n_chips,
                           cost.hbm_bytes_per_dev,
                           cost.wire_ici_per_dev, cost.wire_dcn_per_dev,
                           mflops, n_chips)
        flops_dev = cost.flops_total / n_chips
        bytes_dev = cost.hbm_bytes_per_dev
        rec.update(
            status="ok",
            lower_s=round(t_lower, 1), compile_s=round(t_compile, 1),
            n_chips=n_chips,
            flops_per_dev=flops_dev, bytes_per_dev=bytes_dev,
            memory={
                "args_mb": ma.argument_size_in_bytes / 1e6,
                "output_mb": ma.output_size_in_bytes / 1e6,
                "temp_mb": ma.temp_size_in_bytes / 1e6,
                "peak_mb": (ma.argument_size_in_bytes
                            + ma.temp_size_in_bytes) / 1e6,
            },
            collectives=coll.by_kind(),
            wire_ici_per_dev=cost.wire_ici_per_dev,
            wire_dcn_per_dev=cost.wire_dcn_per_dev,
            xla_raw={"flops_per_dev": float(ca.get("flops", 0.0)),
                     "bytes_per_dev": float(ca.get("bytes accessed", 0.0)),
                     "wire_ici_parsed": coll.wire_bytes_ici,
                     "wire_dcn_parsed": coll.wire_bytes_dcn,
                     "note": "while-loop bodies counted once by XLA"},
            roofline={
                "compute_s": rl.compute_s, "memory_s": rl.memory_s,
                "collective_s": rl.collective_s, "dominant": rl.dominant,
                "step_bound_s": rl.step_time_s,
                "model_flops_total": mflops,
                "useful_ratio": rl.useful_ratio,
                "mfu_bound": rl.mfu_bound,
            },
        )
    except Exception as e:  # a failure here is a bug in the system
        rec.update(status="failed", error=f"{type(e).__name__}: {e}",
                   trace=traceback.format_exc()[-2000:])
    rec["wall_s"] = round(time.time() - t0, 1)
    return rec


def main() -> int:
    from .. import configs
    from . import specs as SP

    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--arch", choices=list(configs.ARCH_NAMES))
    ap.add_argument("--shape", choices=list(SP.SHAPES))
    ap.add_argument("--mesh", choices=("single", "multi", "both"),
                    default="both")
    ap.add_argument("--all", action="store_true")
    ap.add_argument("--force", action="store_true")
    ap.add_argument("--microbatches", type=int, default=8)
    ap.add_argument("--out", default="runs/dryrun")
    args = ap.parse_args()

    out = Path(args.out)
    out.mkdir(parents=True, exist_ok=True)
    archs = list(configs.ARCH_NAMES) if (args.all or not args.arch) \
        else [args.arch]
    shapes = list(SP.SHAPES) if (args.all or not args.shape) \
        else [args.shape]
    meshes = {"single": [False], "multi": [True],
              "both": [False, True]}[args.mesh]

    cells = [(a, s, m) for a in archs for s in shapes for m in meshes]
    single_cell = len(cells) == 1
    failures = 0
    for arch, shape, multi in cells:
        tag = f"{arch}__{shape}__{'multi' if multi else 'single'}"
        path = out / f"{tag}.json"
        if path.exists() and not args.force:
            rec = json.loads(path.read_text())
            print(f"[cached] {tag}: {rec['status']}")
            failures += rec["status"] == "failed"
            continue
        if single_cell:
            rec = run_cell(arch, shape, multi, args.microbatches)
            path.write_text(json.dumps(rec, indent=1))
        else:
            # subprocess isolation: an XLA hard abort (LOG(FATAL)) in one
            # cell must not kill the sweep — straggler/failure handling
            # for the dry-run itself.
            import subprocess
            import sys
            t0 = time.time()
            cp = subprocess.run(
                [sys.executable, "-m", "repro.launch.dryrun",
                 "--arch", arch, "--shape", shape,
                 "--mesh", "multi" if multi else "single",
                 "--out", str(out)] + (["--force"] if args.force else []),
                capture_output=True, text=True, timeout=3600)
            if not path.exists():
                rec = {"arch": arch, "shape": shape,
                       "mesh": "2x16x16" if multi else "16x16",
                       "status": "failed",
                       "error": "hard crash (XLA abort): "
                                + cp.stderr.strip().splitlines()[0][:200]
                                if cp.stderr.strip() else "hard crash",
                       "wall_s": round(time.time() - t0, 1)}
                path.write_text(json.dumps(rec, indent=1))
            else:
                rec = json.loads(path.read_text())
        line = f"[{rec['status']:7s}] {tag} ({rec.get('wall_s', 0)}s)"
        if rec["status"] == "ok":
            r = rec["roofline"]
            line += (f" dominant={r['dominant']}"
                     f" bound={r['step_bound_s']*1e3:.1f}ms"
                     f" peak={rec['memory']['peak_mb']:.0f}MB/dev")
        elif rec["status"] == "failed":
            failures += 1
            line += " " + rec.get("error", "")[:160]
        print(line, flush=True)
    return 1 if failures else 0


if __name__ == "__main__":
    raise SystemExit(main())
