"""Training launcher: end-to-end driver with fault tolerance.

Runs any registered architecture (``--arch``, full or ``--reduced``) with
checkpoint/restart, deterministic data resume, optional ParetoPipe
auto-partitioning of the pipeline axis, gradient compression, and
failure injection for the crash-restart integration test.

Examples:
  # CPU-scale end-to-end run (~100M params), a few hundred steps:
  python -m repro.launch.train --arch qwen3-1.7b --reduced --steps 300 \
      --batch 8 --seq 128 --ckpt-dir runs/train_qwen3

  # crash/restart drill (kills itself mid-run, then resume):
  python -m repro.launch.train ... --fail-at-step 120
  python -m repro.launch.train ...            # resumes from step 100

  # multi-pod pipeline on forced host devices with ParetoPipe cuts:
  REPRO_HOST_DEVICES=8 python -m repro.launch.train --arch qwen3-1.7b \
      --reduced --pods 2 --auto-partition --steps 20
"""
import os
if os.environ.get("REPRO_HOST_DEVICES"):
    os.environ["XLA_FLAGS"] = (
        "--xla_force_host_platform_device_count="
        + os.environ["REPRO_HOST_DEVICES"])

import argparse
import sys
import time


def main(argv=None) -> int:
    import jax
    import jax.numpy as jnp

    from .. import configs
    from ..checkpoint import CheckpointManager
    from ..data.pipeline import DataConfig, SyntheticLM
    from ..models import lm
    from ..models.common import DTYPES, InitBuilder
    from ..optim import CompressionConfig, OptConfig, cosine_schedule
    from ..runtime.pipeline import (PipelineConfig, init_pipeline_state,
                                    make_pipeline_train_step)
    from ..runtime.steps import init_train_state, make_train_step
    from ..sharding.api import use_mesh_context
    from .compile_cache import enable_compile_cache
    from .mesh import make_host_mesh

    enable_compile_cache()
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True, choices=list(configs.ARCH_NAMES))
    ap.add_argument("--reduced", action="store_true")
    ap.add_argument("--steps", type=int, default=100)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--seq", type=int, default=128)
    ap.add_argument("--lr", type=float, default=3e-4)
    ap.add_argument("--warmup", type=int, default=20)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--d-model", type=int, default=0,
                    help="override width (e.g. ~100M-param runs)")
    ap.add_argument("--n-layers", type=int, default=0)
    ap.add_argument("--ckpt-dir", default="")
    ap.add_argument("--ckpt-every", type=int, default=100)
    ap.add_argument("--fail-at-step", type=int, default=0,
                    help="inject a crash (fault-tolerance drill)")
    ap.add_argument("--compress-grads", action="store_true")
    ap.add_argument("--pods", type=int, default=1)
    ap.add_argument("--data-par", type=int, default=1)
    ap.add_argument("--model-par", type=int, default=1)
    ap.add_argument("--microbatches", type=int, default=4)
    ap.add_argument("--auto-partition", action="store_true",
                    help="ParetoPipe chooses the pipeline cuts")
    ap.add_argument("--log-every", type=int, default=10)
    args = ap.parse_args(argv)

    cfg = configs.reduced(args.arch) if args.reduced else configs.get(args.arch)
    over = {}
    if args.d_model:
        over["d_model"] = args.d_model
    if args.n_layers:
        over["n_layers"] = args.n_layers
    if over:
        cfg = cfg.replace(**over)

    mesh = None
    pcfg = None
    if args.pods > 1 or args.data_par * args.model_par > 1:
        mesh = make_host_mesh(args.pods, args.data_par, args.model_par)
    if args.pods > 1:
        if args.auto_partition:
            from ..models.blocks_adapter import choose_pipeline_cuts
            cuts, pick, _ = choose_pipeline_cuts(cfg, args.seq, args.pods,
                                                 batch=args.batch)
            print(f"[paretopipe] cuts={cuts} predicted latency="
                  f"{pick.latency_s*1e3:.2f}ms thr={pick.throughput:.1f}/s")
            pcfg = PipelineConfig(args.pods, args.microbatches, cuts)
        else:
            pcfg = PipelineConfig.even(cfg.n_layers, args.pods,
                                       args.microbatches)

    opt = OptConfig(lr=cosine_schedule(args.lr, args.warmup, args.steps))
    comp = CompressionConfig(enabled=args.compress_grads)

    ctx_mgr = use_mesh_context(mesh) if mesh is not None else None
    if ctx_mgr is not None:
        ctx_mgr.__enter__()
    try:
        key = jax.random.PRNGKey(args.seed)
        if pcfg is not None:
            params = lm.build_params(cfg, InitBuilder(key, DTYPES[cfg.dtype]))
            state = init_pipeline_state(cfg, pcfg, params, mesh)
            del params
            step_fn = make_pipeline_train_step(cfg, pcfg, opt, mesh)
        else:
            state = init_train_state(cfg, key, opt, comp)
            step_fn = make_train_step(cfg, opt, comp)
        step_fn = jax.jit(step_fn, donate_argnums=(0,))

        data = SyntheticLM(cfg, DataConfig(args.batch, args.seq, args.seed))
        mgr = None
        start = 0
        if args.ckpt_dir:
            mgr = CheckpointManager(args.ckpt_dir, every=args.ckpt_every)
            restored, manifest = mgr.restore(specs_tree=None)
            if restored is not None:
                state = jax.tree.map(jnp.asarray, restored)
                start = int(manifest["step"])
                data.load_state_dict(manifest["extra"]["data"])
                print(f"[resume] step {start}")

        t0 = time.time()
        for step in range(start, args.steps):
            if args.fail_at_step and step == args.fail_at_step:
                # crash between async checkpoint writes, not during one:
                # the drill tests restart from a durable checkpoint; a
                # torn in-flight write is a separate failure mode the
                # manager already survives by never restoring *.tmp dirs
                if mgr is not None:
                    mgr.wait()
                print(f"[fault-injection] crashing at step {step}",
                      flush=True)
                os._exit(42)
            batch = data.batch_at(step)
            data.step = step + 1
            state, metrics = step_fn(state, batch)
            if step % args.log_every == 0 or step == args.steps - 1:
                loss = float(metrics["loss"])
                print(f"step {step:5d} loss {loss:.4f} "
                      f"gnorm {float(metrics.get('grad_norm', 0)):.3f} "
                      f"({(time.time()-t0):.1f}s)", flush=True)
            if mgr is not None and mgr.should_save(step + 1):
                mgr.save(state, step + 1,
                         extra={"data": data.state_dict()}, block=False)
        if mgr is not None:
            mgr.save(state, args.steps, extra={"data": data.state_dict()})
        print(f"[done] {args.steps} steps, final loss "
              f"{float(metrics['loss']):.4f}")
        return 0
    finally:
        if ctx_mgr is not None:
            ctx_mgr.__exit__(*sys.exc_info())


if __name__ == "__main__":
    raise SystemExit(main())
