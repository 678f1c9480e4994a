"""Serving launcher: batched prefill + decode with KV caches.

Runs a registered arch (reduced by default — full configs are dry-run
only on this host), prefems a batch of synthetic prompts, decodes N new
tokens, and reports prefill latency / decode throughput — the paper's
two metrics, on the LM serving path.

  python -m repro.launch.serve --arch qwen3-1.7b --reduced \
      --batch 8 --prompt-len 64 --new-tokens 32
"""
import os
if os.environ.get("REPRO_HOST_DEVICES"):
    os.environ["XLA_FLAGS"] = (
        "--xla_force_host_platform_device_count="
        + os.environ["REPRO_HOST_DEVICES"])

import argparse
import time


def main(argv=None) -> int:
    import jax
    import jax.numpy as jnp

    from .. import configs
    from ..data.pipeline import DataConfig, SyntheticLM
    from ..models import lm
    from ..models.common import DTYPES, InitBuilder
    from ..runtime.steps import make_decode_step, make_prefill_step
    from .compile_cache import enable_compile_cache

    enable_compile_cache()
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True, choices=list(configs.ARCH_NAMES))
    ap.add_argument("--reduced", action="store_true")
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--prompt-len", type=int, default=64)
    ap.add_argument("--new-tokens", type=int, default=32)
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)

    cfg = configs.reduced(args.arch) if args.reduced else configs.get(args.arch)
    params = lm.build_params(cfg, InitBuilder(jax.random.PRNGKey(args.seed),
                                              DTYPES[cfg.dtype]))
    data = SyntheticLM(cfg, DataConfig(args.batch, args.prompt_len, args.seed))
    inputs = {k: v for k, v in next(data).items() if k != "targets"}

    cache_len = args.prompt_len + args.new_tokens \
        + (cfg.n_patches if cfg.family == "vlm" else 0)
    prefill = jax.jit(make_prefill_step(cfg, cache_len))
    decode = jax.jit(make_decode_step(cfg), donate_argnums=(2,))

    tok, cache = prefill(params, inputs)            # warmup+compile
    jax.block_until_ready(tok)
    t0 = time.time()
    tok, cache = prefill(params, inputs)
    jax.block_until_ready(tok)
    t_prefill = time.time() - t0

    toks = [tok]
    tok2, cache = decode(params, tok, cache)        # warmup decode
    t0 = time.time()
    tok = tok2
    for _ in range(args.new_tokens - 1):
        tok, cache = decode(params, tok, cache)
        toks.append(tok)
    jax.block_until_ready(tok)
    t_decode = time.time() - t0

    n_dec = args.new_tokens - 1
    print(f"arch={cfg.name} batch={args.batch} prompt={args.prompt_len}")
    print(f"prefill latency: {t_prefill*1e3:.1f} ms "
          f"({args.batch*args.prompt_len/t_prefill:.0f} tok/s)")
    print(f"decode: {t_decode/n_dec*1e3:.2f} ms/token "
          f"({args.batch*n_dec/t_decode:.0f} tok/s aggregate)")
    out = jnp.concatenate(toks, axis=1)
    print(f"generated shape {out.shape}, finite={bool(jnp.all(out >= 0))}")
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
