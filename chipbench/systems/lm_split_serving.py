"""A language model split into stages behind the multi-tenant gateway,
on one chip: the CNN deployment of ``split_serving`` with prompts of
token ids for images and last-position logits for class scores.

``Gateway`` -> ``Session`` -> ``EdgePipeline`` on the thread engine,
every stage on the chip, links that cost nothing, so no emulation
sleeps.  The model is the program's ``LMChain`` (embed, one block per
layer, head); a cut carries the state between blocks.  The cell file
gives the cuts, the codec, the backend and the gateway's batch; the
configuration gives the model in its published keys.

Weights are the reference's own, from the seed, handed to the program
in its block layout; the reference also gives the FLOPs charged per
prompt (``flops_per_item``).  ``check`` runs the plain reference over
every prompt of the pool once and compares every delivered row; then it
compares what crossed each cut, in the micro-batches of set-up, with the
reference at that cut (``compare_cuts``).
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp
import numpy as np

from chipbench.systems import split_serving
from chipbench.systems.split_serving import FREE_LINK, compare_rows

# ArchConfig field <- published config.json key, for every width the
# program reads; the rest (family, activation, kernel chunking) is the
# program's registry entry named by ``program_config``
PUBLISHED = {
    "n_layers": "num_hidden_layers", "d_model": "hidden_size",
    "n_heads": "num_attention_heads", "n_kv_heads": "num_key_value_heads",
    "head_dim": "attention_head_dim", "d_ff": "intermediate_size",
    "vocab": "vocab_size", "ssm_state": "mamba_d_state",
    "ssm_expand": "mamba_expand", "ssm_conv": "mamba_d_conv",
    "ssm_head_dim": "mamba_headdim", "ssm_chunk": "chunk_size",
    "ssm_ngroups": "mamba_ngroups", "ssm_dt_min": "time_step_min",
    "n_mem_blocks": "num_mem_blocks", "adapter_rank": "adapter_rank",
    "norm_eps": "rms_norm_eps", "rope_theta": "rope_theta",
    "hybrid_layer_ids": "hybrid_layer_ids",
}


def arch_config(config: dict):
    """The program's configuration of the published model ``config``."""
    from repro import configs
    cfg = configs.get(config["program_config"]).replace(
        **{f: (tuple(config[k]) if isinstance(config[k], list) else config[k])
           for f, k in PUBLISHED.items()})
    if cfg.ssm_heads != int(config["n_mamba_heads"]):
        raise RuntimeError(f"{cfg.ssm_heads} Mamba heads, the configuration "
                           f"says {config['n_mamba_heads']}")
    return cfg


class System(split_serving.System):
    """The deployment, built and warmed; ``submit``/``poll`` are the
    gateway's own calls, the only entries the window drives."""

    def __init__(self, config: dict, cell: dict, traffic: dict, seed: int):
        from repro.core.devices import Link
        from repro.core.scenarios import TenantSpec
        from repro.models import lm
        from repro.models.common import InitBuilder
        from repro.runtime import EdgePipeline, Gateway

        self.config, self.cell = config, cell
        self.seq = int(traffic["prompt_tokens"])
        self.max_batch = int(cell["max_batch"])
        self.ref = split_serving.reference(config)
        self.flops_per_item = self.ref.flops_per_prompt(config, self.seq)
        cfg = arch_config(config)
        self.vocab = cfg.vocab
        chain = lm.LMChain(cfg)
        self.weights = self.ref.make_weights(seed, config)
        params = self.ref.program_params(self.weights, config)
        want = jax.eval_shape(lambda k: chain.block_params(
            lm.build_params(cfg, InitBuilder(k, jnp.bfloat16))),
            jax.random.PRNGKey(0))
        got = jax.tree.map(lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype),
                           params)
        if want != got:
            raise RuntimeError("the program's block layout changed: "
                               f"{jax.tree.structure(want)}")
        links = [Link(f"free{i}", **FREE_LINK)
                 for i in range(len(cell["cuts"]))]
        self.pipe = EdgePipeline(chain, params, tuple(cell["cuts"]), links,
                                 backend=cell["backend"], codec=cell["codec"])
        self.assert_nothing_emulated()
        self.pipe.warmup(np.zeros((self.max_batch, self.seq), np.int32))
        tenants = [TenantSpec(f"tenant{i}", slo_s=float(traffic["slo_s"]))
                   for i in range(int(traffic["tenants"]))]
        self.tenant_names = [t.name for t in tenants]
        self.gateway = Gateway(self.pipe, tenants, max_batch=self.max_batch)
        self.taps = StageInputs(self.pipe.workers)

    # -- counters --------------------------------------------------------- #
    def hop_counts(self) -> list[tuple[dict, int]]:
        """Per hop, bytes carried by tensor so far and transfers so far
        (an empty dict where the program counts no tensor)."""
        return [(dict(getattr(n, "tensor_bytes", {})), n.total_transfers)
                for n in self.pipe.nets]

    # -- correctness ------------------------------------------------------ #
    def check(self, pool: np.ndarray, answers, limits: dict) -> list:
        """Every delivered row against the reference on the same prompt."""
        fwd = self.ref.Forward(self.config)
        refs = np.concatenate([np.asarray(fwd(self.weights, pool[i:i + 1]))
                               for i in range(len(pool))])
        return (compare_rows(refs, answers, limits)
                + compare_cuts(fwd, self.weights, self.cell["cuts"],
                               self.taps.taken, limits))


class StageInputs:
    """Host copies of what each stage was given, for the micro-batches
    run while ``armed``: the token ids at the first stage, at each later
    one the state its hop delivered.  ``taken[k]`` lists stage k's, in
    the order the batches ran (one replica per stage keeps it)."""

    def __init__(self, workers):
        self.armed = False
        self.taken: list[list] = [[] for _ in workers]
        for k, w in enumerate(workers):
            w.run = self._tap(k, w.run)

    def _tap(self, k, run):
        def tapped(x):
            if self.armed:
                self.taken[k].append(jax.tree.map(np.asarray, x))
            return run(x)
        return tapped


def compare_cuts(fwd, weights: dict, cuts, taken: list, limits: dict) -> list:
    """What crossed each cut against the reference at that cut, over the
    batches in ``taken`` (``StageInputs.taken``; cut ``c`` lies after
    block ``c - 1``: the embedding, then layers ``0..c - 2``).

    * ``cut_embed_err``: the largest error of the original embeddings
      over the largest reference one; the lookup is exact in bfloat16,
      so a sound hop reads 0.
    * ``embed_effect_lost``: how much of what the embeddings add through
      the shared blocks is missing from the residual stream at the
      cuts: the program's deviation from the reference, projected on
      the change that dropping the embeddings from the shared blocks'
      input makes (reference float32 both ways), as a share of that
      change, absolute; 0 where the program carries their whole
      effect, 1 where its shared blocks ignore them.  Rounding noise is
      nearly orthogonal to that change over millions of numbers.

    A cut with nothing taken reads +inf in both.  → [(name, reading,
    limit)]."""
    if not cuts:
        return []
    n = len(taken[0])
    if n == 0 or any(len(t) != n for t in taken[:len(cuts) + 1]):
        return [("cut_embed_err", math.inf, limits["cut_embed_err"]),
                ("embed_effect_lost", math.inf, limits["embed_effect_lost"])]
    emb_err, num, den = 0.0, 0.0, 0.0
    for j in range(n):
        tokens = taken[0][j]
        e = fwd.embed(weights["embed"], jnp.asarray(tokens))
        x, x0, none, lo = e, e, jnp.zeros_like(e), 0
        for k, c in enumerate(cuts, 1):
            x = fwd.layers(weights, x, e, lo, c - 1)
            x0 = fwd.layers(weights, x0, none, lo, c - 1)
            lo = c - 1
            got = taken[k][j]
            want_e = np.asarray(e)
            emb_err = max(emb_err, float(
                np.max(np.abs(got["embed"].astype(np.float32) - want_e))
                / max(float(np.max(np.abs(want_e))), 1e-30)))
            ref = np.asarray(x, np.float64)
            dev = got["residual"].astype(np.float64) - ref
            drop = np.asarray(x0, np.float64) - ref
            num += float(np.vdot(dev, drop))
            den += float(np.vdot(drop, drop))
    lost = abs(num) / den if den > 0 else math.inf
    return [("cut_embed_err", emb_err, limits["cut_embed_err"]),
            ("embed_effect_lost", lost, limits["embed_effect_lost"])]


def hop_mb_per_batch(before: list, after: list) -> float | None:
    """MB carried across every cut per micro-batch between two
    ``hop_counts`` readings; None without the per-tensor counter."""
    if not after:
        return None
    batches = after[0][1] - before[0][1]
    moved = sum(n - b.get(k, 0) for (b, _), (a, _) in zip(before, after)
                for k, n in a.items())
    if batches <= 0 or not any(a for a, _ in after):
        return None
    return moved / batches / 1e6


def quantized_weights(params, dtype=jnp.float8_e4m3fn):
    """A control: every weight rounded to ``dtype`` and back, each
    distinct array once (shared blocks stay shared)."""
    done: dict[int, jax.Array] = {}

    def q(a):
        if id(a) not in done:
            done[id(a)] = a.astype(dtype).astype(a.dtype)
        return done[id(a)]
    return jax.tree.map(q, params)

