"""Zamba2 as published, at a small size on the CPU: the benchmark's plain
reference against ``transformers``' ``Zamba2ForCausalLM`` on the same
weights; the program's block chain, cut at every point, against the
reference; decode through the cache against the full forward; and the
block graph's costs against the benchmark's own count."""
import numpy as np
import pytest

import jax
import jax.numpy as jnp

import repro.configs as configs
from chipbench import lm_flops as flops
from chipbench.reference import zamba2 as ref
from repro.models import lm

# A published-shape Zamba2 at a small size: two memory blocks, two B/C
# groups, two hybrid layers (so each block is applied once), adapters
# on, two SSD chunks per prompt.
TINY = {
    "hidden_size": 32, "num_hidden_layers": 4, "vocab_size": 128,
    "layers_block_type": ["mamba", "hybrid", "mamba", "hybrid"],
    "hybrid_layer_ids": [1, 3], "mamba_expand": 2, "n_mamba_heads": 4,
    "mamba_headdim": 16, "mamba_ngroups": 2, "mamba_d_state": 8,
    "mamba_d_conv": 4, "chunk_size": 8, "num_attention_heads": 4,
    "num_key_value_heads": 4, "attention_head_dim": 16,
    "intermediate_size": 48, "adapter_rank": 4, "num_mem_blocks": 2,
    "rope_theta": 10000.0, "rms_norm_eps": 1e-5, "time_step_min": 0.001,
    "time_step_max": 0.1, "time_step_floor": 1e-4, "use_mem_rope": True,
    "hidden_act": "gelu", "initializer_range": 0.02,
}
SEQ = 16


def _arch(c=TINY):
    """The program's configuration for the same shapes, in float32."""
    return configs.get("zamba2-7b").replace(
        n_layers=c["num_hidden_layers"], d_model=c["hidden_size"],
        n_heads=c["num_attention_heads"], n_kv_heads=c["num_key_value_heads"],
        head_dim=c["attention_head_dim"], d_ff=c["intermediate_size"],
        vocab=c["vocab_size"], ssm_state=c["mamba_d_state"],
        ssm_head_dim=c["mamba_headdim"], ssm_chunk=c["chunk_size"],
        ssm_ngroups=c["mamba_ngroups"],
        hybrid_layer_ids=tuple(c["hybrid_layer_ids"]),
        n_mem_blocks=c["num_mem_blocks"], adapter_rank=c["adapter_rank"],
        attn_chunk=8, dtype="float32", remat=False)


def _weights(seed=0):
    # scaled up from the published 0.02 so that every term moves the
    # logits well above float32 rounding at this width
    w = ref.make_weights(seed, TINY)
    return {k: (v.astype(jnp.float32) * (10.0 if k.endswith(
        ("in_proj", "conv_w", "out_proj", "wq", "wk", "wv", "wo", "w_gate",
         "w_up", "w_down", "ad_in", "ad_gate", "ad_up", "linear", "embed"))
        else 1.0)) for k, v in w.items()}


def _tokens(seed=1, batch=2):
    return np.random.default_rng(seed).integers(
        1, TINY["vocab_size"], (batch, SEQ)).astype(np.int32)


def _reference(w, tokens):
    return np.asarray(ref.forward(w, jnp.asarray(tokens), TINY))


def test_reference_parameter_count_is_the_published_models():
    """The full published configuration cut to 24 layers, counted from
    the reference's layout, equals the count the program's config gives."""
    import json
    from pathlib import Path
    c = json.loads((Path(__file__).parents[1] / "chipbench" / "configs"
                    / "zamba2-7b-l24.json").read_text())
    n = ref.parameter_count(c)
    assert n == c["parameters"]
    assert n == configs.get("zamba2-7b").replace(n_layers=24).param_count()


def _hf_model(w, c=TINY):
    torch = pytest.importorskip("torch")
    tf = pytest.importorskip("transformers")
    from transformers.models.zamba2.modeling_zamba2 import Zamba2ForCausalLM
    keys = ("vocab_size", "hidden_size", "num_hidden_layers",
            "layers_block_type", "mamba_d_state", "mamba_d_conv",
            "mamba_expand", "mamba_ngroups", "time_step_min",
            "time_step_max", "time_step_floor", "n_mamba_heads",
            "chunk_size", "intermediate_size", "hidden_act",
            "num_attention_heads", "num_key_value_heads", "num_mem_blocks",
            "adapter_rank", "use_mem_rope", "rope_theta", "rms_norm_eps",
            "initializer_range")
    cfg = tf.Zamba2Config(**{k: c[k] for k in keys},
                          use_shared_attention_adapter=False)
    cfg._attn_implementation = "eager"
    torch.manual_seed(0)
    m = Zamba2ForCausalLM(cfg).eval().float()
    t = lambda a: torch.tensor(np.asarray(a, np.float32))
    d = ref.dims(TINY)
    D2 = 2 * d["D"]
    sd = {"model.embed_tokens.weight": t(w["embed"]),
          "lm_head.weight": t(w["embed"]),
          "model.final_layernorm.weight": t(w["final_norm"])}
    for i in range(d["L"]):
        p, q = f"layers.{i}.", f"model.layers.{i}."
        if i in d["hybrid"]:
            a = d["hybrid"].index(i)
            m_ = a % d["M"]
            s = f"shared.{m_}."
            st = q + "shared_transformer."
            sd[q + "linear.weight"] = t(w[f"hybrid.{a}.linear"]).T
            sd[st + "input_layernorm.weight"] = t(w[s + "in_norm"])
            for k in "qkv":
                sd[st + f"self_attn.{k}_proj.weight"] = \
                    t(w[s + f"w{k}"]).reshape(D2, -1).T
            sd[st + "self_attn.o_proj.weight"] = \
                t(w[s + "wo"]).reshape(-1, d["D"]).T
            sd[st + "pre_ff_layernorm.weight"] = t(w[s + "ff_norm"])
            sd[st + "feed_forward.gate_up_proj.weight"] = t(np.concatenate(
                [w[s + "w_gate"], w[s + "w_up"]], 1)).T
            sd[st + "feed_forward.down_proj.weight"] = t(w[s + "w_down"]).T
            ad = st + f"feed_forward.gate_up_proj_adapter_list.{a}."
            sd[ad + "0.weight"] = t(w[f"hybrid.{a}.ad_in"]).T
            sd[ad + "1.weight"] = t(np.concatenate(
                [w[f"hybrid.{a}.ad_gate"], w[f"hybrid.{a}.ad_up"]], 1)).T
            q = q + "mamba_decoder."
        sd[q + "input_layernorm.weight"] = t(w[p + "ln"])
        mx = q + "mamba."
        sd[mx + "in_proj.weight"] = t(w[p + "in_proj"]).T
        sd[mx + "conv1d.weight"] = t(w[p + "conv_w"])[:, None, :]
        sd[mx + "conv1d.bias"] = t(w[p + "conv_b"])
        for k in ("A_log", "D", "dt_bias"):
            sd[mx + k] = t(w[p + k])
        sd[mx + "norm.weight"] = t(w[p + "norm"])
        sd[mx + "out_proj.weight"] = t(w[p + "out_proj"]).T
    missing, unexpected = m.load_state_dict(sd, strict=False)
    assert not unexpected, unexpected
    # every parameter tensor was set (the tied ones appear under several
    # names: shared blocks in every layer that applies them)
    left = {n for n, _ in m.named_parameters()} - set(sd)
    assert not left, left
    return m


def test_reference_matches_transformers_zamba2():
    """Logits of the plain reference and of the installed
    ``Zamba2ForCausalLM`` on the same weights and prompts, with the
    prompt one SSD chunk long: ``transformers``' CPU path passes state
    between chunks wrongly (its output moves with ``chunk_size``; the
    reference's does not, next test).  Both run in float32 on the CPU;
    the tolerance, 1e-4 of the largest logit, is float32 rounding
    through four layers, two orders below what any one term of the model
    moves (dropping the adapters moves it more than 1e-2)."""
    torch = pytest.importorskip("torch")
    one_chunk = dict(TINY, chunk_size=SEQ)
    w = ref.as_numpy(_weights())
    tokens = _tokens()
    model = _hf_model(w, one_chunk)
    with torch.no_grad():
        hf = model(torch.tensor(tokens, dtype=torch.long), use_cache=False,
                   logits_to_keep=1).logits[:, -1].numpy()
    wj = {k: jnp.asarray(v) for k, v in w.items()}
    ours = np.asarray(ref.forward(wj, jnp.asarray(tokens), one_chunk))
    scale = np.abs(hf).max()
    assert np.abs(ours - hf).max() / scale < 1e-4, np.abs(ours - hf).max()
    # the comparison sees the adapters
    no_ad = {k: (jnp.zeros_like(v) if ".ad_" in k else v)
             for k, v in wj.items()}
    moved = np.asarray(ref.forward(no_ad, jnp.asarray(tokens), one_chunk))
    assert np.abs(moved - hf).max() / scale > 1e-2


def test_reference_ssd_does_not_depend_on_the_chunk():
    """The reference's chunked SSD gives the same logits at one, two and
    four chunks per prompt: state passes between chunks as the
    recurrence says.  Tolerance 1e-5 of the largest logit: float32
    rounding of a reordered sum."""
    w = {k: jnp.asarray(v) for k, v in ref.as_numpy(_weights()).items()}
    tokens = jnp.asarray(_tokens())
    outs = [np.asarray(ref.forward(w, tokens, dict(TINY, chunk_size=ch)))
            for ch in (SEQ, SEQ // 2, SEQ // 4)]
    scale = np.abs(outs[0]).max()
    for o in outs[1:]:
        assert np.abs(o - outs[0]).max() / scale < 1e-5


def test_benchmark_flops_match_an_independent_count():
    """``chipbench.lm_flops``' count of one prompt against torch's FLOP
    counter over ``transformers``' Zamba2 at a small size.  The counter
    sees the matmuls, the convolution (over the S + K - 1 positions its
    padding makes) and eager attention over all S² pairs, not the causal
    half; it cannot see the SSD's elementwise products.  The angles of
    RoPE, one small matmul it counts, are not model work here."""
    torch = pytest.importorskip("torch")
    from torch.utils.flop_counter import FlopCounterMode
    model = _hf_model(ref.as_numpy(_weights()))
    tokens = torch.tensor(_tokens(batch=1), dtype=torch.long)
    with torch.no_grad(), FlopCounterMode(display=False) as fc:
        model(tokens, use_cache=False, logits_to_keep=1)
    parts = flops.zamba2_prompt_parts(TINY, SEQ)
    K = TINY["mamba_d_conv"]
    seen = (parts["matmul"] + parts["conv"] * (SEQ + K - 1) / SEQ
            + parts["attention"] * 2 * SEQ / (SEQ + 1))
    rope = sum(fc.get_flop_counts()["Zamba2ForCausalLM.model.rotary_emb"]
               .values())
    assert fc.get_total_flops() - rope == pytest.approx(seen, rel=1e-12)


def _chain_and_params(seed=0):
    cfg = _arch()
    w = _weights(seed)
    chain = lm.LMChain(cfg)
    return cfg, chain, ref.program_params(w, TINY), w


@pytest.mark.parametrize("cuts", [(c,) for c in range(1, 6)] + [(2, 4)])
def test_split_program_matches_reference(cuts):
    """The program's block chain split into stages at ``cuts`` (every
    single cut, and a two-cut split), each stage one jitted program and
    the state crossing each cut in two tensors, against the reference.
    Tolerance 1e-4 of the largest logit: float32 rounding, as above."""
    cfg, chain, params, w = _chain_and_params()
    tokens = jnp.asarray(_tokens())
    bounds = (0, *cuts, len(chain.blocks))
    x = tokens
    for lo, hi in zip(bounds, bounds[1:]):
        stage = jax.jit(lambda ps, x, lo=lo, hi=hi: _run(chain, ps, x, lo, hi))
        x = stage(params[lo:hi], x)
        if hi < len(chain.blocks):
            assert set(x) == {"residual", "embed"}
    want = _reference(w, np.asarray(tokens))
    assert np.abs(np.asarray(x) - want).max() / np.abs(want).max() < 1e-4


def _run(chain, ps, x, lo, hi):
    for (_, blk), p in zip(chain.blocks[lo:hi], ps):
        x = blk.apply(p, x)
    return x


@pytest.mark.parametrize("name", ["zamba2-7b"])
def test_chain_params_agree_with_the_stacked_model(name):
    """``LMChain.block_params`` of ``build_params``'s stacked weights runs
    the same model as the scanned forward pass."""
    from repro.models.common import InitBuilder
    cfg = configs.reduced(name)
    params = lm.build_params(cfg, InitBuilder(jax.random.PRNGKey(0),
                                              jnp.float32))
    tokens = jnp.asarray(np.random.default_rng(0).integers(
        0, cfg.vocab, (2, 32)), jnp.int32)
    full, _ = lm.forward_train(cfg, params, {"tokens": tokens})
    chain = lm.LMChain(cfg)
    got = chain.apply(chain.block_params(params), tokens)
    assert float(jnp.max(jnp.abs(got - full[:, -1]))) < 1e-5


@pytest.mark.parametrize("name", ["falcon-mamba-7b", "qwen3-1.7b"])
def test_chain_takes_only_the_hybrid_family(name):
    """No serving cell runs another family through the split runtime, so
    ``LMChain`` refuses it rather than run it untested."""
    with pytest.raises(ValueError, match="no block chain"):
        lm.LMChain(configs.reduced(name))


def test_prefill_then_decode_matches_full_forward_at_published_shapes():
    """Decode through the cache (K/V per application at the attention's
    224/2-style concatenated width, Mamba-2 state per layer) agrees with
    the full forward pass.  Tolerance 2e-4 absolute, the repo's serving
    tolerance for float32 at this size."""
    cfg = _arch()
    from repro.models.common import InitBuilder
    params = lm.build_params(cfg, InitBuilder(jax.random.PRNGKey(3),
                                              jnp.float32))
    tokens = jnp.asarray(_tokens(batch=2))
    full, _ = lm.forward_train(cfg, params, {"tokens": tokens})
    T1 = SEQ // 2
    _, cache = lm.forward_prefill(cfg, params, {"tokens": tokens[:, :T1]},
                                  cache_len=SEQ)
    assert cache["ak"].shape == (2, 2, SEQ, 4, 16)
    worst = 0.0
    for t in range(T1, SEQ):
        lg, cache = lm.forward_decode(cfg, params, tokens[:, t:t + 1], cache)
        worst = max(worst, float(jnp.max(jnp.abs(lg[:, 0] - full[:, t]))))
    assert worst < 2e-4, worst


def test_block_graph_charges_the_benchmarks_flops():
    """The partitioner's block graph of the published block charges, per
    prompt, the FLOPs the benchmark counts itself, within 2%: the graph
    charges executed work (the chunked attention's diagonal blocks and
    each SSD chunk's L×L whole), the benchmark only the causal pairs,
    1.09% less at 2048 tokens.  Each hop carries the residual stream and
    the original embeddings; each memory block is one shared group, its
    weights counted once."""
    import json
    from pathlib import Path
    from repro.models.blocks_adapter import arch_block_graph
    c = json.loads((Path(__file__).parents[1] / "chipbench" / "configs"
                    / "zamba2-7b-l24.json").read_text())
    cfg = configs.get("zamba2-7b").replace(n_layers=24)
    g = arch_block_graph(cfg, 2048)
    assert g.total_flops == pytest.approx(flops.zamba2_prompt_flops(c, 2048),
                                          rel=2e-2)
    assert g.blocks[3].out_bytes == 2 * 2048 * 3584 * 2
    groups = {b.shared_group for b in g.blocks if b.shared_group}
    assert groups == {"mem_block0", "mem_block1"}
    assert g.total_weight_bytes == 2 * c["parameters"]


def test_solver_cuts_are_the_cells():
    """best_throughput of the solver over the block graph at 2048 tokens,
    batch 4, on three v5e chips in a line: the cuts the cell records."""
    import json
    from pathlib import Path
    from repro.core import best_throughput, solve
    from repro.core.scenarios import chips_linear
    from repro.models.blocks_adapter import arch_block_graph
    root = Path(__file__).parents[1] / "chipbench"
    cell = json.loads((root / "cells" /
                       "zamba2-7b-l24.split3.prompt2k.poisson.json").read_text())
    cfg = configs.get("zamba2-7b").replace(n_layers=24)
    g = arch_block_graph(cfg, 2048)
    pick = best_throughput(solve(g, chips_linear(3), batch=4))
    assert list(pick.partition) == cell["cuts"]


# --------------------------------------------------------------------------- #
# the split-serving path: Gateway → Session → EdgePipeline → Worker
# --------------------------------------------------------------------------- #
FREE = dict(rtt_s=0.0, bw_bytes_per_s=float("inf"))


def _gateway(model, params, cuts, max_batch):
    from repro.core.devices import Link
    from repro.core.scenarios import TenantSpec
    from repro.runtime import EdgePipeline, Gateway
    pipe = EdgePipeline(model, params, tuple(cuts),
                        [Link(f"free{i}", **FREE) for i in range(len(cuts))])
    tenants = [TenantSpec(f"t{i}", slo_s=60.0) for i in range(2)]
    return pipe, Gateway(pipe, tenants, max_batch=max_batch)


def _serve(gw, requests):
    ids = [(name, gw.submit(name, x)) for name, x in requests]
    got: dict = {}
    deadline = 120.0
    import time
    t0 = time.perf_counter()
    while len(got) < len(ids) and time.perf_counter() - t0 < deadline:
        for name, rid, value in gw.poll(block=True):
            got[(name, rid)] = value
    return [got[k] for k in ids]


def test_zamba2_serves_split_through_the_gateway():
    """Token-id rows through ``Gateway`` → ``Session`` → ``EdgePipeline``
    on the thread engine, three stages: each request's last-position
    logits agree with the reference (1e-4 of the largest logit, float32
    rounding), every hop carries the residual stream and the original
    embeddings and counts each, and a shared block applied in two stages
    sits on the device once."""
    c = dict(TINY, hybrid_layer_ids=[0, 1, 2, 3],
             layers_block_type=["hybrid"] * 4)
    cfg = _arch(c)
    w = _weights_for(c)
    chain = lm.LMChain(cfg)
    params = ref.program_params(w, c)
    pipe, gw = _gateway(chain, params, (2, 4), max_batch=4)
    try:
        toks = _tokens(seed=5, batch=5)
        reqs = [("t0", toks[:2]), ("t1", toks[2:3]), ("t0", toks[3:5])]
        outs = _serve(gw, reqs)
        want = np.asarray(ref.forward(w, jnp.asarray(toks), c))
        got = np.concatenate(outs)
        assert got.shape == want.shape
        assert np.abs(got - want).max() / np.abs(want).max() < 1e-4
        per_row = SEQ * c["hidden_size"] * 4
        batches = pipe.nets[0].total_transfers
        for net in pipe.nets:
            assert net.tensor_bytes == {"residual": per_row * 4 * batches,
                                        "embed": per_row * 4 * batches}
        # memory block 0 serves applications 0 and 2, in stages 1 and 2
        ws = pipe._engine.workers
        ptrs = {p["shared"]["attn"]["wq"].unsafe_buffer_pointer()
                for wk in ws for p in wk.params if "shared" in p}
        assert len(ptrs) == c["num_mem_blocks"]
    finally:
        gw.close()
        pipe.close()


def _weights_for(c):
    w = ref.make_weights(0, c)
    return {k: v.astype(jnp.float32) for k, v in w.items()}


def test_one_stage_pipeline_serves_through_the_gateway():
    """A one-stage ``EdgePipeline`` (no cuts, no links) on the thread
    engine behind the gateway: results bit-identical to the unsplit
    model, and no hop."""
    from repro.models.cnn import zoo
    model = zoo.get("resnet18", num_classes=10)
    params = model.init(jax.random.PRNGKey(0))
    pipe, gw = _gateway(model, params, (), max_batch=4)
    try:
        assert pipe.n_stages == 1 and pipe.nets == []
        x = np.asarray(jax.random.normal(jax.random.PRNGKey(1),
                                         (3, 32, 32, 3)))
        outs = _serve(gw, [("t0", x[:1]), ("t1", x[1:])])
        pad = np.concatenate([x, np.zeros((1, 32, 32, 3), x.dtype)])
        want = np.asarray(jax.jit(model.apply)(params, pad))[:3]
        assert np.array_equal(np.concatenate(outs), want)
    finally:
        gw.close()
        pipe.close()


def test_benchmark_configuration_is_the_programs_published_zamba2():
    """The benchmark's configuration file, read back at its published 81
    layers, gives the program's registry entry for Zamba2-7B exactly."""
    import json
    from pathlib import Path
    from chipbench.systems.lm_split_serving import arch_config
    c = json.loads((Path(__file__).parents[1] / "chipbench" / "configs"
                    / "zamba2-7b-l24.json").read_text())
    assert arch_config(dict(c, num_hidden_layers=81)) == configs.get("zamba2-7b")
    assert arch_config(c).n_attn_apps == 4
    assert arch_config(c).attn_apps.count(-1) == 20
