"""A plain Zamba2 (Glorioso et al., arXiv:2411.15242; the equations of
``transformers``' ``modeling_zamba2.py``), prefill to the last position's
logits, written for the benchmark alone.  It imports nothing of the
program.

Configuration keys are the published ``config.json``'s.  Per layer: a
Mamba-2 layer ``x + mamba(rmsnorm(x + t))``, where on a hybrid layer
``t = linear(shared(concat(x, e)))`` and ``e`` is the token embeddings.
``shared`` is memory block ``a % num_mem_blocks`` for the layer's
application ``a``: RMSNorm over the 2·hidden width, attention with RoPE
on every head dimension and scores scaled by ``(head_dim/2)^-0.5``,
RMSNorm, and the gated-GELU MLP with the application's rank-r adapter
on its gate and up projections; it has no residual of its own.  The
Mamba-2 mixer is the chunked SSD of the published torch path, heads in
``mamba_ngroups`` groups sharing B and C, ``dt`` floored at
``time_step_min``, and a group-wise gated RMSNorm (eps 1e-5).  The head
is the embedding, tied.

``make_weights`` draws the weights from a seed on the device as
``Zamba2PreTrainedModel._init_weights`` does: normal, std
``initializer_range`` (0.02), for every linear, convolution and the
embedding (its padding row 0 zeroed), convolution biases 0, norms 1,
``dt_bias`` the inverse softplus of ``dt`` log-uniform in
[time_step_min, time_step_max] (floored at time_step_floor),
``A_log = log(1..H)`` and ``D = 1``.  They are kept in bfloat16, except
``A_log``, ``D`` and ``dt_bias`` in float32, and stored in the
program's shapes (matrices as (in, out); attention as (in, heads,
head_dim)), so ``program_params`` only regroups them.  ``forward`` runs
in float32 at matmul precision ``highest``: the weights are upcast one
layer at a time inside each layer's program, which changes no number
and keeps the float32 copy to one layer.

Departures from the published description: none in the mathematics.
The SSD passes state between chunks as the recurrence says (the
``mamba_ssm`` kernels the published model runs with do the same);
``transformers`` 4.57's CPU fallback sums that decay over the wrong
axis, so its output changes with ``chunk_size`` once a prompt spans two
chunks, and the tests compare with it on one chunk.  The attention mask
has no padding (every prompt is whole), and the published chunked SSD
zero-pads a sequence to a whole number of chunks, which here is already
whole.
"""
from __future__ import annotations

import math
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np

from chipbench import lm_flops

PRECISION = "highest"
MAMBA_NORM_EPS = 1e-5            # Zamba2RMSNormGated's, fixed in the source


# --------------------------------------------------------------------------- #
# shapes
# --------------------------------------------------------------------------- #
def dims(c: dict) -> dict:
    D = int(c["hidden_size"])
    di = int(c["mamba_expand"]) * D
    H = int(c["n_mamba_heads"])
    G = int(c["mamba_ngroups"])
    N = int(c["mamba_d_state"])
    L = int(c["num_hidden_layers"])
    hybrid = [i for i in c["hybrid_layer_ids"] if i < L]
    return dict(D=D, di=di, H=H, P=di // H, G=G, N=N, K=int(c["mamba_d_conv"]),
                xbc=di + 2 * G * N, L=L, hybrid=hybrid,
                M=int(c["num_mem_blocks"]), A=len(hybrid),
                NH=int(c["num_attention_heads"]),
                KV=int(c["num_key_value_heads"]),
                hd=int(c["attention_head_dim"]),
                F=int(c["intermediate_size"]), r=int(c["adapter_rank"]),
                V=int(c["vocab_size"]), chunk=int(c["chunk_size"]))


def layout(c: dict) -> list[tuple[str, tuple[int, ...], str]]:
    """(name, shape, init) of every weight, in a fixed order."""
    d = dims(c)
    D, D2 = d["D"], 2 * d["D"]
    out: list[tuple[str, tuple[int, ...], str]] = [("embed", (d["V"], D), "embed")]
    for i in range(d["L"]):
        p = f"layers.{i}."
        out += [(p + "ln", (D,), "ones"),
                (p + "in_proj", (D, d["di"] + d["xbc"] + d["H"]), "normal"),
                (p + "conv_w", (d["xbc"], d["K"]), "normal"),
                (p + "conv_b", (d["xbc"],), "zeros"),
                (p + "A_log", (d["H"],), "A_log"),
                (p + "D", (d["H"],), "ones32"),
                (p + "dt_bias", (d["H"],), "dt_bias"),
                (p + "norm", (d["di"],), "ones"),
                (p + "out_proj", (d["di"], D), "normal")]
    for m in range(d["M"]):
        p = f"shared.{m}."
        out += [(p + "in_norm", (D2,), "ones"),
                (p + "wq", (D2, d["NH"], d["hd"]), "normal"),
                (p + "wk", (D2, d["KV"], d["hd"]), "normal"),
                (p + "wv", (D2, d["KV"], d["hd"]), "normal"),
                (p + "wo", (d["NH"], d["hd"], D), "normal"),
                (p + "ff_norm", (D,), "ones"),
                (p + "w_gate", (D, d["F"]), "normal"),
                (p + "w_up", (D, d["F"]), "normal"),
                (p + "w_down", (d["F"], D), "normal")]
    for a in range(d["A"]):
        p = f"hybrid.{a}."
        out += [(p + "ad_in", (D, d["r"]), "normal"),
                (p + "ad_gate", (d["r"], d["F"]), "normal"),
                (p + "ad_up", (d["r"], d["F"]), "normal"),
                (p + "linear", (D, D), "normal")]
    out.append(("final_norm", (D,), "ones"))
    return out


def parameter_count(c: dict) -> int:
    return sum(math.prod(s) for _, s, _ in layout(c))


def flops_per_prompt(c: dict, seq: int) -> float:
    return lm_flops.zamba2_prompt_flops(c, seq)


# --------------------------------------------------------------------------- #
# weights
# --------------------------------------------------------------------------- #
@partial(jax.jit, static_argnums=(1, 2, 3))
def _draw(key, shape, init, c_items):
    c = dict(c_items)
    std = float(c.get("initializer_range", 0.02))
    if init in ("normal", "embed"):
        w = jax.random.normal(key, shape, jnp.float32) * std
        if init == "embed":
            w = w.at[0].set(0.0)                 # pad_token_id 0
        return w.astype(jnp.bfloat16)
    if init == "zeros":
        return jnp.zeros(shape, jnp.bfloat16)
    if init == "ones":
        return jnp.ones(shape, jnp.bfloat16)
    if init == "ones32":
        return jnp.ones(shape, jnp.float32)
    if init == "A_log":
        return jnp.log(jnp.arange(1, shape[0] + 1, dtype=jnp.float32))
    if init == "dt_bias":
        lo, hi = math.log(c["time_step_min"]), math.log(c["time_step_max"])
        dt = jnp.exp(jax.random.uniform(key, shape) * (hi - lo) + lo)
        dt = jnp.maximum(dt, c["time_step_floor"])
        return dt + jnp.log(-jnp.expm1(-dt))     # inverse softplus
    raise ValueError(init)


def _hashable(c: dict) -> tuple:
    keys = ("initializer_range", "time_step_min", "time_step_max",
            "time_step_floor")
    return tuple((k, float(c[k])) for k in keys if k in c)


def make_weights(seed: int, c: dict) -> dict:
    """Every weight from ``seed``, on the device, one draw per array."""
    key = jax.random.PRNGKey(seed)
    ch = _hashable(c)
    return {name: _draw(jax.random.fold_in(key, j), shape, init, ch)
            for j, (name, shape, init) in enumerate(layout(c))}


def program_params(w: dict, c: dict) -> list:
    """The same arrays in the program's block order (``LMChain``):
    embed, one block per layer, head.  Every application of a memory
    block holds the same dict of arrays; no number changes."""
    d = dims(c)
    shared = [{"in_norm": {"scale": w[f"shared.{m}.in_norm"]},
               "attn": {k: w[f"shared.{m}.{k}"] for k in ("wq", "wk", "wv", "wo")},
               "ff_norm": {"scale": w[f"shared.{m}.ff_norm"]},
               "mlp": {k: w[f"shared.{m}.{k}"]
                       for k in ("w_gate", "w_up", "w_down")}}
              for m in range(d["M"])]
    blocks: list = [{"table": w["embed"]}]
    for i in range(d["L"]):
        p = f"layers.{i}."
        mamba = {k: w[p + k] for k in ("in_proj", "conv_w", "conv_b", "A_log",
                                       "D", "dt_bias", "norm", "out_proj")}
        blk = {"layer": {"ln": {"scale": w[p + "ln"]}, "mamba": mamba}}
        if i in d["hybrid"]:
            a = d["hybrid"].index(i)
            blk["shared"] = shared[a % d["M"]]
            blk["hybrid"] = {k: w[f"hybrid.{a}.{k}"]
                             for k in ("ad_in", "ad_gate", "ad_up", "linear")}
        blocks.append(blk)
    blocks.append({"norm": {"scale": w["final_norm"]},
                   "embed": {"table": w["embed"]}})
    return blocks


# --------------------------------------------------------------------------- #
# forward, float32
# --------------------------------------------------------------------------- #
def _f32(tree):
    return jax.tree.map(lambda a: a.astype(jnp.float32), tree)


def rms_norm(x, w, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * w


def segment_sum(a):
    """(..., T) → (..., T, T): sum of a[j+1..i] below the diagonal, -inf
    above it (the published ``segment_sum``)."""
    T = a.shape[-1]
    x = jnp.broadcast_to(a[..., None], (*a.shape, T))
    x = jnp.where(jnp.tril(jnp.ones((T, T), bool), -1), x, 0.0)
    s = jnp.cumsum(x, axis=-2)
    return jnp.where(jnp.tril(jnp.ones((T, T), bool)), s, -jnp.inf)


def mamba_layer(c: dict, w: dict, x, t):
    """One Mamba-2 decoder layer: ``x + mixer(rmsnorm(x + t))``."""
    d = dims(c)
    w = _f32(w)
    B, S, _ = x.shape
    H, P, G, N, di, K = d["H"], d["P"], d["G"], d["N"], d["di"], d["K"]
    h = rms_norm(x + t, w["ln"], float(c["rms_norm_eps"]))
    proj = h @ w["in_proj"]
    gate, xbc, dt = jnp.split(proj, [di, di + d["xbc"]], axis=-1)
    xpad = jnp.pad(xbc, ((0, 0), (K - 1, 0), (0, 0)))
    conv = sum(xpad[:, j:j + S] * w["conv_w"][:, j] for j in range(K))
    xbc = jax.nn.silu(conv + w["conv_b"])
    xs, Bm, Cm = jnp.split(xbc, [di, di + G * N], axis=-1)
    dt = jnp.maximum(jax.nn.softplus(dt + w["dt_bias"]),
                     float(c["time_step_min"]))                  # (B,S,H)
    A = -jnp.exp(w["A_log"])
    xs = xs.reshape(B, S, H, P)
    rep = H // G
    Bm = jnp.repeat(Bm.reshape(B, S, G, N), rep, axis=2)          # (B,S,H,N)
    Cm = jnp.repeat(Cm.reshape(B, S, G, N), rep, axis=2)
    D_res = w["D"][:, None] * xs
    L = min(d["chunk"], S)
    nc = S // L
    assert nc * L == S, "the sequence is a whole number of chunks"
    xdt = (xs * dt[..., None]).reshape(B, nc, L, H, P)
    Adt = (A * dt).reshape(B, nc, L, H).transpose(0, 3, 1, 2)     # (B,H,c,l)
    Bc, Cc = Bm.reshape(B, nc, L, H, N), Cm.reshape(B, nc, L, H, N)
    Acum = jnp.cumsum(Adt, axis=-1)
    Lmat = jnp.exp(segment_sum(Adt))                              # (B,H,c,l,s)
    Gm = jnp.einsum("bclhn,bcshn->bclsh", Cc, Bc)
    Mm = Gm * Lmat.transpose(0, 2, 3, 4, 1)
    y_diag = jnp.einsum("bclsh,bcshp->bclhp", Mm, xdt)
    decay = jnp.exp(Acum[..., -1:] - Acum)                        # (B,H,c,l)
    states = jnp.einsum("bclhn,bhcl,bclhp->bchpn", Bc, decay, xdt)
    states = jnp.concatenate([jnp.zeros_like(states[:, :1]), states], 1)
    dchunk = jnp.exp(segment_sum(jnp.pad(Acum[..., -1], ((0, 0), (0, 0),
                                                         (1, 0)))))
    states = jnp.einsum("bhzc,bchpn->bzhpn", dchunk, states)[:, :-1]
    y_off = jnp.einsum("bclhn,bchpn,bhcl->bclhp", Cc, states, jnp.exp(Acum))
    y = (y_diag + y_off).reshape(B, S, H, P) + D_res
    y = y.reshape(B, S, di) * jax.nn.silu(gate)
    y = y.reshape(B, S, G, di // G)
    y = y * jax.lax.rsqrt(jnp.mean(y * y, -1, keepdims=True) + MAMBA_NORM_EPS)
    y = y.reshape(B, S, di) * w["norm"]
    return x + y @ w["out_proj"]


def rope(x, theta):
    """Rotate-half RoPE over the whole head dimension.  x: (B,S,H,hd)."""
    S, hd = x.shape[1], x.shape[-1]
    inv = 1.0 / theta ** (jnp.arange(0, hd, 2, dtype=jnp.float32) / hd)
    ang = jnp.arange(S, dtype=jnp.float32)[:, None] * inv[None]
    emb = jnp.concatenate([ang, ang], -1)
    cos, sin = jnp.cos(emb)[None, :, None], jnp.sin(emb)[None, :, None]
    x1, x2 = x[..., :hd // 2], x[..., hd // 2:]
    return x * cos + jnp.concatenate([-x2, x1], -1) * sin


def shared_block(c: dict, ws: dict, wa: dict, x, e):
    """One application: ``linear(mlp(rmsnorm(attn(rmsnorm(concat(x, e))))))``."""
    ws, wa = _f32(ws), _f32(wa)
    eps = float(c["rms_norm_eps"])
    d = dims(c)
    B, S, _ = x.shape
    h = rms_norm(jnp.concatenate([x, e], -1), ws["in_norm"], eps)
    q = jnp.einsum("bsd,dhk->bshk", h, ws["wq"])
    k = jnp.einsum("bsd,dhk->bshk", h, ws["wk"])
    v = jnp.einsum("bsd,dhk->bshk", h, ws["wv"])
    theta = float(c["rope_theta"])
    q, k = rope(q, theta), rope(k, theta)
    rep = d["NH"] // d["KV"]
    k, v = jnp.repeat(k, rep, axis=2), jnp.repeat(v, rep, axis=2)
    s = jnp.einsum("bqhk,bshk->bhqs", q, k) * (d["hd"] / 2) ** -0.5
    s = jnp.where(jnp.tril(jnp.ones((S, S), bool)), s, -jnp.inf)
    o = jnp.einsum("bhqs,bshk->bqhk", jax.nn.softmax(s, -1), v)
    o = jnp.einsum("bqhk,hkd->bqd", o, ws["wo"])
    h = rms_norm(o, ws["ff_norm"], eps)
    lo = h @ wa["ad_in"]
    gate = h @ ws["w_gate"] + lo @ wa["ad_gate"]
    up = h @ ws["w_up"] + lo @ wa["ad_up"]
    y = (jax.nn.gelu(gate, approximate=False) * up) @ ws["w_down"]
    return y @ wa["linear"]


def _highest(fn):
    def run(*args):
        with jax.default_matmul_precision(PRECISION):
            return fn(*args)
    return jax.jit(run)


class Forward:
    """The reference's programs for one configuration, compiled once."""

    def __init__(self, c: dict):
        self.c, self.d = c, dims(c)
        self.layer = _highest(partial(mamba_layer, c))
        self.shared = _highest(partial(shared_block, c))
        eps = float(c["rms_norm_eps"])
        self.head = _highest(lambda emb, n, x: rms_norm(
            x[:, -1], n.astype(jnp.float32), eps) @ emb.astype(jnp.float32).T)
        self.embed = jax.jit(lambda emb, t: emb[t].astype(jnp.float32))

    def layers(self, w: dict, x, e, lo: int, hi: int):
        """Layers ``lo``..``hi - 1`` on the residual stream ``x``, the
        shared blocks reading the embeddings ``e``."""
        d = self.d
        for i in range(lo, hi):
            p = f"layers.{i}."
            lw = {k[len(p):]: v for k, v in w.items() if k.startswith(p)}
            t = jnp.zeros_like(x)
            if i in d["hybrid"]:
                a = d["hybrid"].index(i)
                m = a % d["M"]
                ws = {k[len(f"shared.{m}."):]: v for k, v in w.items()
                      if k.startswith(f"shared.{m}.")}
                wa = {k[len(f"hybrid.{a}."):]: v for k, v in w.items()
                      if k.startswith(f"hybrid.{a}.")}
                t = self.shared(ws, wa, x, e)
            x = self.layer(lw, x, t)
        return x

    def __call__(self, w: dict, tokens):
        """tokens (B, S) → last-position logits (B, V), float32."""
        x = e = self.embed(w["embed"], tokens)
        x = self.layers(w, x, e, 0, self.d["L"])
        return self.head(w["embed"], w["final_norm"], x)


def forward(w: dict, tokens, c: dict):
    return Forward(c)(w, tokens)


def as_numpy(w: dict) -> dict:
    """The weights as float32 host arrays (for a comparison on the CPU)."""
    return {k: np.asarray(v.astype(jnp.float32)) for k, v in w.items()}
