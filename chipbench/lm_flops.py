"""The benchmark's own operation counts for language models, kept apart
from the program (it imports nothing of it), beside ``flops.py``.

* ``zamba2_prompt_flops`` — one prompt's prefill through Zamba2 (the
  published ``config.json`` keys) to its last position's logits, 2 per
  multiply-accumulate: every projection, the depthwise convolution, the
  shared blocks' attention over the causal pairs, and the chunked SSD
  (within a chunk over its causal pairs; each chunk's state built,
  read and passed on), then the head at one position.  Norms,
  activations, softmax and RoPE are not counted.
"""
from __future__ import annotations


def zamba2_prompt_parts(c: dict, seq: int) -> dict[str, float]:
    """FLOPs of one ``seq``-token prompt by kind (see the module doc)."""
    D, L = int(c["hidden_size"]), int(c["num_hidden_layers"])
    di = int(c["mamba_expand"]) * D
    H, G = int(c["n_mamba_heads"]), int(c["mamba_ngroups"])
    N, K = int(c["mamba_d_state"]), int(c["mamba_d_conv"])
    P = di // H
    xbc = di + 2 * G * N
    NH, KV = int(c["num_attention_heads"]), int(c["num_key_value_heads"])
    hd, F = int(c["attention_head_dim"]), int(c["intermediate_size"])
    r, V = int(c["adapter_rank"]), int(c["vocab_size"])
    apps = sum(1 for i in c["hybrid_layer_ids"] if i < L)
    chunk = min(int(c["chunk_size"]), seq)
    n_chunks = -(-seq // chunk)
    S = float(seq)
    mamba = 2.0 * D * (di + xbc + H) + 2.0 * di * D
    shared = (2.0 * 2 * D * (NH + 2 * KV) * hd + 2.0 * NH * hd * D
              + 2.0 * 3 * D * F + 2.0 * D * r + 2.0 * r * 2 * F
              + 2.0 * D * D)
    pairs_in_chunk = S * (chunk + 1) / 2          # causal (t, s), s <= t
    ssd = (2.0 * G * N * pairs_in_chunk + 2.0 * H * P * pairs_in_chunk
           + 4.0 * H * P * N * S + 2.0 * H * P * N * n_chunks)
    return {
        "matmul": L * mamba * S + apps * shared * S + 2.0 * D * V,
        "conv": L * 2.0 * K * xbc * S,
        "attention": apps * 2.0 * 2 * NH * hd * S * (S + 1) / 2,
        "ssd": L * ssd,
    }


def zamba2_prompt_flops(c: dict, seq: int) -> float:
    """FLOPs of one ``seq``-token prompt's prefill to its last logits."""
    return sum(zamba2_prompt_parts(c, seq).values())
