"""The benchmark's own operation counts, kept apart from the program.

Nothing here imports the program: the counts follow the published
layer shapes, so a change to the program cannot change what the
benchmark charges it for.

* ``resnet_flops_per_image`` — a bottleneck ResNet (He et al.,
  arXiv:1512.03385, torchvision layout: stride on the 3x3) at a given
  input size.  Convolutions and the classifier count 2 per
  multiply-accumulate; batch norm 2 per element, ReLU, the residual add
  and global average pooling 1, max pooling one per window element.
  The ReLU that follows a residual add is fused into the add and not
  counted apart.
* ``lm_train_flops_per_token`` — one training step of a dense
  decoder-only transformer: 6 x the parameters a token passes through
  (non-embedding weights plus the tied output head) plus attention's
  12 x layers x attention width x sequence (PaLM, arXiv:2204.02311,
  appendix B).  Recomputed activations (``remat``) are not counted.
"""
from __future__ import annotations

RESNET50_STAGES = ((64, 3, 1), (128, 4, 2), (256, 6, 2), (512, 3, 2))


def _conv_out(h: int, k: int, s: int, p: int) -> int:
    return (h + 2 * p - k) // s + 1


def resnet_flops_per_image(hw: int = 224, num_classes: int = 1000,
                           stages=RESNET50_STAGES) -> float:
    """FLOPs of one image through a bottleneck ResNet at ``hw`` x ``hw``."""
    total = 0.0

    def conv(h, cin, cout, k, s, p):
        nonlocal total
        ho = _conv_out(h, k, s, p)
        total += 2.0 * ho * ho * k * k * cin * cout
        return ho

    def bn(h, c):
        nonlocal total
        total += 2.0 * h * h * c

    def elementwise(h, c):
        nonlocal total
        total += 1.0 * h * h * c

    h = conv(hw, 3, 64, 7, 2, 3)          # stem
    bn(h, 64)
    elementwise(h, 64)                    # relu
    ho = _conv_out(h, 3, 2, 1)            # 3x3/2 max pool
    total += 9.0 * ho * ho * 64
    h, cin = ho, 64
    for mid, blocks, stride in stages:
        cout = 4 * mid
        for j in range(blocks):
            s = stride if j == 0 else 1
            h_in = h
            h1 = conv(h_in, cin, mid, 1, 1, 0)
            bn(h1, mid)
            elementwise(h1, mid)
            h2 = conv(h1, mid, mid, 3, s, 1)
            bn(h2, mid)
            elementwise(h2, mid)
            h3 = conv(h2, mid, cout, 1, 1, 0)
            bn(h3, cout)
            if s != 1 or cin != cout:     # projection shortcut
                hs = conv(h_in, cin, cout, 1, s, 0)
                bn(hs, cout)
            elementwise(h3, cout)         # residual add, its relu fused
            h, cin = h3, cout
    elementwise(h, cin)                   # global average pool
    total += 2.0 * cin * num_classes      # classifier
    return total


def lm_params_per_token(d_model: int, n_layers: int, n_heads: int,
                        n_kv_heads: int, head_dim: int, d_ff: int,
                        vocab: int, qk_norm: bool = True,
                        gated_mlp: bool = True) -> int:
    """Weights one token's forward pass multiplies through: every
    layer's projections and norms, the final norm and the output head
    (the tied embedding, counted once as the head)."""
    q = d_model * n_heads * head_dim
    kv = 2 * d_model * n_kv_heads * head_dim
    o = n_heads * head_dim * d_model
    mlp = (3 if gated_mlp else 2) * d_model * d_ff
    norms = 2 * d_model + (2 * head_dim if qk_norm else 0)
    per_layer = q + kv + o + mlp + norms
    return n_layers * per_layer + d_model + vocab * d_model


def lm_train_flops_per_token(cfg: dict, seq: int) -> float:
    """Model FLOPs of one trained token (forward and backward)."""
    n = lm_params_per_token(
        cfg["d_model"], cfg["n_layers"], cfg["n_heads"], cfg["n_kv_heads"],
        cfg["head_dim"], cfg["d_ff"], cfg["vocab"], cfg.get("qk_norm", True),
        cfg.get("gated_mlp", True))
    attn = 12.0 * cfg["n_layers"] * cfg["n_heads"] * cfg["head_dim"] * seq
    return 6.0 * n + attn
