"""The benchmark's own operation counts, pinned by hand."""
from chipbench import flops


def test_resnet50_224_flops_per_image():
    # the same count as the program's block graph for resnet50 at 224
    # (conv and classifier 2/MAC, BN 2/elem, ReLU, add and pooling 1)
    assert flops.resnet_flops_per_image(224, 1000) == 8_212_111_872


def test_qwen3_l23_train_flops_per_token():
    # Qwen/Qwen3-1.7B config.json widths, depth cut from 28 to 23
    cfg = {"d_model": 2048, "n_layers": 23, "n_heads": 16, "n_kv_heads": 8,
           "head_dim": 128, "d_ff": 6144, "vocab": 151936}
    per_layer = (2048 * 16 * 128            # q
                 + 2 * 2048 * 8 * 128       # k, v
                 + 16 * 128 * 2048          # o
                 + 3 * 2048 * 6144          # gate, up, down
                 + 2 * 2048 + 2 * 128)      # ln1, ln2, q_norm, k_norm
    n = 23 * per_layer + 2048 + 151936 * 2048
    assert n == 1_468_894_976
    attn = 12 * 23 * 16 * 128 * 512
    assert flops.lm_train_flops_per_token(cfg, 512) == 6 * n + attn
