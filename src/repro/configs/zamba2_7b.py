"""zamba2-7b [hybrid] — Zamba2-7B-Instruct as published (Glorioso et al.,
arXiv:2411.15242; huggingface.co/Zyphra/Zamba2-7B-Instruct config.json):
81 Mamba-2 layers (112 heads of 64, state 64, 2 B/C groups, conv 4,
chunk 256); at the 13 ``hybrid_layer_ids`` one of two shared
attention+MLP blocks, alternating, reads concat(hidden, original
embeddings) (7168 wide, 32 heads of 224, RoPE), with a rank-128 adapter
on the gated-GELU MLP per application and a per-application linear whose
output joins that layer's Mamba input.  The embedding is tied to the
head, the installed ``transformers`` class's default."""
from ..models.config import ArchConfig

HYBRID_LAYER_IDS = (6, 11, 17, 23, 29, 35, 41, 47, 53, 59, 65, 71, 77)

CONFIG = ArchConfig(
    name="zamba2-7b", family="hybrid",
    n_layers=81, d_model=3584, n_heads=32, n_kv_heads=32,
    d_ff=14336, vocab=32000, head_dim=224, tie_embeddings=True,
    rope_theta=1e4, norm_eps=1e-5,
    ssm_state=64, ssm_expand=2, ssm_conv=4, ssm_head_dim=64, ssm_chunk=256,
    ssm_ngroups=2, ssm_dt_min=1e-3,
    hybrid_layer_ids=HYBRID_LAYER_IDS, n_mem_blocks=2, adapter_rank=128,
    gated_mlp=True, mlp_act="gelu", attn_chunk=512,
)
