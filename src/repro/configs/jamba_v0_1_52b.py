"""jamba-v0.1-52b [hybrid] — Mamba + attention 1:7 interleave, MoE 16e top-2.

32L d_model=4096 32H (GQA kv=8) d_ff=14336 vocab=65536, MoE 16 experts top-2
[arXiv:2403.19887; hf]
Layout (per the Jamba paper and config): blocks of 8 layers with 1
attention (offset 4 of 8) + 7 Mamba; MoE replaces the MLP on every other
layer (offset 1 of 2). Attention has no positional encoding; Mamba
normalizes dt, B and C after x_proj (dt_rank 256).
"""
from repro.configs.base import ArchConfig, MoEConfig, SSMConfig

CONFIG = ArchConfig(
    name="jamba-v0.1-52b",
    family="hybrid",
    n_layers=32,
    d_model=4096,
    n_heads=32,
    n_kv_heads=8,
    d_ff=14336,
    vocab_size=65536,
    mlp_act="swiglu",
    norm_eps=1e-6,
    attn_every=8,                # 1 attention layer per 8 (1:7 with Mamba)
    attn_offset=4,
    use_rope=False,
    ssm=SSMConfig(d_state=16, d_conv=4, expand=2, dt_rank=256,
                  inner_norms=True),
    moe=MoEConfig(num_experts=16, top_k=2, d_ff=14336, every=2),
    use_fsdp=True,
    subquadratic=True,           # Mamba layers O(1)/token; 4 attn layers KV
)
