"""jamba2-3b [hybrid] — Mamba-1 + attention 13:1, dense SwiGLU MLP.

28L d_model=2560 20H (MQA kv=1, head_dim 128) d_ff=8192 vocab=65536 tied
[hf ai21labs/AI21-Jamba2-3B config.json; arXiv:2403.19887]
Layout: attention at layer i where i % 14 == 7 (layers 7 and 21), Mamba
elsewhere; no positional encoding; Mamba d_state 16, d_conv 4, expand 2,
dt_rank 160, RMSNorms on dt, B and C; one MLP per layer (num_experts 1).
"""
from repro.configs.base import ArchConfig, SSMConfig

CONFIG = ArchConfig(
    name="jamba2-3b",
    family="hybrid",
    n_layers=28,
    d_model=2560,
    n_heads=20,
    n_kv_heads=1,
    head_dim=128,
    d_ff=8192,
    vocab_size=65536,
    mlp_act="swiglu",
    norm_eps=1e-6,
    tie_embeddings=True,
    attn_every=14,
    attn_offset=7,
    use_rope=False,
    ssm=SSMConfig(d_state=16, d_conv=4, expand=2, dt_rank=160,
                  inner_norms=True),
    subquadratic=True,           # 26 Mamba layers O(1)/token; 2 attn KV
)
