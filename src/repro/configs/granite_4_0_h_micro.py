"""granite-4.0-h-micro — Mamba2 layers interleaved with NoPE GQA attention.

[hf: ibm-granite/granite-4.0-h-micro config.json, model_type
granitemoehybrid]  40L d_model=2048, 32H (GQA kv=8, head 64, no position
embedding) d_ff=8192 vocab=100352, tied embeddings. ``layer_types``: attention
at layers 5, 15, 25, 35, Mamba2 elsewhere (64 heads x 64, d_state 128, one
group, conv 4, expand 2); every layer also has a SwiGLU MLP. muP
multipliers: embedding 12, residual 0.22, attention 0.015625, logits / 8.
"""
from repro.configs.base import ArchConfig, SSMConfig

M, A = "mamba_mlp", "attn"

CONFIG = ArchConfig(
    name="granite-4.0-h-micro",
    family="hybrid",
    source="[hf: ibm-granite/granite-4.0-h-micro]",
    num_layers=40,
    d_model=2048,
    num_heads=32,
    num_kv_heads=8,
    d_ff=8192,
    vocab_size=100352,
    norm_eps=1e-5,
    tie_embeddings=True,
    use_rope=False,
    attn_scale=0.015625,
    embedding_multiplier=12.0,
    residual_multiplier=0.22,
    logits_scaling=8.0,
    # one period of layer_types (attention at i % 10 == 5)
    block_pattern=(M, M, M, M, M, A, M, M, M, M),
    ssm=SSMConfig(kind="mamba2", state_dim=128, head_dim=64, expand=2,
                  conv_width=4),
    remat="block",
)
