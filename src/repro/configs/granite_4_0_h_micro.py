"""Granite-4.0-H-Micro: 40 layers, 36 Mamba-2 and 4 GQA attention layers
without positional encoding, a SwiGLU MLP in every layer, muP
multipliers, tied embeddings.
[hf:ibm-granite/granite-4.0-h-micro config.json]

Per layer: ``h = x + 0.22 * mixer(rmsnorm(x))``, then
``x = h + 0.22 * mlp(rmsnorm(h))``; ``x0 = emb[tok] * 12``, logits
``rmsnorm(x) @ emb.T / 8``, attention scores scaled by 1/64. Mamba-2:
64 heads of 64, d_state 128, one group, a causal conv of width 4 with a
bias over x, B and C, a D skip and a gated RMSNorm before out_proj.
"""
from repro.configs.base import ModelConfig

_PERIOD = ("mamba",) * 5 + ("attention",) + ("mamba",) * 4

CONFIG = ModelConfig(
    arch_id="granite-4.0-h-micro", family="mamba2",
    source="hf:ibm-granite/granite-4.0-h-micro",
    n_layers=40, d_model=2048, n_heads=32, n_kv_heads=8,
    d_ff=8192, vocab_size=100352,
    layer_types=_PERIOD * 4,
    ssm_state=128, ssm_heads=64, ssm_head_dim=64, conv_width=4, rope=False,
    embedding_multiplier=12.0, residual_multiplier=0.22,
    attention_multiplier=0.015625, logits_scaling=8.0, norm_eps=1e-5,
    tie_embeddings=True,
)
