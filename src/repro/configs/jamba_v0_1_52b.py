"""jamba-v0.1-52b [hybrid] — Mamba+attn 7:1 interleave, MoE 16e top-2.
[arXiv:2403.19887; hf ai21labs/Jamba-v0.1 and AI21-Jamba2-Mini config.json,
transformers models/jamba/modeling_jamba.py]

Blocks of 8 layers: attention at position 4 (`attn_layer_offset`), without
positional encoding; Mamba-1 elsewhere, with RMSNorms on dt, B and C. The
MoE (softmax over 16 experts, top-2 gates not renormalised, no dropped
token) sits at odd positions (`expert_layer_offset` 1), a dense SwiGLU at
even ones."""
from repro.configs.base import MambaConfig, ModelConfig, MoEConfig, register

CONFIG = register(ModelConfig(
    name="jamba-v0.1-52b", family="hybrid",
    num_layers=32, d_model=4096, num_heads=32, num_kv_heads=8,
    head_dim=128, d_ff=14336, vocab_size=65536, norm_eps=1e-6,
    use_rope=False, attn_every=8, attn_offset=4,
    mamba=MambaConfig(d_state=16, d_conv=4, expand=2),
    moe=MoEConfig(num_experts=16, top_k=2, d_ff=14336, renormalize=False),
    moe_every=2,
))
