"""whisper-large-v3 [audio] — enc-dec, conv frontend stubbed.
[arXiv:2212.04356; unverified]"""
from repro.configs.base import ModelConfig, register

CONFIG = register(ModelConfig(
    name="whisper-large-v3", family="audio",
    num_layers=32, d_model=1280, num_heads=20, num_kv_heads=20,
    head_dim=64, d_ff=5120, vocab_size=51866,
    encoder_layers=32, num_audio_frames=1500,
    causal=True, use_rope=False,   # absolute sinusoidal positions
))
