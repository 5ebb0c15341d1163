"""Beyond-paper optimization paths must compute the identical function."""
import dataclasses
import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_config, smoke_variant
from repro.models import model as M


def test_chunked_attention_matches_naive():
    for arch in ("qwen3-32b", "h2o-danube-1.8b", "whisper-large-v3"):
        cfg = smoke_variant(get_config(arch))
        toks = jax.random.randint(jax.random.PRNGKey(1), (2, 64), 0,
                                  cfg.vocab_size)
        extra = {}
        if cfg.encoder_layers:
            extra["audio_frames"] = jnp.ones(
                (2, cfg.num_audio_frames, cfg.d_model), jnp.bfloat16)
        params = M.init_params(jax.random.PRNGKey(0), cfg, tp=1)
        a, _, _ = M.forward(params, cfg, toks, extra=extra, remat=False)
        cfg2 = dataclasses.replace(cfg, attn_block=16)
        b, _, _ = M.forward(params, cfg2, toks, extra=extra, remat=False)
        np.testing.assert_allclose(
            np.asarray(a, np.float32), np.asarray(b, np.float32), atol=3e-2)


def test_chunked_attention_grads():
    cfg = dataclasses.replace(smoke_variant(get_config("qwen3-32b")),
                              attn_block=16)
    params = M.init_params(jax.random.PRNGKey(0), cfg, tp=1)
    toks = jax.random.randint(jax.random.PRNGKey(1), (2, 32), 0,
                              cfg.vocab_size)

    def loss(p):
        lg, _, _ = M.forward(p, cfg, toks, remat=False)
        return jnp.sum(lg.astype(jnp.float32) ** 2)
    g = jax.grad(loss)(params)
    assert all(np.isfinite(np.asarray(l, np.float32)).all()
               for l in jax.tree.leaves(g))


def test_scatter_moe_matches_einsum():
    for arch in ("kimi-k2-1t-a32b", "jamba-v0.1-52b"):
        cfg = smoke_variant(get_config(arch))
        cfg = dataclasses.replace(   # jamba's own dispatch is 'grouped'
            cfg, moe=dataclasses.replace(cfg.moe, dispatch="einsum"))
        toks = jax.random.randint(jax.random.PRNGKey(1), (2, 32), 0,
                                  cfg.vocab_size)
        params = M.init_params(jax.random.PRNGKey(0), cfg, tp=1)
        a, aux_a, _ = M.forward(params, cfg, toks, remat=False)
        cfg2 = dataclasses.replace(
            cfg, moe=dataclasses.replace(cfg.moe, dispatch="scatter"))
        b, aux_b, _ = M.forward(params, cfg2, toks, remat=False)
        np.testing.assert_allclose(
            np.asarray(a, np.float32), np.asarray(b, np.float32), atol=3e-2)
        assert abs(float(aux_a) - float(aux_b)) < 1e-6


@pytest.mark.parametrize("arch", ["h2o-danube-1.8b", "jamba-v0.1-52b",
                                  "rwkv6-7b"])
def test_stepwise_decode_matches_forward(arch):
    """Decoding every token from an empty cache, one step at a time, gives
    the full-sequence forward's logits at each position; h2o-danube's ring
    of 8 slots wraps twice."""
    cfg = smoke_variant(get_config(arch))
    if cfg.sliding_window:
        cfg = dataclasses.replace(cfg, sliding_window=8)
    params = M.init_params(jax.random.PRNGKey(0), cfg, tp=1)
    B, S = 2, 20
    toks = jax.random.randint(jax.random.PRNGKey(1), (B, S), 0,
                              cfg.vocab_size)
    full, _, _ = M.forward(params, cfg, toks, remat=False)
    caches = M.init_caches(cfg, B, S, tp=1)
    step = jax.jit(functools.partial(M.decode_step, cfg=cfg))
    for t in range(S):
        lg, caches = step(params, token=toks[:, t:t + 1], caches=caches)
        np.testing.assert_allclose(np.asarray(lg[:, 0], np.float32),
                                   np.asarray(full[:, t], np.float32),
                                   atol=0.15, err_msg=f"position {t}")


def test_microbatch_accumulation_matches_full_batch():
    from repro.optim import OptimizerConfig, adamw_init
    from repro.train import make_train_step
    cfg = smoke_variant(get_config("qwen3-32b"))
    oc = OptimizerConfig(lr=1e-3)
    toks = jax.random.randint(jax.random.PRNGKey(1), (8, 17), 0,
                              cfg.vocab_size)
    batch = {"tokens": toks[:, :-1], "labels": toks[:, 1:]}
    outs = {}
    for k in (1, 4):
        params = M.init_params(jax.random.PRNGKey(0), cfg, tp=1)
        state = adamw_init(params, oc)
        step = jax.jit(make_train_step(cfg, oc, microbatches=k))
        for _ in range(3):
            state, m = step(state, batch)
        outs[k] = float(m["loss"])
    assert abs(outs[1] - outs[4]) < 0.02, outs
