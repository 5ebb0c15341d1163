"""Model substrate: per-arch smoke steps, decode consistency, padding."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import ALL_ARCHS, get_config, smoke_variant
from repro.models import model as M


def _extra(cfg, B):
    extra = {}
    if cfg.cross_attn_every:
        extra["image_embeds"] = jnp.ones(
            (B, cfg.num_image_tokens, cfg.d_model), jnp.bfloat16)
    if cfg.encoder_layers:
        extra["audio_frames"] = jnp.ones(
            (B, cfg.num_audio_frames, cfg.d_model), jnp.bfloat16)
    return extra


@pytest.mark.parametrize("arch", ALL_ARCHS)
def test_smoke_forward_shapes_no_nans(arch):
    cfg = smoke_variant(get_config(arch))
    params = M.init_params(jax.random.PRNGKey(0), cfg, tp=1)
    B, S = 2, 16
    toks = jax.random.randint(jax.random.PRNGKey(1), (B, S), 0,
                              cfg.vocab_size)
    logits, aux, _ = M.forward(params, cfg, toks, extra=_extra(cfg, B),
                               remat=False)
    assert logits.shape == (B, S, cfg.padded_vocab())
    assert not jnp.isnan(logits.astype(jnp.float32)).any()
    assert float(aux) >= 0.0


@pytest.mark.parametrize("arch", ALL_ARCHS)
def test_smoke_train_step(arch):
    from repro.optim import OptimizerConfig, adamw_init
    from repro.train import make_train_step
    cfg = smoke_variant(get_config(arch))
    params = M.init_params(jax.random.PRNGKey(0), cfg, tp=1)
    oc = OptimizerConfig()
    state = adamw_init(params, oc)
    step = jax.jit(make_train_step(cfg, oc))
    B, S = 2, 16
    toks = jax.random.randint(jax.random.PRNGKey(1), (B, S + 1), 0,
                              cfg.vocab_size)
    batch = {"tokens": toks[:, :-1], "labels": toks[:, 1:]}
    if _extra(cfg, B):
        batch["extra"] = _extra(cfg, B)
    state, metrics = step(state, batch)
    assert np.isfinite(float(metrics["loss"]))
    assert int(state.step) == 1


@pytest.mark.parametrize("arch", ["qwen3-32b", "h2o-danube-1.8b",
                                  "rwkv6-7b", "jamba-v0.1-52b",
                                  "whisper-large-v3",
                                  "llama-3.2-vision-90b"])
def test_decode_matches_forward(arch):
    """Teacher-forcing consistency: prefill+decode logits == full forward."""
    cfg = smoke_variant(get_config(arch))
    params = M.init_params(jax.random.PRNGKey(0), cfg, tp=1)
    B, S = 2, 12
    toks = jax.random.randint(jax.random.PRNGKey(1), (B, S), 0,
                              cfg.vocab_size)
    extra = _extra(cfg, B)
    full, _, _ = M.forward(params, cfg, toks, extra=extra, remat=False)

    # prefill on the first S-4 tokens, then decode the next 4 one by one
    P = S - 4
    caches = M.init_caches(cfg, B, S, tp=1)
    _, _, caches = M.forward(params, cfg, toks[:, :P], extra=extra,
                             caches=caches, remat=False)
    errs = []
    for t in range(P, S):
        lg, caches = M.decode_step(params, cfg, toks[:, t:t + 1], caches)
        ref = full[:, t]
        errs.append(float(jnp.max(jnp.abs(
            lg[:, 0].astype(jnp.float32) - ref.astype(jnp.float32)))))
    assert max(errs) < 0.15, errs   # bf16 compute tolerance


def test_swa_ring_cache_decode():
    """SWA decode with a ring cache smaller than the sequence."""
    import dataclasses
    cfg = smoke_variant(get_config("h2o-danube-1.8b"))
    cfg = dataclasses.replace(cfg, sliding_window=8)
    params = M.init_params(jax.random.PRNGKey(0), cfg, tp=1)
    B, S = 1, 24
    toks = jax.random.randint(jax.random.PRNGKey(1), (B, S), 0,
                              cfg.vocab_size)
    full, _, _ = M.forward(params, cfg, toks, remat=False)
    caches = M.init_caches(cfg, B, S, tp=1)   # W = window = 8 ring
    # (n_periods, B, K, hd, Wp): the ring's 8 slots padded to 128 lanes
    assert caches["layers"][0]["kv"]["k"].shape[1:] == (B, 2, 16, 128)
    _, _, caches = M.forward(params, cfg, toks[:, :S - 4], caches=caches,
                             remat=False)
    errs = []
    for t in range(S - 4, S):
        lg, caches = M.decode_step(params, cfg, toks[:, t:t + 1], caches)
        errs.append(float(jnp.max(jnp.abs(
            lg[:, 0].astype(jnp.float32)
            - full[:, t].astype(jnp.float32)))))
    assert max(errs) < 0.15, errs


@pytest.mark.parametrize("arch", ["qwen3-32b", "deepseek-coder-33b",
                                  "whisper-large-v3", "kimi-k2-1t-a32b"])
def test_head_padding_is_exact(arch):
    """TP-padded layouts must compute the identical function."""
    cfg = smoke_variant(get_config(arch))
    toks = jax.random.randint(jax.random.PRNGKey(1), (2, 16), 0,
                              cfg.vocab_size)
    outs = []
    for tp in (1, 4):
        params = M.init_params(jax.random.PRNGKey(0), cfg, tp=tp)
        lg, _, _ = M.forward(params, cfg, toks, extra=_extra(cfg, 2),
                             remat=False)
        outs.append(np.asarray(lg.astype(jnp.float32)))
    np.testing.assert_allclose(outs[0], outs[1], atol=1e-3)


def test_remat_matches_no_remat():
    cfg = smoke_variant(get_config("qwen3-32b"))
    params = M.init_params(jax.random.PRNGKey(0), cfg, tp=1)
    toks = jax.random.randint(jax.random.PRNGKey(1), (2, 16), 0,
                              cfg.vocab_size)
    a, _, _ = M.forward(params, cfg, toks, remat=False)
    b, _, _ = M.forward(params, cfg, toks, remat=True)
    np.testing.assert_allclose(np.asarray(a.astype(jnp.float32)),
                               np.asarray(b.astype(jnp.float32)), atol=1e-5)


def test_use_pallas_matches_ref_path():
    """interpret-mode kernels == jnp path inside the real model."""
    for arch in ("qwen3-32b", "rwkv6-7b"):
        cfg = smoke_variant(get_config(arch))
        params = M.init_params(jax.random.PRNGKey(0), cfg, tp=1)
        toks = jax.random.randint(jax.random.PRNGKey(1), (2, 16), 0,
                                  cfg.vocab_size)
        a, _, _ = M.forward(params, cfg, toks, remat=False,
                            use_pallas=False)
        b, _, _ = M.forward(params, cfg, toks, remat=False, use_pallas=True)
        np.testing.assert_allclose(np.asarray(a.astype(jnp.float32)),
                                   np.asarray(b.astype(jnp.float32)),
                                   atol=3e-2)
