"""Beyond-paper optimization paths must compute the identical function."""
import dataclasses
import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_config, smoke_variant
from repro.configs.base import MoEConfig
from repro.models import jamba_ref as R
from repro.models import layers as L
from repro.models import model as M


@pytest.mark.parametrize("arch", ["kimi-k2-1t-a32b", "llama4-scout-17b-a16e",
                                  "jamba-v0.1-52b"])
def test_moe_matches_reference(arch):
    """The dropless MoE layer is the float32 reference's: every top-k
    assignment computed and weighted by its gate (renormalised or not),
    plus the shared expert where the config has one; `routed` counts the
    reference's assignments to each expert."""
    cfg = smoke_variant(get_config(arch))
    moe = cfg.moe
    e = M._moe_params(jax.random.PRNGKey(0), cfg)
    keys = jax.random.split(jax.random.PRNGKey(1), len(jax.tree.leaves(e)))
    e = jax.tree.unflatten(jax.tree.structure(e), [   # std 1/sqrt(d)
        0.125 * jax.random.normal(k, a.shape)
        for k, a in zip(keys, jax.tree.leaves(e))])
    x = jax.random.normal(jax.random.PRNGKey(2), (2, 24, cfg.d_model))
    with jax.default_matmul_precision("highest"):
        out, _, routed = L.moe_ffn(e, x, moe)
        want = R.moe(e, x, moe)
        if moe.num_shared_experts:
            want = want + R.swiglu(e["shared"], x)
        ids = jax.lax.top_k(jax.nn.softmax(R.linear(x, e["router"]), -1),
                            moe.top_k)[1]
    np.testing.assert_allclose(np.asarray(out), np.asarray(want),
                               rtol=1e-5, atol=1e-5)
    assert np.abs(np.asarray(want)).max() > 0.1
    counts = np.bincount(np.asarray(ids).ravel(), minlength=moe.num_experts)
    assert (np.asarray(routed) == counts).all()


def test_moe_dispatch_is_grouped_only():
    with pytest.raises(ValueError, match="dispatch"):
        MoEConfig(num_experts=4, top_k=2, d_ff=64, dispatch="einsum")


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
