"""One configuration file -> the program's config, weights and prompts.

A configuration file (`configs/<name>.json`) holds the published
`config.json` keys of a dense decoder as the benchmark runs it.  This
module maps those keys onto the program's `ModelConfig`, and makes the
weights and prompts from a seed.  The weights are the benchmark's own:
made here, on the device, in one jitted program, in the layout that the
program's step functions take (which is the layout `reference.py` reads).
"""
from __future__ import annotations

import json

import jax
import jax.numpy as jnp
import numpy as np

# The random weights have the configuration's `initializer_range` as std;
# wo and w_down are scaled by 1/sqrt(2 L) as in GPT-2's init, so the
# residual stream stays O(1) over depth.  The norm scales are random around
# 1 so that the norms' weights are exercised.
NORM_STD = 0.1


def head_dim(c: dict) -> int:
    return c.get("head_dim") or c["hidden_size"] // c["num_attention_heads"]


def program_config(c: dict):
    """The program's `ModelConfig` for a configuration file."""
    from repro.configs.base import ModelConfig
    return ModelConfig(
        name=c["name"], family="dense",
        num_layers=c["num_hidden_layers"], d_model=c["hidden_size"],
        num_heads=c["num_attention_heads"],
        num_kv_heads=c["num_key_value_heads"], head_dim=head_dim(c),
        d_ff=c["intermediate_size"], vocab_size=c["vocab_size"],
        sliding_window=c.get("sliding_window"), rope_theta=c["rope_theta"],
        norm_eps=c["rms_norm_eps"], tie_embeddings=False)


def seed_key(seed: int, stream: int):
    """A JAX key from a seed of any size (PRNGKey keeps only 32 bits)."""
    words = np.random.SeedSequence([seed, stream]).generate_state(2)
    return jax.random.wrap_key_data(jnp.asarray(words, jnp.uint32),
                                    impl="threefry2x32")


def padded_vocab(c: dict) -> int:
    return -(-c["vocab_size"] // 128) * 128


def weight_shapes(c: dict) -> dict:
    """Leaf shapes of the weight tree (stacked over layers)."""
    L, D, F = c["num_hidden_layers"], c["hidden_size"], c["intermediate_size"]
    H, K, hd = c["num_attention_heads"], c["num_key_value_heads"], head_dim(c)
    Vp = padded_vocab(c)
    return {
        "embed": (Vp, D), "lm_head": (Vp, D), "final_norm": (D,),
        "layers": [{
            "ln1": (L, D), "ln2": (L, D),
            "attn": {"wq": (L, D, H, hd), "wk": (L, D, K, hd),
                     "wv": (L, D, K, hd), "wo": (L, H, hd, D)},
            "ffn": {"w_gate": (L, D, F), "w_up": (L, D, F),
                    "w_down": (L, F, D)},
        }],
    }


def _make_weights(key, c: dict):
    L, V = c["num_hidden_layers"], c["vocab_size"]
    shapes = weight_shapes(c)
    leaves, tdef = jax.tree.flatten(shapes, is_leaf=lambda s: isinstance(
        s, tuple))
    paths = [jax.tree_util.keystr(p) for p, _ in
             jax.tree_util.tree_flatten_with_path(
                 shapes, is_leaf=lambda s: isinstance(s, tuple))[0]]
    keys = jax.random.split(key, len(leaves))
    out = []
    for k, shape, path in zip(keys, leaves, paths):
        if "ln" in path or "norm" in path:      # f32 scales, stored as w - 1
            out.append(jax.random.normal(k, shape, jnp.float32) * NORM_STD)
            continue
        std = c["initializer_range"]
        if "wo" in path or "w_down" in path:
            std /= np.sqrt(2 * L)
        w = jax.random.normal(k, shape, jnp.bfloat16) * jnp.bfloat16(std)
        if "embed" in path or "lm_head" in path:   # rows past the vocabulary
            w = jnp.where(jnp.arange(shape[0])[:, None] < V, w, 0)
        out.append(w)
    return jax.tree.unflatten(tdef, out)


def make_weights(c: dict, seed: int):
    """The weight tree for `seed`: bf16 matrices, f32 norm scales, on the
    default device, made by one jitted program."""
    key = seed_key(seed, 0)
    return jax.jit(_make_weights, static_argnums=(1,))(key, _Frozen(c))


class _Frozen(dict):
    """A hashable config, so that it can be a static jit argument."""

    def __hash__(self):
        return hash(json.dumps(self, sort_keys=True))


def prompts(seed: int, batch_index: int, batch: int, prompt_len: int,
            vocab: int) -> np.ndarray:
    """Token ids of one batch's prompts: uniform over the vocabulary."""
    rng = np.random.default_rng([seed, 1, batch_index])
    return rng.integers(0, vocab, (batch, prompt_len), dtype=np.int32)
