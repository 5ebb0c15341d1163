"""One hybrid configuration file -> the program's config, weights and
prompts.

A hybrid configuration file (`configs/<name>.json`) holds the published
`config.json` keys of a Jamba model (`model_type` "jamba") as the
benchmark runs it: blocks of `attn_layer_period` layers with attention at
`attn_layer_offset` and Mamba-1 mixers elsewhere, an MoE every
`expert_layer_period` layers.  `num_experts` is the count this chip holds,
`experts_held` their range among the `num_experts_published` the router
scores.  This module maps those keys onto the program's `ModelConfig`, and
makes the weights and prompts from a seed.  The weights are the
benchmark's own: made here, on the device, one leaf at a time (a large leaf
one matrix at a time), in the layout of the program's `init_params`, their
routers then balanced (`balance_routers`).
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
import numpy as np

# Norm scales are random around 1 (stored as w - 1); matrices have the
# configuration's `initializer_range` as std, the residual writers (wo,
# w_down, out_proj) scaled by 1/sqrt(2 L) as in GPT-2's init.  The Mamba
# mixer's own parameters follow the Mamba reference init (Gu and Dao 2023,
# state-spaces/mamba): conv weight and bias uniform in +-1/sqrt(d_conv),
# dt_proj uniform in +-dt_rank**-0.5, dt_bias the inverse softplus of a dt
# log-uniform in [DT_MIN, DT_MAX], A_log = log(1..d_state), D = 1.
NORM_STD = 0.1
LEAF_BYTES = 2**28          # a leaf larger than this is made a matrix a time
DT_MIN, DT_MAX = 1e-3, 1e-1


def head_dim(c: dict) -> int:
    return c.get("head_dim") or c["hidden_size"] // c["num_attention_heads"]


def program_config(c: dict):
    """The program's `ModelConfig` for a hybrid configuration file."""
    from repro.configs.base import MambaConfig, ModelConfig, MoEConfig
    D = c["hidden_size"]
    if c["mamba_dt_rank"] != math.ceil(D / 16) or not c["mamba_conv_bias"] \
            or c["mamba_proj_bias"]:
        raise ValueError("the program's mixer has dt_rank = d / 16, a conv "
                         "bias and no projection bias")
    if c["expert_layer_offset"] != c["expert_layer_period"] - 1:
        raise ValueError("the program puts the MoE last in its period")
    lo, hi = c["experts_held"]
    if hi - lo != c["num_experts"]:
        raise ValueError("num_experts is the count of experts_held")
    return ModelConfig(
        name=c["name"], family="hybrid",
        num_layers=c["num_hidden_layers"], d_model=D,
        num_heads=c["num_attention_heads"],
        num_kv_heads=c["num_key_value_heads"], head_dim=head_dim(c),
        d_ff=c["intermediate_size"], vocab_size=c["vocab_size"],
        sliding_window=c["sliding_window"], use_rope=False,
        norm_eps=c["rms_norm_eps"], tie_embeddings=False,
        attn_every=c["attn_layer_period"],
        attn_offset=c["attn_layer_offset"],
        mamba=MambaConfig(d_state=c["mamba_d_state"],
                          d_conv=c["mamba_d_conv"],
                          expand=c["mamba_expand"]),
        moe=MoEConfig(num_experts=c["num_experts_published"],
                      top_k=c["num_experts_per_tok"],
                      d_ff=c["intermediate_size"], dispatch="grouped",
                      renormalize=False, held=(lo, hi)),
        moe_every=c["expert_layer_period"])


def seed_key(seed: int, stream: int):
    """A JAX key from a seed of any size (PRNGKey keeps only 32 bits)."""
    words = np.random.SeedSequence([seed, stream]).generate_state(2)
    return jax.random.wrap_key_data(jnp.asarray(words, jnp.uint32),
                                    impl="threefry2x32")


def weight_shapes(c: dict):
    """ShapeDtypeStructs of the weight tree: `init_params`' layout."""
    from repro.models import model as M
    cfg = program_config(c)
    return jax.eval_shape(lambda: M.init_params(jax.random.PRNGKey(0), cfg))


def _leaf_kind(path: str) -> str:
    last = path.rsplit("[", 1)[-1].strip("]'\"")
    if last in ("ln1", "ln2", "final_norm", "dt_norm", "b_norm", "c_norm"):
        return "norm"
    if last in ("wo", "w_down", "out_proj"):
        return "residual"
    if last in ("conv_w", "conv_b", "dt_proj", "dt_bias", "A_log", "Dskip"):
        return last
    return "matrix"


@functools.partial(jax.jit, static_argnums=(1, 2, 3, 4, 5))
def _leaf(key, shape, dtype, kind, c_items, rows):
    """One leaf; `rows` > 1 makes it a matrix at a time (`lax.map`), so
    that the transient of a large leaf is one matrix's."""
    c = dict(c_items)
    dtype = jnp.dtype(dtype)

    def one(k, shp):
        if kind == "norm":
            return jax.random.normal(k, shp, jnp.float32) * NORM_STD
        if kind in ("conv_w", "conv_b"):
            b = 1.0 / math.sqrt(c["mamba_d_conv"])
            return jax.random.uniform(k, shp, jnp.float32, -b, b)
        if kind == "dt_proj":
            b = c["mamba_dt_rank"] ** -0.5
            return jax.random.uniform(k, shp, jnp.float32, -b, b)
        if kind == "dt_bias":
            u = jax.random.uniform(k, shp, jnp.float32)
            dt = jnp.exp(u * (math.log(DT_MAX) - math.log(DT_MIN))
                         + math.log(DT_MIN))
            return dt + jnp.log(-jnp.expm1(-dt))      # softplus^-1(dt)
        if kind == "A_log":
            return jnp.broadcast_to(jnp.log(jnp.arange(
                1, shp[-1] + 1, dtype=jnp.float32)), shp)
        if kind == "Dskip":
            return jnp.ones(shp, jnp.float32)
        std = c["initializer_range"]
        if kind == "residual":
            std /= math.sqrt(2 * c["num_hidden_layers"])
        return jax.random.normal(k, shp, dtype) * jnp.asarray(std, dtype)

    if rows == 1:
        return one(key, shape).astype(dtype)
    out = jax.lax.map(lambda k: one(k, shape[-2:]).astype(dtype),
                      jax.random.split(key, rows))
    return out.reshape(shape)


def make_weights(c: dict, seed: int):
    """The weight tree for `seed`, on the default device, one leaf (a large
    one a matrix) at a time; rows of the embedding and the head past the
    vocabulary are zero."""
    shapes = weight_shapes(c)
    flat, tdef = jax.tree_util.tree_flatten_with_path(shapes)
    keys = jax.random.split(seed_key(seed, 0), len(flat))
    items = tuple(sorted((k, v) for k, v in c.items()
                         if isinstance(v, (int, float))))
    out = []
    for k, (path, s) in zip(keys, flat):
        kind = _leaf_kind(jax.tree_util.keystr(path))
        big = s.size * s.dtype.itemsize > LEAF_BYTES and len(s.shape) > 2
        rows = math.prod(s.shape[:-2]) if big else 1
        w = _leaf(k, s.shape, s.dtype.name, kind, items, rows)
        out.append(w)
    tree = jax.tree.unflatten(tdef, out)
    V = c["vocab_size"]
    for name in ("embed", "lm_head"):            # rows past the vocabulary
        w = tree[name]
        if w.shape[0] > V:
            tree[name] = w.at[V:].set(0)
    return balance_routers(tree, c, seed)


def balance_routers(w, c: dict, seed: int):
    """Each MoE router with the direction that the layer's input shares
    across all tokens projected out of it, and its experts' logits made
    alike in spread and uncorrelated.

    Random weights leave a large component common to every token in the
    residual stream (the Mamba mixers gate one positive-mean activation by
    another), which a random router scores differently for each expert:
    the same few experts would then take most tokens in every batch, by
    the seed's draw.  A trained router is balanced across its experts (the
    published model is trained with a load-balancing loss).  So, layer by
    layer in the float32 reference, over a seeded batch of CALIBRATION
    prompts drawn as the traffic draws them, the mean normed input m of
    each MoE layer is taken and every router column r becomes r - (r . m)
    m / |m|^2, so that the experts' mean logits are equal and a token's
    route depends on what is particular to it.  Then the router's columns
    are mixed (R C^-1/2 s, C the experts' logit covariance over the batch,
    s^2 its mean diagonal) so that every expert's logit has the same
    variance and none moves with another: no expert, and no half of the
    experts (the held share), is favoured by the seed's draw, and every
    seed's decode steps read as many held experts.  Only then is the next
    layer run."""
    import importlib.util
    import pathlib
    import sys
    if "hybrid_reference" not in sys.modules:
        path = pathlib.Path(__file__).resolve().parent / "reference.py"
        spec = importlib.util.spec_from_file_location("hybrid_reference",
                                                      path)
        mod = importlib.util.module_from_spec(spec)
        sys.modules["hybrid_reference"] = mod
        spec.loader.exec_module(mod)
    ref = sys.modules["hybrid_reference"]
    n, T = CALIBRATION
    rng = np.random.default_rng([seed, 4])
    tokens = rng.integers(0, c["vocab_size"], (n, T), dtype=np.int32)
    P = len(w["layers"])

    def balance(i, x):
        lw, li = w["layers"][i % P], i // P
        h = ref._normed(x, lw["ln2"], li, eps=c["rms_norm_eps"])[:n * T]
        lw["moe"]["router"] = _balance(lw["moe"]["router"], li, h)

    with jax.default_matmul_precision("highest"):
        ref.residual(w, c, tokens, before_moe=balance)
    return w


# rows and tokens of the batch the routers are balanced on
CALIBRATION = (4, 512)


@jax.jit
def _balance(router, li, h):
    r = router[li].astype(jnp.float32)
    m = h.mean(0)
    m = m / jnp.linalg.norm(m)
    r = r - jnp.outer(m, m @ r)
    z = h @ r                      # the experts' logits, mean 0 by now
    cov = z.T @ z / z.shape[0]
    lam, v = jnp.linalg.eigh(cov)
    s = jnp.sqrt(jnp.trace(cov) / cov.shape[0])
    r = r @ (v * (s / jnp.sqrt(lam))) @ v.T
    return router.at[li].set(r.astype(router.dtype))


def prompts(seed: int, batch_index: int, batch: int, prompt_len: int,
            vocab: int) -> np.ndarray:
    """Token ids of one batch's prompts: uniform over the vocabulary."""
    rng = np.random.default_rng([seed, 1, batch_index])
    return rng.integers(0, vocab, (batch, prompt_len), dtype=np.int32)
