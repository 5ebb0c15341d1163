"""Plain float32 reference of the dense decoder, and its lower-precision
control.

The published block of both configurations (Llama / Mistral): token
embedding; per layer, RMSNorm -> GQA self-attention with rotary position
embedding (rotate-half form), causal and, where the configuration sets
`sliding_window`, limited to the last `sliding_window` positions ->
residual -> RMSNorm -> SwiGLU MLP -> residual; final RMSNorm; an untied
output head.  Linear RoPE scaling is applied where `rope_scaling` says so.

It reads the weight tree that `model.make_weights` makes and nothing of
the program: only `jax.numpy`, in float32, with every matrix product at
`Precision.HIGHEST`.  The norm scales are stored as `w - 1` (the layout
the program's steps take), so the reference multiplies by `1 + s`.

It runs layer by layer (one jitted layer body, called once per layer on
that layer's slice of the stacked weights), and attention in blocks of
query rows, so that a float32 copy of one layer is all the weights it adds
to what the device already holds.

`quant="fp8"` is the control: the same computation with the operands of
every linear layer (projections, MLP, output head) rounded to float8 e4m3
with a scale per row of the activations and per output column of the
weights, the accuracy of a W8A8 fp8 inference path; attention scores and
values stay as in the reference.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax import lax

HI = lax.Precision.HIGHEST
F8_MAX = 448.0          # largest finite float8_e4m3fn
Q_BLOCK = 512           # query rows per attention block


def _f8(x, axis):
    """Round to float8 e4m3 with a per-slice scale over `axis`."""
    s = jnp.max(jnp.abs(x), axis=axis, keepdims=True) / F8_MAX
    s = jnp.where(s > 0, s, 1.0)
    return (x / s).astype(jnp.float8_e4m3fn).astype(jnp.float32) * s


def linear(x, w, quant):
    """x (..., I) @ w (I, O) in float32; `quant` rounds both operands."""
    if quant == "fp8":
        x, w = _f8(x, -1), _f8(w, 0)
    return jnp.einsum("...i,io->...o", x, w, precision=HI)


def rms_norm(x, s, eps):
    return x * lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * (1.0 + s)


def rope(x, pos, theta, scaling):
    """x (n, T, h, d), pos (T,): rotate-half RoPE."""
    d = x.shape[-1]
    inv = theta ** (-jnp.arange(0, d, 2, dtype=jnp.float32) / d)
    p = pos.astype(jnp.float32)
    if scaling:
        assert scaling["type"] == "linear", scaling
        p = p / scaling["factor"]
    ang = p[:, None] * inv[None, :]                       # (T, d/2)
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    x1, x2 = x[..., :d // 2], x[..., d // 2:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def attention(q, k, v, window):
    """q (n, T, H, d), k/v (n, T, K, d) -> (n, T, H, d), in query blocks."""
    n, T, H, d = q.shape
    G = H // k.shape[2]
    k = jnp.repeat(k, G, axis=2)                          # q head h -> h // G
    v = jnp.repeat(v, G, axis=2)
    nb = -(-T // Q_BLOCK)
    qp = jnp.pad(q, ((0, 0), (0, nb * Q_BLOCK - T), (0, 0), (0, 0)))
    kpos = jnp.arange(T)

    def block(i):
        qb = lax.dynamic_slice_in_dim(qp, i * Q_BLOCK, Q_BLOCK, axis=1)
        s = jnp.einsum("nqhd,nkhd->nhqk", qb, k, precision=HI) / math.sqrt(d)
        qpos = i * Q_BLOCK + jnp.arange(Q_BLOCK)
        dist = qpos[:, None] - kpos[None, :]
        ok = dist >= 0
        if window is not None:
            ok = ok & (dist < window)
        s = jnp.where(ok, s, -jnp.inf)
        p = jax.nn.softmax(s, axis=-1)
        return jnp.einsum("nhqk,nkhd->nqhd", p, v, precision=HI)

    o = lax.map(block, jnp.arange(nb))                    # (nb, n, Qb, H, d)
    o = jnp.moveaxis(o, 0, 1).reshape(n, nb * Q_BLOCK, H, d)
    return o[:, :T]


@functools.partial(jax.jit, static_argnames=("eps", "theta", "window",
                                             "scaling", "quant"))
def _layer(x, lw, li, *, eps, theta, window, scaling, quant):
    p = jax.tree.map(lambda a: a[li].astype(jnp.float32), lw)
    n, T, D = x.shape
    a = p["attn"]
    H, hd = a["wq"].shape[1], a["wq"].shape[2]
    K = a["wk"].shape[1]
    pos = jnp.arange(T)
    sc = dict(scaling) if scaling else None
    h = rms_norm(x, p["ln1"], eps)
    q = linear(h, a["wq"].reshape(D, H * hd), quant).reshape(n, T, H, hd)
    k = linear(h, a["wk"].reshape(D, K * hd), quant).reshape(n, T, K, hd)
    v = linear(h, a["wv"].reshape(D, K * hd), quant).reshape(n, T, K, hd)
    q, k = rope(q, pos, theta, sc), rope(k, pos, theta, sc)
    o = attention(q, k, v, window).reshape(n, T, H * hd)
    x = x + linear(o, a["wo"].reshape(H * hd, D), quant)
    h = rms_norm(x, p["ln2"], eps)
    f = p["ffn"]
    g = linear(h, f["w_gate"], quant)
    u = linear(h, f["w_up"], quant)
    return x + linear(jax.nn.silu(g) * u, f["w_down"], quant)


@functools.partial(jax.jit, static_argnames=("vocab",))
def _embed(embed, tokens, *, vocab):
    return embed[:vocab][tokens].astype(jnp.float32)


@functools.partial(jax.jit, static_argnames=("eps", "vocab", "first",
                                             "quant"))
def _head(x, final_norm, lm_head, *, eps, vocab, first, quant):
    h = rms_norm(x[:, first:], final_norm.astype(jnp.float32), eps)
    return linear(h, lm_head[:vocab].astype(jnp.float32).T, quant)


def logits(weights, c: dict, tokens, first: int, quant=None):
    """Reference logits (n, T - first, vocab) of `tokens` (n, T) at
    positions first .. T-1."""
    scaling = c.get("rope_scaling")
    kw = dict(eps=c["rms_norm_eps"], theta=float(c["rope_theta"]),
              window=c.get("sliding_window"),
              scaling=tuple(sorted(scaling.items())) if scaling else None,
              quant=quant)
    with jax.default_matmul_precision("highest"):
        x = _embed(weights["embed"], jnp.asarray(tokens),
                   vocab=c["vocab_size"])
        lw = weights["layers"][0]
        for li in range(c["num_hidden_layers"]):
            x = _layer(x, lw, li, **kw)
        return _head(x, weights["final_norm"], weights["lm_head"],
                     eps=c["rms_norm_eps"], vocab=c["vocab_size"],
                     first=first, quant=quant)


def widest_gap(ref_logits, tokens):
    """Largest max(ref) - ref[token] over positions: how far below the
    reference's best a served token's logit lies.  ref (n, G, V), tokens
    (n, G)."""
    best = jnp.max(ref_logits, -1)
    got = jnp.take_along_axis(ref_logits, jnp.asarray(tokens)[..., None],
                              -1)[..., 0]
    return float(jnp.max(best - got))
