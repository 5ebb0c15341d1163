"""Plain float32 reference of the hybrid block (Jamba), and its
lower-precision control.

The published forward of `transformers`' `JambaForCausalLM`
(`models/jamba/modeling_jamba.py`, `use_mamba_kernels=False`): token
embedding; per layer i, RMSNorm -> mixer -> residual -> RMSNorm ->
feed-forward -> residual; final RMSNorm; an untied output head.  The mixer
is causal GQA attention without positional encoding where i %
attn_layer_period == attn_layer_offset, else the Mamba-1 mixer (in_proj,
depthwise causal conv with bias, SiLU, x_proj, RMSNorm on dt, B and C,
dt_proj with bias and softplus, the selective scan one token at a time,
+ D x, gated by SiLU(z), out_proj).  The feed-forward is the MoE where i %
expert_layer_period == expert_layer_offset (softmax over all
`num_experts_published` experts, top `num_experts_per_tok`, gates not
renormalised, each expert a SwiGLU), else a dense SwiGLU.

The chip holds the experts `experts_held`: an MoE layer gives that share's
part of its result, the gates those of the full softmax and top k, and
assignments to absent experts add nothing (the program does the same; the
model-configs guide's expert-parallel share, without its exchange).

It reads the weight tree that `hybrid/model.py` makes (the program's
`init_params` layout; norm scales stored as `w - 1`) and nothing of the
program: only `jax.numpy`, in float32, with every matrix product at
`Precision.HIGHEST`.  It runs layer by layer, one jitted body per kind of
layer and per expert, on float32 copies of one layer's (or one expert's)
weights, attention in blocks of query rows (`reference.py`'s) and an
expert over blocks of tokens, so that what it adds to the device is
about a gigabyte.

`quant="fp8"` is the control: the operands of every linear layer (the
projections, the router, the experts, the MLPs, the output head) rounded
to float8 e4m3 as `reference.py` rounds them.
"""
from __future__ import annotations

import functools
import importlib.util
import pathlib
import sys

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

HI = lax.Precision.HIGHEST
T_BLOCK = 1024          # tokens per block of an expert's SwiGLU


def _dense_reference():
    """`reference.py`, by path as `run.py` loads it."""
    if "bench_reference" not in sys.modules:
        path = pathlib.Path(__file__).resolve().parents[1] / "reference.py"
        spec = importlib.util.spec_from_file_location("bench_reference",
                                                      path)
        mod = importlib.util.module_from_spec(spec)
        sys.modules["bench_reference"] = mod
        spec.loader.exec_module(mod)
    return sys.modules["bench_reference"]


_ref = _dense_reference()
linear, rms_norm = _ref.linear, _ref.rms_norm


def gaps(ref_logits, tokens) -> np.ndarray:
    """max(ref) - ref[token] at every position: how far below the
    reference's best each served token's logit lies.  ref (n, G, V),
    tokens (n, G) -> (n, G)."""
    best = jnp.max(ref_logits, -1)
    got = jnp.take_along_axis(ref_logits, jnp.asarray(tokens)[..., None],
                              -1)[..., 0]
    return np.asarray(best - got)


@functools.partial(jax.jit, static_argnames=("eps", "quant"))
def _attn_layer(x, ln, a, li, *, eps, quant):
    a = jax.tree.map(lambda w: w[li].astype(jnp.float32), a)
    n, T, D = x.shape
    H, hd = a["wq"].shape[1], a["wq"].shape[2]
    K = a["wk"].shape[1]
    h = rms_norm(x, ln[li], eps)
    q = linear(h, a["wq"].reshape(D, H * hd), quant).reshape(n, T, H, hd)
    k = linear(h, a["wk"].reshape(D, K * hd), quant).reshape(n, T, K, hd)
    v = linear(h, a["wv"].reshape(D, K * hd), quant).reshape(n, T, K, hd)
    o = _ref.attention(q, k, v, None).reshape(n, T, H * hd)
    return x + linear(o, a["wo"].reshape(H * hd, D), quant)


@functools.partial(jax.jit, static_argnames=("eps", "quant"))
def _mamba_layer(x, ln, m, li, *, eps, quant):
    m = jax.tree.map(lambda w: w[li].astype(jnp.float32), m)
    n, T, D = x.shape
    I = m["out_proj"].shape[0]
    R, N = m["dt_norm"].shape[0], m["b_norm"].shape[0]
    h = rms_norm(x, ln[li], eps)
    xz = linear(h, m["in_proj"], quant)
    xi, z = xz[..., :I], xz[..., I:]
    dc = m["conv_w"].shape[0]
    ctx = jnp.pad(xi, ((0, 0), (dc - 1, 0), (0, 0)))
    xi = jax.nn.silu(sum(ctx[:, j:j + T] * m["conv_w"][j] for j in range(dc))
                     + m["conv_b"])
    dbc = linear(xi, m["x_proj"], quant)
    dt = rms_norm(dbc[..., :R], m["dt_norm"], eps)
    Bm = rms_norm(dbc[..., R:R + N], m["b_norm"], eps)
    Cm = rms_norm(dbc[..., R + N:], m["c_norm"], eps)
    dt = jax.nn.softplus(linear(dt, m["dt_proj"], quant) + m["dt_bias"])
    A = -jnp.exp(m["A_log"])

    def step(s, inp):                     # one token: s (n, I, N)
        dt_t, B_t, C_t, x_t = inp
        s = (jnp.exp(dt_t[..., None] * A) * s
             + (dt_t * x_t)[..., None] * B_t[:, None, :])
        return s, jnp.einsum("nis,ns->ni", s, C_t, precision=HI)

    _, ys = lax.scan(step, jnp.zeros((n, I, N), jnp.float32),
                     tuple(jnp.moveaxis(t, 1, 0) for t in (dt, Bm, Cm, xi)))
    y = (jnp.moveaxis(ys, 0, 1) + xi * m["Dskip"]) * jax.nn.silu(z)
    return x + linear(y, m["out_proj"], quant)


@functools.partial(jax.jit, static_argnames=("eps",))
def _normed(x, ln, li, *, eps):
    """The feed-forward's input (M, D), padded to whole token blocks."""
    n, T, D = x.shape
    h = rms_norm(x, ln[li], eps).reshape(n * T, D)
    return jnp.pad(h, ((0, -(n * T) % T_BLOCK), (0, 0)))


@functools.partial(jax.jit, static_argnames=("quant",))
def _swiglu(h, f, idx, quant):
    """The SwiGLU of the matrices f[...][idx] (bf16, cast here) over h
    (M, D), in blocks of T_BLOCK tokens."""
    f = {k: w[idx].astype(jnp.float32) for k, w in f.items()}

    def block(hb):
        g = linear(hb, f["w_gate"], quant)
        u = linear(hb, f["w_up"], quant)
        return linear(jax.nn.silu(g) * u, f["w_down"], quant)
    M, D = h.shape
    return lax.map(block, h.reshape(M // T_BLOCK, T_BLOCK, D)).reshape(M, D)


@functools.partial(jax.jit, static_argnames=("top_k", "lo", "E_held",
                                             "quant"))
def _gates(h, router, li, *, top_k, lo, E_held, quant):
    """Each held expert's gate (E_held, M): its top-k softmax weight where
    the token routed to it, else 0."""
    probs = jax.nn.softmax(linear(h, router[li].astype(jnp.float32), quant),
                           -1)
    gates, ids = lax.top_k(probs, top_k)
    held = lo + jnp.arange(E_held)
    return jnp.sum(jnp.where(ids[None] == held[:, None, None], gates[None],
                             0.0), -1)


@jax.jit
def _add(x, y, g):
    """x + g * y over the real tokens of y (M, D)."""
    n, T, D = x.shape
    return x + (g[:, None] * y)[:n * T].reshape(n, T, D)


def _moe(x, lw, li, *, eps, top_k, lo, quant):
    e = lw["moe"]
    E_held = e["w_gate"].shape[1]
    h = _normed(x, lw["ln2"], li, eps=eps)
    g = _gates(h, e["router"], li, top_k=top_k, lo=lo, E_held=E_held,
               quant=quant)
    f = {k: e[k] for k in ("w_gate", "w_up", "w_down")}
    for j in range(E_held):               # one expert's weights at a time
        x = _add(x, _swiglu(h, f, (li, j), quant), g[j])
    return x


def _dense_ffn(x, lw, li, *, eps, quant):
    h = _normed(x, lw["ln2"], li, eps=eps)
    y = _swiglu(h, lw["ffn"], (li,), quant)
    return _add(x, y, jnp.ones(y.shape[0], y.dtype))


@functools.partial(jax.jit, static_argnames=("vocab",))
def _embed(embed, tokens, *, vocab):
    return embed[:vocab][tokens].astype(jnp.float32)


def residual(weights, c: dict, tokens, quant=None, before_moe=None):
    """The residual stream (n, T, D) after the last layer, float32.
    `before_moe(i, x)`, where given, is called with the stream ahead of
    each MoE layer i's feed-forward."""
    eps = c["rms_norm_eps"]
    P = len(weights["layers"])
    lo = c["experts_held"][0]
    x = _embed(weights["embed"], jnp.asarray(tokens), vocab=c["vocab_size"])
    for i in range(c["num_hidden_layers"]):
        lw, li = weights["layers"][i % P], i // P
        if i % c["attn_layer_period"] == c["attn_layer_offset"]:
            x = _attn_layer(x, lw["ln1"], lw["attn"], li, eps=eps,
                            quant=quant)
        else:
            x = _mamba_layer(x, lw["ln1"], lw["mamba"], li, eps=eps,
                             quant=quant)
        if i % c["expert_layer_period"] == c["expert_layer_offset"]:
            if before_moe is not None:
                before_moe(i, x)
            x = _moe(x, lw, li, eps=eps, top_k=c["num_experts_per_tok"],
                     lo=lo, quant=quant)
        else:
            x = _dense_ffn(x, lw, li, eps=eps, quant=quant)
    return x


def logits(weights, c: dict, tokens, first: int, quant=None):
    """Reference logits (n, T - first, vocab) of `tokens` (n, T) at
    positions first .. T-1."""
    with jax.default_matmul_precision("highest"):
        x = residual(weights, c, tokens, quant)
        return _ref._head(x, weights["final_norm"], weights["lm_head"],
                          eps=c["rms_norm_eps"], vocab=c["vocab_size"],
                          first=first, quant=quant)
