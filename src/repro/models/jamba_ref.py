"""Plain float32 reference of the Jamba block (AI21-Jamba2-Mini, Jamba
v0.1), for tests.

The published forward, after `transformers`' `models/jamba/modeling_jamba.py`
(`JambaForCausalLM` with `use_mamba_kernels=False`): token embedding; per
layer i, RMSNorm -> mixer -> residual -> RMSNorm -> feed-forward ->
residual; final RMSNorm; an untied output head.

- The mixer is attention where i % attn_layer_period == attn_layer_offset
  (`cfg.attn_every`, `cfg.attn_offset`): GQA, causal, no positional
  encoding (`JambaAttention` has no rotary embedding).  Elsewhere it is the
  Mamba-1 mixer (`JambaMambaMixer.slow_forward`): in_proj -> depthwise
  causal conv with bias -> SiLU -> x_proj -> RMSNorm on dt, B and C ->
  dt_proj with bias, softplus -> the selective scan, one token at a time ->
  + D x -> gate by SiLU(z) -> out_proj.
- The feed-forward is the MoE where i % expert_layer_period == 1
  (`cfg.moe_every`, offset 1): softmax over every expert the router
  scores, the top k gates not renormalised (`JambaSparseMoeBlock`), each
  expert a SwiGLU.  Elsewhere a dense SwiGLU.

Everything is `jax.numpy` in float32, with every matrix product at
`Precision.HIGHEST`: no kernels, caches or batching (rows are independent
sequences).  It reads the weight tree of `models.model.init_params` and
nothing else of the program.  Departures from `modeling_jamba.py`:

- RMSNorm scales are stored as `w - 1` (the program's layout), so the
  reference multiplies by `1 + s`; the router is the same matrix.
- Where the layer holds a share of the experts (`MoEConfig.held`), the
  result is that share's part of the layer: the gates are those of the
  full softmax and top k, and assignments to absent experts add nothing
  (model-configs: an expert layer on one chip of an expert-parallel
  deployment, without its exchange).
- Mamba's padding mask and the attention mask of padded batches are left
  out: every row is a whole sequence.
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp
from jax import lax

HI = lax.Precision.HIGHEST


def rms_norm(x, s, eps):
    return x * lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * (1.0 + s)


def linear(x, w):
    """x (..., I) @ w (I, O)."""
    return jnp.einsum("...i,io->...o", x, w, precision=HI)


def swiglu(f, x):
    return linear(jax.nn.silu(linear(x, f["w_gate"])) * linear(x, f["w_up"]),
                  f["w_down"])


def attention(a, x, n_kv):
    """Causal GQA self-attention without positions; x (n, T, D)."""
    n, T, D = x.shape
    H, hd = a["wq"].shape[1], a["wq"].shape[2]
    q = linear(x, a["wq"].reshape(D, H * hd)).reshape(n, T, H, hd)
    k = linear(x, a["wk"].reshape(D, n_kv * hd)).reshape(n, T, n_kv, hd)
    v = linear(x, a["wv"].reshape(D, n_kv * hd)).reshape(n, T, n_kv, hd)
    k, v = (jnp.repeat(t, H // n_kv, axis=2) for t in (k, v))
    s = jnp.einsum("nqhd,nkhd->nhqk", q, k, precision=HI) / math.sqrt(hd)
    s = jnp.where(jnp.tril(jnp.ones((T, T), bool)), s, -jnp.inf)
    o = jnp.einsum("nhqk,nkhd->nqhd", jax.nn.softmax(s, -1), v,
                   precision=HI)
    return linear(o.reshape(n, T, H * hd), a["wo"].reshape(H * hd, D))


def selective_scan(dt, A, Bm, Cm, x, h0=None):
    """h_t = exp(dt_t A) h_{t-1} + dt_t B_t x_t, y_t = C_t . h_t, one token
    at a time.  dt, x (n, T, I); A (I, N); Bm, Cm (n, T, N).  Returns
    (y (n, T, I), h_T)."""
    n, T, I = x.shape
    h = jnp.zeros((n, I, A.shape[1]), jnp.float32) if h0 is None else h0

    def step(h, inp):
        dt_t, B_t, C_t, x_t = inp
        h = (jnp.exp(dt_t[..., None] * A) * h
             + (dt_t * x_t)[..., None] * B_t[:, None, :])
        return h, jnp.einsum("nis,ns->ni", h, C_t, precision=HI)

    h, ys = lax.scan(step, h, tuple(jnp.moveaxis(t, 1, 0)
                                    for t in (dt, Bm, Cm, x)))
    return jnp.moveaxis(ys, 0, 1), h


def mamba(m, x, eps):
    """Jamba's Mamba-1 mixer over x (n, T, D)."""
    T = x.shape[1]
    I = m["out_proj"].shape[0]
    R, N = m["dt_norm"].shape[0], m["b_norm"].shape[0]
    xz = linear(x, m["in_proj"])
    xi, z = xz[..., :I], xz[..., I:]
    dc = m["conv_w"].shape[0]
    ctx = jnp.pad(xi, ((0, 0), (dc - 1, 0), (0, 0)))
    xi = jax.nn.silu(sum(ctx[:, j:j + T] * m["conv_w"][j] for j in range(dc))
                     + m["conv_b"])
    dbc = linear(xi, m["x_proj"])
    dt = rms_norm(dbc[..., :R], m["dt_norm"], eps)
    Bm = rms_norm(dbc[..., R:R + N], m["b_norm"], eps)
    Cm = rms_norm(dbc[..., R + N:], m["c_norm"], eps)
    dt = jax.nn.softplus(linear(dt, m["dt_proj"]) + m["dt_bias"])
    y, _ = selective_scan(dt, -jnp.exp(m["A_log"]), Bm, Cm, xi)
    y = (y + xi * m["Dskip"]) * jax.nn.silu(z)
    return linear(y, m["out_proj"])


def moe(e, x, moe_cfg):
    """The held experts' part of the layer over x (n, T, D): each held
    expert's SwiGLU weighted by its gate where the token routed to it."""
    probs = jax.nn.softmax(linear(x, e["router"]), -1)
    gates, ids = lax.top_k(probs, moe_cfg.top_k)
    if moe_cfg.renormalize:
        gates = gates / gates.sum(-1, keepdims=True)
    lo, hi = moe_cfg.held_range()
    out = jnp.zeros_like(x)
    for j in range(hi - lo):
        g = jnp.sum(jnp.where(ids == lo + j, gates, 0.0), -1)   # (n, T)
        f = {k: e[k][j] for k in ("w_gate", "w_up", "w_down")}
        out = out + g[..., None] * swiglu(f, x)
    return out


def layer(p, x, i, cfg):
    """Layer i of the stack; p its slice of the weights."""
    eps = cfg.norm_eps
    h = rms_norm(x, p["ln1"], eps)
    if i % cfg.attn_every == cfg.attn_offset:
        x = x + attention(p["attn"], h, cfg.num_kv_heads)
    else:
        x = x + mamba(p["mamba"], h, eps)
    h = rms_norm(x, p["ln2"], eps)
    if cfg.moe is not None and i % cfg.moe_every == 1:
        return x + moe(p["moe"], h, cfg.moe)
    return x + swiglu(p["ffn"], h)


def logits(params, cfg, tokens):
    """Float32 logits (n, T, vocab) of `tokens` (n, T)."""
    f32 = jax.tree.map(lambda a: jnp.asarray(a, jnp.float32), params)
    P = len(f32["layers"])
    with jax.default_matmul_precision("highest"):
        x = f32["embed"][:cfg.vocab_size][jnp.asarray(tokens)]
        for i in range(cfg.num_layers):
            p = jax.tree.map(lambda a: a[i // P], f32["layers"][i % P])
            x = layer(p, x, i, cfg)
        x = rms_norm(x, f32["final_norm"], cfg.norm_eps)
        return linear(x, f32["lm_head"][:cfg.vocab_size].T)
