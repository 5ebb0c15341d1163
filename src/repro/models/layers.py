"""Pure-functional model layers (params are plain dict pytrees).

Conventions
-----------
* Weights carry TP-aligned padded head counts (see ModelConfig.padded_heads):
  padded q heads have zero Wq columns / zero Wo rows, so the function equals
  the unpadded architecture exactly.
* `rules` is an optional `ShardingRules`; `rules.cs(x, logical)` applies a
  with_sharding_constraint, or is a no-op on a single device.
* Layers are written with jnp/lax only (scan/associative_scan for SSMs) so
  they lower under GSPMD; with `use_pallas` (TPU), attention and the Mamba
  prefill scan run Pallas kernels instead.
* Attention names its device work with `jax.named_scope` (metadata only):
  `qkv`, `rope`, `kv` (prefill's cache fill; the flash wrapper's layout),
  `kernel` (the Pallas path; the flash wrapper adds `kv` and `kernel`
  itself; the decode kernel also writes the token's cache slot) or `core`
  (the XLA path, the decode slot write included), and `out`.  The caller
  puts them under `attn` (`models/model.py`).
* The MoE is dropless: each token's top-k assignments to the experts the
  layer holds run as grouped matmuls (`lax.ragged_dot`), with no capacity.
"""
from __future__ import annotations

import math
from typing import Optional

import jax
import jax.numpy as jnp
from jax import lax

# ---------------------------------------------------------------------------
# Small pieces
# ---------------------------------------------------------------------------


def rms_norm(x, scale, eps=1e-5):
    dtype = x.dtype
    x = x.astype(jnp.float32)
    var = jnp.mean(jnp.square(x), axis=-1, keepdims=True)
    return ((x * lax.rsqrt(var + eps)) * (1.0 + scale.astype(jnp.float32))
            ).astype(dtype)


def rope(x, positions, theta: float):
    """x: (..., S, H, d). positions: (..., S)."""
    d = x.shape[-1]
    half = d // 2
    freqs = jnp.exp(-math.log(theta) * jnp.arange(half, dtype=jnp.float32)
                    / half)
    angles = positions[..., :, None].astype(jnp.float32) * freqs  # (...,S,half)
    cos = jnp.cos(angles)[..., :, None, :]
    sin = jnp.sin(angles)[..., :, None, :]
    x1, x2 = x[..., :half].astype(jnp.float32), x[..., half:].astype(jnp.float32)
    out = jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)
    return out.astype(x.dtype)


def _mask_bias(q_pos, k_pos, causal, window):
    """(..., Sq, Sk) additive bias; q_pos (...,Sq), k_pos (...,Sk)."""
    d = q_pos[..., :, None] - k_pos[..., None, :]
    ok = d >= 0 if causal else jnp.full(d.shape, True)
    if window is not None:
        ok = ok & (d < window)
    return jnp.where(ok, 0.0, -1e30).astype(jnp.float32)


# ---------------------------------------------------------------------------
# Attention (GQA + RoPE + qk-norm + SWA + cross-attn)
# ---------------------------------------------------------------------------


def _attn_core(q, k, v, bias, rules=None):
    """q (B,Sq,H,d), k/v (B,Sk,K,d), bias (B,Sq,Sk) additive fp32."""
    B, Sq, Hq, dh = q.shape
    Kv = k.shape[2]
    group = Hq // Kv
    qg = q.reshape(B, Sq, Kv, group, dh)
    scores = (jnp.einsum("bskgd,btkd->bkgst", qg, k).astype(jnp.float32)
              / math.sqrt(dh))
    scores = scores + bias[:, None, None, :, :]
    w = jax.nn.softmax(scores, axis=-1).astype(v.dtype)
    o = jnp.einsum("bkgst,btkd->bskgd", w, v).reshape(B, Sq, Hq, dh)
    return o


def _qkv(p, x, src, cfg, rules=None):
    q = jnp.einsum("bsd,dhk->bshk", x, p["wq"])
    k = jnp.einsum("bsd,dhk->bshk", src, p["wk"])
    v = jnp.einsum("bsd,dhk->bshk", src, p["wv"])
    if rules is not None:
        q, k, v = (rules.cs(t, "act_bshd") for t in (q, k, v))
    if cfg.qk_norm:
        q = rms_norm(q, p["qn"], cfg.norm_eps)
        k = rms_norm(k, p["kn"], cfg.norm_eps)
    return q, k, v


def _proj_out(p, o, rules=None):
    out = jnp.einsum("bshk,hkd->bsd", o, p["wo"])
    if rules is not None:
        out = rules.cs(out, "act_bsd")
    return out


def _ring_slots(cfg, Wp):
    """How many of a self-attention cache's Wp slots its ring uses: the
    window's worth, or all of them where the window is wider (the cache is
    then sized to the sequence and never wraps)."""
    return min(cfg.sliding_window or Wp, Wp)


def self_attention(p, x, cfg, rules=None, *, causal=None, use_rope=True,
                   kv_cache=None, layer=None, use_pallas=False):
    """Self-attention over a full sequence (train / prefill).

    p: {wq (D,H',hd), wk/wv (D,K',hd), wo (H',hd,D), [qn, kn (hd,)]}
    If kv_cache ({k, v}: (L,B,K',hd,Wp), stacked over layers) is given, the
    sequence's K/V (its last ring's worth) is written into layer `layer`
    from position 0, and (out, new_cache) is returned; attention itself
    always runs over the freshly computed full-sequence K/V.
    """
    causal = cfg.causal if causal is None else causal
    B, S, D = x.shape
    with jax.named_scope("qkv"):
        q, k, v = _qkv(p, x, x, cfg, rules)
    with jax.named_scope("rope"):
        positions = jnp.broadcast_to(jnp.arange(S)[None, :],
                                     (B, S)).astype(jnp.int32)
        if use_rope:
            q = rope(q, positions, cfg.rope_theta)
            k = rope(k, positions, cfg.rope_theta)
    new_cache = None
    if kv_cache is not None:
        with jax.named_scope("kv"):
            Wp = kv_cache["k"].shape[-1]
            R = _ring_slots(cfg, Wp)
            n = min(S, R)                # positions S-n .. S-1 stay,
            new_cache = {}               # position t in slot t % R; the
            for name, t in (("k", k), ("v", v)):   # layer is written whole
                c = kv_cache[name]
                t = t[:, S - n:].transpose(0, 2, 3, 1)[None].astype(c.dtype)
                if S > R:
                    t = jnp.roll(t, S % R, axis=-1)
                t = jnp.pad(t, ((0, 0),) * 4 + ((0, Wp - n),))
                new_cache[name] = lax.dynamic_update_slice(
                    c, t, (layer, 0, 0, 0, 0))
    if use_pallas:                       # the wrapper names its kv and kernel
        from repro.kernels.flash_attention import ops as fops
        o = fops.flash_attention(q, k, v, causal=causal,
                                 window=cfg.sliding_window)
    else:
        with jax.named_scope("core"):
            bias = _mask_bias(positions, positions, causal,
                              cfg.sliding_window)
            o = _attn_core(q, k, v, bias, rules)
    with jax.named_scope("out"):
        return _proj_out(p, o, rules), new_cache


def decode_attention(p, x, cfg, rules=None, *, cache, layer, cache_index,
                     use_rope=True, use_pallas=False):
    """Single-token (Sq=1) self-attention over layer `layer` of the stacked
    KV caches {k, v}: (L,B,K',hd,Wp), a ring of slots on the minor axis.

    The token's K/V is written in place, into its slot of that layer, and
    the attention reads the stacked caches where they lie: the kernel does
    both (under `kernel`), the XLA path both under `core`.
    """
    B, S, D = x.shape
    assert S == 1
    with jax.named_scope("qkv"):
        q, k, v = _qkv(p, x, x, cfg, rules)
    with jax.named_scope("rope"):
        pos = jnp.broadcast_to(cache_index[None, None]
                               if jnp.ndim(cache_index) == 0 else cache_index,
                               (B, 1)).astype(jnp.int32)
        if use_rope:
            q = rope(q, pos, cfg.rope_theta)
            k = rope(k, pos, cfg.rope_theta)
    with jax.named_scope("kernel" if use_pallas else "core"):
        Wp = cache["k"].shape[-1]
        R = _ring_slots(cfg, Wp)
        slot = (cache_index % R).astype(jnp.int32)
        slots = jnp.arange(Wp)
        # slot s holds the latest position t <= cache_index with t % R == s;
        # slots never written yet get negative positions, and slots past the
        # ring none: both are masked.
        kv_pos = cache_index - ((cache_index - slots) % R)
        valid = (slots < R) & (kv_pos >= 0)
        bias = jnp.broadcast_to(jnp.where(valid, 0.0, -1e30),
                                (B, Wp)).astype(jnp.float32)
    args = (q, k, v, cache["k"], cache["v"], bias, layer, slot)
    if use_pallas:                       # the wrapper names its kernel
        from repro.kernels.decode_attention import ops as dops
        o, ck, cv = dops.decode_attention(*args)
    else:
        from repro.kernels.decode_attention import decode_attention_ref
        with jax.named_scope("core"):
            o, ck, cv = decode_attention_ref(*args)
    with jax.named_scope("out"):
        return _proj_out(p, o, rules), {"k": ck, "v": cv}


def cross_attention(p, x, cfg, rules=None, *, kv=None, cache=None):
    """Cross-attention to a fixed source (image tokens / encoder output).

    Either `kv` (source activations (B,T,D), prefill — projects and returns
    a cache) or `cache` ({k,v} precomputed, decode) must be given.
    """
    B, S, D = x.shape
    if cache is None:
        q = jnp.einsum("bsd,dhk->bshk", x, p["wq"])
        k = jnp.einsum("bsd,dhk->bshk", kv, p["wk"])
        v = jnp.einsum("bsd,dhk->bshk", kv, p["wv"])
        if cfg.qk_norm:
            q = rms_norm(q, p["qn"], cfg.norm_eps)
            k = rms_norm(k, p["kn"], cfg.norm_eps)
        new_cache = {"k": k, "v": v}
    else:
        q = jnp.einsum("bsd,dhk->bshk", x, p["wq"])
        if cfg.qk_norm:
            q = rms_norm(q, p["qn"], cfg.norm_eps)
        k, v = cache["k"], cache["v"]
        new_cache = cache
    if rules is not None:
        q = rules.cs(q, "act_bshd")
    T = k.shape[1]
    bias = jnp.zeros((B, S, T), jnp.float32)
    o = _attn_core(q, k, v, bias, rules)
    out = _proj_out(p, o, rules)
    if "gate" in p:                      # gated cross-attn (llama-3.2-vision)
        out = out * jnp.tanh(p["gate"]).astype(out.dtype)
    return out, new_cache


# ---------------------------------------------------------------------------
# FFN: SwiGLU dense + dropless token-choice top-k MoE (grouped matmuls)
# ---------------------------------------------------------------------------


def swiglu(p, x, rules=None):
    g = jnp.einsum("bsd,df->bsf", x, p["w_gate"])
    u = jnp.einsum("bsd,df->bsf", x, p["w_up"])
    h = jax.nn.silu(g) * u
    if rules is not None:
        h = rules.cs(h, "act_bsf")
    return jnp.einsum("bsf,fd->bsd", h, p["w_down"])


def _moe_gates(p, xt, moe_cfg):
    """Softmax over every expert the router scores, then the top k:
    (gate_vals, expert_ids) (N,K), probs (N,E)."""
    logits = jnp.einsum("nd,de->ne", xt, p["router"]).astype(jnp.float32)
    probs = jax.nn.softmax(logits, axis=-1)
    gate_vals, expert_ids = lax.top_k(probs, moe_cfg.top_k)   # (N,K)
    if moe_cfg.renormalize:
        gate_vals = gate_vals / jnp.clip(gate_vals.sum(-1, keepdims=True),
                                         1e-9)
    return gate_vals, expert_ids, probs


def _moe_aux(expert_ids, probs, moe_cfg):
    E = moe_cfg.num_experts
    f = jnp.mean(jax.nn.one_hot(expert_ids[:, 0], E, dtype=jnp.float32), 0)
    pm = jnp.mean(probs, 0)
    return E * jnp.sum(f * pm) * moe_cfg.aux_loss_weight


# rows (token, expert) assignments of one grouped-matmul call: bounds the
# (rows, d_ff) activations of a long prefill
MOE_ROWS = 4096


def _moe_grouped(p, xt, moe_cfg):
    """Dropless top-k MoE over the held experts' share (`moe_cfg.held`).

    The assignments to held experts are sorted by expert and each expert's
    group multiplied by its weights in a grouped matmul (`lax.ragged_dot`),
    in slices of at most MOE_ROWS rows; assignments to absent experts add
    nothing.  Returns (out (N,D), probs, expert_ids, routed (E_held,):
    tokens routed to each held expert)."""
    N, D = xt.shape
    K = moe_cfg.top_k
    lo, hi = moe_cfg.held_range()
    E = hi - lo
    with jax.named_scope("route"):
        gate_vals, expert_ids, probs = _moe_gates(p, xt, moe_cfg)
        local = expert_ids.reshape(-1) - lo                   # (N*K,)
        held = (local >= 0) & (local < E)
        group = jnp.where(held, local, E)                     # absent last
        order = jnp.argsort(group, stable=True)
        routed = jnp.sum(jax.nn.one_hot(group, E, dtype=jnp.int32), 0)
        ends = jnp.cumsum(routed)
        starts = ends - routed
    M = N * K
    c = min(MOE_ROWS, M)
    n = -(-M // c)
    with jax.named_scope("experts"):
        tok = jnp.pad(order // K, (0, n * c - M))
        def rows(j):
            """Assignments j*c .. j*c+c-1 in expert order: the group sizes
            are each expert's rows among them."""
            r0 = j * c
            sizes = jnp.clip(jnp.minimum(ends, r0 + c)
                             - jnp.maximum(starts, r0), 0)
            xs = jnp.take(xt, lax.dynamic_slice_in_dim(tok, r0, c), axis=0)
            g = lax.ragged_dot(xs, p["w_gate"], sizes)
            u = lax.ragged_dot(xs, p["w_up"], sizes)
            h = (jax.nn.silu(g) * u).astype(xt.dtype)
            return lax.ragged_dot(h, p["w_down"], sizes)      # (c, D)
        y = rows(0) if n == 1 else lax.map(rows, jnp.arange(n)).reshape(
            n * c, D)
    with jax.named_scope("combine"):
        # rows past the held groups (absent experts, padding) are not
        # computed: zero them, then put each assignment back in place
        kept = jnp.arange(n * c) < ends[-1]
        y = jnp.where(kept[:, None], y, 0)[:M]
        inv = jnp.zeros((M,), order.dtype).at[order].set(
            jnp.arange(M, dtype=order.dtype))
        w = jnp.where(held, gate_vals.reshape(-1), 0.0)
        out = jnp.sum(y[inv].reshape(N, K, D).astype(jnp.float32)
                      * w.reshape(N, K, 1), axis=1).astype(xt.dtype)
    return out, probs, expert_ids, routed


def moe_ffn(p, x, moe_cfg, rules=None):
    """Token-choice top-k MoE, dropless, over the held experts' share
    (`_moe_grouped`); names its work `moe/route`, `moe/experts` and
    `moe/combine`.

    p: {router (D,E), w_gate/w_up (E',D,F), w_down (E',F,D),
        [shared: swiglu params]}, E' the held experts (all E by default).
    Returns (out, aux_loss, routed): routed (E',) counts the tokens routed
    to each held expert.
    """
    B, S, D = x.shape
    xt = x.reshape(B * S, D)
    with jax.named_scope("moe"):
        out, probs, expert_ids, routed = _moe_grouped(p, xt, moe_cfg)
    out = out.reshape(B, S, D)
    if "shared" in p:
        out = out + swiglu(p["shared"], x, rules)
    return out, _moe_aux(expert_ids, probs, moe_cfg), routed


# ---------------------------------------------------------------------------
# Mamba (selective SSM) — chunked associative scan, bounded working set
# ---------------------------------------------------------------------------

# tokens per chunk of the selective scan: its working set is a few
# (B, MAMBA_CHUNK, I, N) float32 tensors
MAMBA_CHUNK = 16


def _comb(e1, e2):
    a1, b1 = e1
    a2, b2 = e2
    return a2 * a1, a2 * b1 + b2


def _ssm_chunk(h, dt, A, Bm, Cm, xin):
    """The recurrence over one chunk of c tokens from state h (B,I,N):
    dt, xin (B,c,I); Bm, Cm (B,c,N), any float dtype (upcast here).
    Returns (h after the chunk, y (B,c,I) float32)."""
    f32 = jnp.float32
    dt, xin = dt.astype(f32), xin.astype(f32)
    dA = jnp.exp(dt[..., None] * A)                          # (B,c,I,N)
    dBx = (dt * xin)[..., None] * Bm.astype(f32)[:, :, None, :]
    aa, bb = lax.associative_scan(_comb, (dA, dBx), axis=1)
    h_all = aa * h[:, None] + bb                              # (B,c,I,N)
    y = jnp.einsum("bcin,bcn->bci", h_all, Cm.astype(f32))
    return h_all[:, -1], y


def _mamba_ssm_chunked(dt, A, Bm, Cm, xin, h0, chunk=MAMBA_CHUNK):
    """h_t = exp(dt_t*A) h_{t-1} + dt_t*B_t*x_t ; y_t = C_t . h_t.

    dt,xin: (B,S,I)  Bm,Cm: (B,S,Nst)  A: (I,Nst) f32  h0: (B,I,Nst) f32.
    A loop over chunks of `chunk` tokens (an associative scan inside each),
    then the last S % chunk tokens, read from the inputs in place: no
    (B,S,I,N) tensor is formed.  One token (decode) is one chunk.
    Returns y (B,S,I) in xin's dtype, h_final (B,I,Nst) f32.
    """
    Bsz, S, I = xin.shape
    c = min(chunk, S)
    n, tail = divmod(S, c)
    args = (dt, Bm, Cm, xin)

    def sl(t, t0, size):
        return lax.dynamic_slice_in_dim(t, t0, size, axis=1)

    def body(j, carry):
        h, y = carry
        dt_c, B_c, C_c, x_c = (sl(t, j * c, c) for t in args)
        h, y_c = _ssm_chunk(h, dt_c, A, B_c, C_c, x_c)
        return h, lax.dynamic_update_slice_in_dim(y, y_c.astype(y.dtype),
                                                  j * c, axis=1)

    y = jnp.zeros((Bsz, S, I), xin.dtype)
    if n == 1:
        h, y = body(0, (h0, y))
    else:
        h, y = lax.fori_loop(0, n, body, (h0, y))
    if tail:
        dt_t, B_t, C_t, x_t = (sl(t, n * c, tail) for t in args)
        h, y_t = _ssm_chunk(h, dt_t, A, B_t, C_t, x_t)
        y = lax.dynamic_update_slice_in_dim(y, y_t.astype(y.dtype), n * c,
                                            axis=1)
    return y, h


def mamba(p, x, cfg, rules=None, *, state=None, use_pallas=False):
    """Mamba-1 selective SSM block, as Jamba's mixer (`JambaMambaMixer`):
    RMSNorms on dt, B and C after `x_proj`, conv bias, no projection bias.
    With `use_pallas`, a sequence of more than one token runs the scan in
    the Pallas kernel (`kernels/selective_scan`); one token (decode) and
    the XLA path run `_mamba_ssm_chunked`.

    p: {in_proj (D, 2I), conv_w (dc, I), conv_b (I,), x_proj (I, R+2N),
        dt_norm (R,), b_norm, c_norm (N,), dt_proj (R, I), dt_bias (I,),
        A_log (I,N), Dskip (I,), out_proj (I,D)}
    state: {conv: (B, dc-1, I), ssm: (B,I,N)} for decode.
    Names its work `in_proj`, `conv`, `ssm_params`, `scan`, `out`.
    """
    m = cfg.mamba
    B, S, D = x.shape
    I = m.expand * D
    with jax.named_scope("in_proj"):
        xz = jnp.einsum("bsd,de->bse", x, p["in_proj"])
        xin, z = xz[..., :I], xz[..., I:]
        if rules is not None:
            xin = rules.cs(xin, "act_bsf")
            z = rules.cs(z, "act_bsf")
    # depthwise causal conv over seq (dc taps)
    dc = m.d_conv
    with jax.named_scope("conv"):
        if state is not None:
            ctx = jnp.concatenate([state["conv"], xin], axis=1)  # (B,dc-1+S,I)
        else:
            ctx = jnp.pad(xin, ((0, 0), (dc - 1, 0), (0, 0)))
        conv = sum(ctx[:, i:i + S] * p["conv_w"][i] for i in range(dc))
        xin_c = jax.nn.silu(conv + p["conv_b"])
        new_conv = ctx[:, -(dc - 1):] if dc > 1 else ctx[:, :0]

    R = p["dt_proj"].shape[0]
    N = m.d_state
    with jax.named_scope("ssm_params"):
        dbc = jnp.einsum("bsi,ir->bsr", xin_c, p["x_proj"])
        dt_r = rms_norm(dbc[..., :R], p["dt_norm"], cfg.norm_eps)
        Bm = rms_norm(dbc[..., R:R + N], p["b_norm"], cfg.norm_eps)
        Cm = rms_norm(dbc[..., R + N:], p["c_norm"], cfg.norm_eps)
        dt = jax.nn.softplus(jnp.einsum("bsr,ri->bsi", dt_r, p["dt_proj"])
                             + p["dt_bias"])
        A = -jnp.exp(p["A_log"].astype(jnp.float32))
    with jax.named_scope("scan"):
        h0 = state["ssm"] if state is not None else jnp.zeros(
            (B, I, N), jnp.float32)
        if use_pallas and S > 1:
            from repro.kernels.selective_scan import selective_scan
            y, h_last = selective_scan(dt, A, Bm, Cm, xin_c, h0)
        else:
            y, h_last = _mamba_ssm_chunked(dt, A, Bm, Cm, xin_c, h0)
    with jax.named_scope("out"):
        y = y.astype(x.dtype) + xin_c * p["Dskip"]
        y = y * jax.nn.silu(z)
        out = jnp.einsum("bsi,id->bsd", y, p["out_proj"])
    new_state = {"conv": new_conv, "ssm": h_last}
    return out, new_state


# ---------------------------------------------------------------------------
# RWKV6 (Finch): data-dependent decay WKV, chunked parallel form
# ---------------------------------------------------------------------------

RWKV_CHUNK = 64


def _wkv6_chunked(r, k, v, logw, u, S0):
    """out_t = r_t . (S_{t-1} + (u*k_t) v_t^T);  S_t = diag(w_t) S_{t-1}
    + k_t v_t^T,  w_t = exp(logw_t) in (0,1].

    r,k,v,logw: (B,H,S,d)  u: (H,d)  S0: (B,H,d,d)  ->  (out, S_final)
    All decay exponents are differences of a running cumsum and are <= 0,
    so the chunked form is overflow-free by construction.
    """
    B, H, S, d = r.shape
    c = min(RWKV_CHUNK, S)
    nch = S // c
    assert S % c == 0
    rs = r.reshape(B, H, nch, c, d).transpose(2, 0, 1, 3, 4)
    ks = k.reshape(B, H, nch, c, d).transpose(2, 0, 1, 3, 4)
    vs = v.reshape(B, H, nch, c, d).transpose(2, 0, 1, 3, 4)
    lws = logw.reshape(B, H, nch, c, d).transpose(2, 0, 1, 3, 4)

    tri = jnp.tril(jnp.ones((c, c), bool), -1)               # i < t strictly

    def step(S0_, inp):
        rc, kc, vc, lw = inp                                  # (B,H,c,d)
        clw = jnp.cumsum(lw, axis=2)                          # (B,H,c,d)
        clw_prev = clw - lw                                   # sum_{i<t}
        # intra-chunk scores: P[t,i,d] = exp(clw_prev[t] - clw[i]),  i < t
        diff = clw_prev[:, :, :, None, :] - clw[:, :, None, :, :]
        P = jnp.exp(jnp.where(tri[None, None, :, :, None], diff, -jnp.inf))
        att = jnp.einsum("bhtd,bhtid,bhid->bhti", rc, P, kc)
        out = jnp.einsum("bhti,bhie->bhte", att, vc)
        # bonus diagonal term: (r_t . (u * k_t)) v_t
        out = out + jnp.einsum("bhtd,hd,bhtd,bhte->bhte", rc, u, kc, vc)
        # inter-chunk: r~_t = r_t * exp(clw_prev[t])
        out = out + jnp.einsum("bhtd,bhde->bhte", rc * jnp.exp(clw_prev), S0_)
        # state update: S = exp(clw[-1]) S0 + sum_i exp(clw[-1]-clw[i]) k_i v_i
        wtot = clw[:, :, -1:, :]
        Kdec = kc * jnp.exp(wtot - clw)
        S1 = (jnp.exp(wtot.squeeze(2))[..., None] * S0_
              + jnp.einsum("bhid,bhie->bhde", Kdec, vc))
        return S1, out

    S_fin, outs = lax.scan(step, S0, (rs, ks, vs, lws))
    out = outs.transpose(1, 2, 0, 3, 4).reshape(B, H, S, d)
    return out, S_fin


def _lora(x, p, act=jnp.tanh):
    return jnp.einsum("bsr,rd->bsd", act(jnp.einsum("bsd,dr->bsr", x, p["a"])),
                      p["b"])


def rwkv_time_mix(p, x, cfg, rules=None, *, state=None, use_pallas=False):
    """RWKV6 time-mix with data-dependent decay.

    p: {mu_r/k/v/g/w (D,), w0 (D,), w_lora {a (D,r), b (r,D)},
        wr/wk/wv/wg (D,H,hd), wo (H,hd,D), u (H,hd), ln_x (H*hd,)}
    state: {shift (B,1,D), wkv (B,H,hd,hd)}
    """
    B, S, D = x.shape
    H, hd = p["u"].shape
    if state is not None:
        prev = jnp.concatenate([state["shift"], x[:, :-1]], axis=1)
    else:
        prev = jnp.pad(x, ((0, 0), (1, 0), (0, 0)))[:, :-1]
    def mix(mu):
        return x + (prev - x) * mu
    xr, xk, xv, xg, xw = (mix(p[f"mu_{n}"]) for n in "rkvgw")
    r = jnp.einsum("bsd,dhk->bhsk", xr, p["wr"])
    k = jnp.einsum("bsd,dhk->bhsk", xk, p["wk"])
    v = jnp.einsum("bsd,dhk->bhsk", xv, p["wv"])
    g = jax.nn.silu(jnp.einsum("bsd,dhk->bhsk", xg, p["wg"]))
    # data-dependent decay (the Finch contribution)
    wdyn = p["w0"] + _lora(xw, p["w_lora"])                   # (B,S,D)
    logw = -jnp.exp(wdyn.astype(jnp.float32))                 # <= 0
    logw = logw.reshape(B, S, H, hd).transpose(0, 2, 1, 3)
    S0 = state["wkv"] if state is not None else jnp.zeros(
        (B, H, hd, hd), jnp.float32)
    if use_pallas and state is None:
        from repro.kernels.rwkv6 import wkv6
        out, S_fin = wkv6(r.astype(jnp.float32), k.astype(jnp.float32),
                          v.astype(jnp.float32), logw,
                          p["u"].astype(jnp.float32))
    else:
        out, S_fin = _wkv6_chunked(
            r.astype(jnp.float32), k.astype(jnp.float32),
            v.astype(jnp.float32), logw, p["u"].astype(jnp.float32), S0)
    out = out.transpose(0, 2, 1, 3).reshape(B, S, H * hd)
    # group norm per head
    out = out.reshape(B, S, H, hd)
    mu_ = out.mean(-1, keepdims=True)
    var = out.var(-1, keepdims=True)
    out = (out - mu_) * lax.rsqrt(var + 64e-5)
    out = (out.reshape(B, S, H * hd) * p["ln_x"]).astype(x.dtype)
    out = out * g.transpose(0, 2, 1, 3).reshape(B, S, H * hd)
    out = jnp.einsum("bshk,hkd->bsd", out.reshape(B, S, H, hd), p["wo"])
    new_state = {"shift": x[:, -1:], "wkv": S_fin}
    return out, new_state


def rwkv_channel_mix(p, x, *, state=None):
    """p: {mu_k, mu_r (D,), wk (D,F), wv (F,D), wr (D,D)}"""
    if state is not None:
        prev = jnp.concatenate([state, x[:, :-1]], axis=1)
    else:
        prev = jnp.pad(x, ((0, 0), (1, 0), (0, 0)))[:, :-1]
    xk = x + (prev - x) * p["mu_k"]
    xr = x + (prev - x) * p["mu_r"]
    kk = jnp.square(jax.nn.relu(jnp.einsum("bsd,df->bsf", xk, p["wk"])))
    vv = jnp.einsum("bsf,fd->bsd", kk, p["wv"])
    rr = jax.nn.sigmoid(jnp.einsum("bsd,de->bse", xr, p["wr"]))
    return rr * vv, x[:, -1:]
