"""Model assembly: init / train forward / prefill / decode for all families.

Layer stacks are grouped into *periods* (the repeating structural unit —
e.g. Jamba's [7×mamba, 1×attn], the VLM's [4×self, 1×cross]) and scanned
over `n_periods = num_layers // period`, so even the 126-layer 405B model
lowers to a compact HLO.

Device work is named with `jax.named_scope`, which is metadata only (the
optimized HLO is the same without it) and shows in a profiler trace as each
op's name path: `embed`, `norm`, `attn` (inside it `qkv`, `rope`, `kv`,
`kernel` or `core`, `out`; `models/layers.py`), `mamba` (`in_proj`, `conv`,
`ssm_params`, `scan`, `out`), `mlp` (an MoE's inside it under `moe`:
`route`, `experts`, `combine`), `unembed`, and `sample` (`train/steps.py`).  The slicing of the scanned weights and
caches that `lax.scan` itself emits carries no scope: a scope inside the
body does not reach it.  The self-attention caches are not scanned: they
ride in the scan's carry, and `attn/kv` holds every op on them.
"""
from __future__ import annotations

import dataclasses
import math
from typing import Any, Optional

import jax
import jax.numpy as jnp
from jax import lax

from repro.configs.base import ModelConfig
from repro.kernels.decode_attention import cache_width
from repro.models import layers as L

Pytree = Any


# ---------------------------------------------------------------------------
# Block structure
# ---------------------------------------------------------------------------


def period_of(cfg: ModelConfig) -> int:
    if cfg.rwkv:
        return 1
    if cfg.attn_every > 1:
        return cfg.attn_every
    if cfg.cross_attn_every:
        return cfg.cross_attn_every
    if cfg.moe is not None and cfg.moe_every > 1:
        return cfg.moe_every
    return 1


def block_specs(cfg: ModelConfig) -> list[dict]:
    """One spec per position within a period."""
    P = period_of(cfg)
    specs = []
    for pos in range(P):
        if cfg.rwkv:
            specs.append({"kind": "rwkv", "ffn": "rwkv"})
            continue
        if cfg.encoder_layers:   # whisper decoder: self + cross every layer
            specs.append({"kind": "attn", "ffn": "dense", "cross": True})
            continue
        if cfg.attn_every > 1:
            kind = "attn" if pos == cfg.attn_offset else "mamba"
        elif cfg.cross_attn_every and pos == P - 1:
            kind = "xattn"
        else:
            kind = "attn"
        if cfg.moe is not None and (pos % cfg.moe_every == cfg.moe_every - 1):
            ffn = "moe"
        else:
            ffn = "dense"
        specs.append({"kind": kind, "ffn": ffn})
    return specs


# ---------------------------------------------------------------------------
# Parameter init
# ---------------------------------------------------------------------------


def _norm_init(key, d):
    return jnp.zeros((d,), jnp.float32)


def _dense(key, shape, dtype, scale=0.02):
    return (jax.random.normal(key, shape, jnp.float32) * scale).astype(dtype)


def _attn_params(key, cfg: ModelConfig, tp: int, *, cross=False):
    D, hd = cfg.d_model, cfg.head_dim_()
    H, K = cfg.num_heads, cfg.num_kv_heads
    Hp, Kp, Gp = cfg.padded_heads(tp)
    G = H // K
    dt = jnp.dtype(cfg.param_dtype)
    ks = jax.random.split(key, 6)
    # real weights, then scatter into padded/group-aligned layout
    wq = _dense(ks[0], (D, K, G, hd), dt, 0.02 / math.sqrt(2 * cfg.num_layers))
    K_eff = Kp if K >= tp else K          # K>=tp: zero-pad kv groups too
    wq_p = jnp.zeros((D, K_eff, Gp, hd), dt).at[:, :K, :G].set(wq)
    wk = _dense(ks[1], (D, K, hd), dt)
    wv = _dense(ks[2], (D, K, hd), dt)
    if K < tp:
        r = tp // K
        wk_p = jnp.repeat(wk, r, axis=1)
        wv_p = jnp.repeat(wv, r, axis=1)
    else:
        wk_p = jnp.zeros((D, Kp, hd), dt).at[:, :K].set(wk)
        wv_p = jnp.zeros((D, Kp, hd), dt).at[:, :K].set(wv)
    wo = _dense(ks[3], (K, G, hd, D), dt, 0.02 / math.sqrt(2 * cfg.num_layers))
    wo_p = jnp.zeros((Kp if K >= tp else K, Gp, hd, D), dt)
    wo_p = wo_p.at[:K, :G].set(wo) if K >= tp else wo_p.at[:, :G].set(wo)
    p = {
        "wq": wq_p.reshape(D, Hp, hd),
        "wk": wk_p, "wv": wv_p,
        "wo": wo_p.reshape(Hp, hd, D),
    }
    if cfg.qk_norm:
        p["qn"] = _norm_init(ks[4], hd)
        p["kn"] = _norm_init(ks[5], hd)
    if cross:
        p["gate"] = jnp.zeros((), jnp.float32)
    return p


def _ffn_params(key, cfg: ModelConfig, d_ff=None):
    D = cfg.d_model
    F = d_ff or cfg.d_ff
    dt = jnp.dtype(cfg.param_dtype)
    k1, k2, k3 = jax.random.split(key, 3)
    return {"w_gate": _dense(k1, (D, F), dt),
            "w_up": _dense(k2, (D, F), dt),
            "w_down": _dense(k3, (F, D), dt,
                             0.02 / math.sqrt(2 * cfg.num_layers))}


def _moe_params(key, cfg: ModelConfig):
    """The router scores all `num_experts`; the layer holds the experts of
    `held_range()`."""
    m = cfg.moe
    lo, hi = m.held_range()
    D, F, E = cfg.d_model, m.d_ff, hi - lo
    dt = jnp.dtype(cfg.param_dtype)
    ks = jax.random.split(key, 5)
    p = {"router": _dense(ks[0], (D, m.num_experts), jnp.float32),
         "w_gate": _dense(ks[1], (E, D, F), dt),
         "w_up": _dense(ks[2], (E, D, F), dt),
         "w_down": _dense(ks[3], (E, F, D), dt,
                          0.02 / math.sqrt(2 * cfg.num_layers))}
    if m.num_shared_experts:
        p["shared"] = _ffn_params(ks[4], cfg,
                                  d_ff=F * m.num_shared_experts)
    return p


def _mamba_params(key, cfg: ModelConfig):
    m = cfg.mamba
    D = cfg.d_model
    I = m.expand * D
    R = max(1, D // 16)
    N = m.d_state
    dt = jnp.dtype(cfg.param_dtype)
    ks = jax.random.split(key, 8)
    A = jnp.broadcast_to(jnp.arange(1, N + 1, dtype=jnp.float32), (I, N))
    return {
        "in_proj": _dense(ks[0], (D, 2 * I), dt),
        "conv_w": _dense(ks[1], (m.d_conv, I), dt, 0.1),
        "conv_b": jnp.zeros((I,), dt),
        "x_proj": _dense(ks[2], (I, R + 2 * N), dt),
        "dt_proj": _dense(ks[3], (R, I), dt),
        "dt_bias": jnp.full((I,), -2.0, dt),
        "A_log": jnp.log(A),
        "Dskip": jnp.ones((I,), dt),
        "dt_norm": _norm_init(ks[5], R),
        "b_norm": _norm_init(ks[6], N),
        "c_norm": _norm_init(ks[7], N),
        "out_proj": _dense(ks[4], (I, D), dt,
                           0.02 / math.sqrt(2 * cfg.num_layers)),
    }


def _rwkv_params(key, cfg: ModelConfig):
    D = cfg.d_model
    hd = cfg.head_dim_()
    H = D // hd
    r_lora = max(8, D // 64)
    dt = jnp.dtype(cfg.param_dtype)
    ks = jax.random.split(key, 12)
    tm = {f"mu_{n}": jnp.full((D,), 0.5, dt) for n in "rkvgw"}
    tm.update({
        "w0": jnp.full((D,), -1.5, jnp.float32),
        "w_lora": {"a": _dense(ks[0], (D, r_lora), jnp.float32),
                   "b": _dense(ks[1], (r_lora, D), jnp.float32)},
        "wr": _dense(ks[2], (D, H, hd), dt),
        "wk": _dense(ks[3], (D, H, hd), dt),
        "wv": _dense(ks[4], (D, H, hd), dt),
        "wg": _dense(ks[5], (D, H, hd), dt),
        "wo": _dense(ks[6], (H, hd, D), dt,
                     0.02 / math.sqrt(2 * cfg.num_layers)),
        "u": _dense(ks[7], (H, hd), jnp.float32),
        "ln_x": jnp.ones((D,), jnp.float32),
    })
    cm = {"mu_k": jnp.full((D,), 0.5, dt), "mu_r": jnp.full((D,), 0.5, dt),
          "wk": _dense(ks[8], (D, cfg.d_ff), dt),
          "wv": _dense(ks[9], (cfg.d_ff, D), dt),
          "wr": _dense(ks[10], (D, D), dt)}
    return {"time_mix": tm, "channel_mix": cm}


def _block_params(key, cfg: ModelConfig, spec: dict, tp: int):
    ks = jax.random.split(key, 6)
    p: dict = {"ln1": _norm_init(ks[0], cfg.d_model),
               "ln2": _norm_init(ks[1], cfg.d_model)}
    if spec["kind"] == "attn":
        p["attn"] = _attn_params(ks[2], cfg, tp)
        if spec.get("cross"):
            p["ln_x"] = _norm_init(ks[4], cfg.d_model)
            p["xattn"] = _attn_params(ks[5], cfg, tp, cross=False)
    elif spec["kind"] == "xattn":
        p["attn"] = _attn_params(ks[2], cfg, tp, cross=True)
    elif spec["kind"] == "mamba":
        p["mamba"] = _mamba_params(ks[2], cfg)
    elif spec["kind"] == "rwkv":
        p.update(_rwkv_params(ks[2], cfg))
        return p
    if spec["ffn"] == "moe":
        p["moe"] = _moe_params(ks[3], cfg)
    else:
        p["ffn"] = _ffn_params(ks[3], cfg)
    return p


def init_params(key, cfg: ModelConfig, tp: int = 1) -> Pytree:
    P = period_of(cfg)
    specs = block_specs(cfg)
    n_periods = cfg.num_layers // P
    assert n_periods * P == cfg.num_layers, (cfg.name, cfg.num_layers, P)
    Vp = cfg.padded_vocab()
    dt = jnp.dtype(cfg.param_dtype)
    keys = jax.random.split(key, 8)
    embed = jnp.zeros((Vp, cfg.d_model), dt).at[:cfg.vocab_size].set(
        _dense(keys[0], (cfg.vocab_size, cfg.d_model), dt))
    params: dict = {"embed": embed, "final_norm": _norm_init(keys[1],
                                                             cfg.d_model)}
    if not cfg.tie_embeddings:
        params["lm_head"] = jnp.zeros((Vp, cfg.d_model), dt).at[
            :cfg.vocab_size].set(
            _dense(keys[2], (cfg.vocab_size, cfg.d_model), dt))

    def stack_init(spec, key):
        lk = jax.random.split(key, n_periods)
        return jax.vmap(lambda k: _block_params(k, cfg, spec, tp))(lk)

    pk = jax.random.split(keys[3], P)
    params["layers"] = [stack_init(s, pk[i]) for i, s in enumerate(specs)]

    if cfg.encoder_layers:      # whisper encoder stack (self-attn, dense ffn)
        ek = jax.random.split(keys[4], cfg.encoder_layers)
        enc_spec = {"kind": "attn", "ffn": "dense"}
        params["encoder"] = {
            "layers": jax.vmap(
                lambda k: _block_params(k, cfg, enc_spec, tp))(ek),
            "final_norm": _norm_init(keys[5], cfg.d_model),
        }
    return params


# ---------------------------------------------------------------------------
# Forward passes
# ---------------------------------------------------------------------------


def _apply_block(p, spec, x, cfg, rules, *, cache=None, layer=None,
                 cache_index=None, mode="train", extra=None,
                 use_pallas=False):
    """One block. Returns (x, new_cache, aux).

    `cache` holds this layer's slice of the scanned caches and the whole
    stack of the self-attention caches (`kv`), of which layer `layer` is
    this block's (see `_carried`)."""
    aux = jnp.zeros((), jnp.float32)
    new_cache = cache
    kind = spec["kind"]
    if kind == "rwkv":
        h = L.rms_norm(x, p["ln1"], cfg.norm_eps)
        st = cache.get("tm") if cache else None
        o, tm_state = L.rwkv_time_mix(p["time_mix"], h, cfg, rules, state=st,
                                      use_pallas=use_pallas)
        x = x + o
        h = L.rms_norm(x, p["ln2"], cfg.norm_eps)
        st = cache.get("cm") if cache else None
        o, cm_state = L.rwkv_channel_mix(p["channel_mix"], h, state=st)
        x = x + o
        if cache is not None:
            new_cache = {"tm": tm_state, "cm": cm_state}
        return x, new_cache, aux

    with jax.named_scope("norm"):
        h = L.rms_norm(x, p["ln1"], cfg.norm_eps)
    if kind == "attn":
        use_rope = cfg.use_rope
        with jax.named_scope("attn"):
            if mode == "decode":
                o, kvc = L.decode_attention(p["attn"], h, cfg, rules,
                                            cache=cache["kv"], layer=layer,
                                            cache_index=cache_index,
                                            use_rope=use_rope,
                                            use_pallas=use_pallas)
                new_cache = {**cache, "kv": kvc}
            else:
                o, kvc = L.self_attention(
                    p["attn"], h, cfg, rules,
                    causal=spec.get("causal", cfg.causal), use_rope=use_rope,
                    kv_cache=cache["kv"] if cache is not None else None,
                    layer=layer, use_pallas=use_pallas)
                if cache is not None:
                    new_cache = {**cache, "kv": kvc}
        if spec.get("cross"):            # whisper decoder cross-attn sublayer
            x = x + o
            h = L.rms_norm(x, p["ln_x"], cfg.norm_eps)
            if mode == "decode":
                o, xc = L.cross_attention(p["xattn"], h, cfg, rules,
                                          cache=cache["xkv"])
                new_cache = {**new_cache, "xkv": xc}
            else:
                o, xc = L.cross_attention(p["xattn"], h, cfg, rules,
                                          kv=extra["cross_source"])
                if cache is not None:
                    new_cache = {**new_cache, "xkv": xc}
    elif kind == "xattn":
        if mode == "decode":
            o, xc = L.cross_attention(p["attn"], h, cfg, rules,
                                      cache=cache["xkv"])
            new_cache = {**cache, "xkv": xc}
        else:
            o, xc = L.cross_attention(p["attn"], h, cfg, rules,
                                      kv=extra["cross_source"])
            if cache is not None:
                new_cache = {**cache, "xkv": xc}
    elif kind == "mamba":
        st = cache.get("mamba") if cache is not None else None
        with jax.named_scope("mamba"):
            o, mst = L.mamba(p["mamba"], h, cfg, rules, state=st,
                             use_pallas=use_pallas)
        if cache is not None:
            new_cache = {**cache, "mamba": mst}
    x = x + o
    with jax.named_scope("norm"):
        h = L.rms_norm(x, p["ln2"], cfg.norm_eps)
    with jax.named_scope("mlp"):
        if spec["ffn"] == "moe":
            o, aux, routed = L.moe_ffn(p["moe"], h, cfg.moe, rules)
            if cache is not None:
                new_cache = {**new_cache, "moe": _counted(
                    cache["moe"], routed, mode)}
        else:
            o = L.swiglu(p["ffn"], h, rules)
    return x + o, new_cache, aux


def _counted(counts, routed, mode):
    """The MoE counter (E_held, 3) int32 after one call that routed
    `routed` (E_held,) tokens to each held expert: its columns are tokens
    routed in prefill, tokens routed in decode, and decode steps in which
    the expert got a token.  Prefill sets it anew; each decode step adds."""
    zero = jnp.zeros_like(routed)
    if mode == "decode":
        return counts + jnp.stack(
            [zero, routed, (routed > 0).astype(routed.dtype)], -1)
    return jnp.stack([routed, zero, zero], -1)


def _sinusoid(T, D):
    pos = jnp.arange(T)[:, None].astype(jnp.float32)
    i = jnp.arange(D // 2)[None, :].astype(jnp.float32)
    ang = pos / jnp.power(10000.0, 2 * i / D)
    return jnp.concatenate([jnp.sin(ang), jnp.cos(ang)], -1)


def _run_encoder(params, cfg, frames, rules, use_pallas=False):
    x = frames + _sinusoid(frames.shape[1], cfg.d_model).astype(frames.dtype)
    spec = {"kind": "attn", "ffn": "dense", "causal": False}

    def body(x, p):
        x, _, _ = _apply_block(p, spec, x, cfg, rules, mode="train",
                               use_pallas=use_pallas)
        return x, None
    x, _ = lax.scan(body, x, params["encoder"]["layers"])
    return L.rms_norm(x, params["encoder"]["final_norm"], cfg.norm_eps)


def _embed(params, cfg, tokens, rules):
    with jax.named_scope("embed"):
        x = jnp.take(params["embed"], tokens, axis=0)
        if rules is not None:
            x = rules.cs(x, "act_bsd")
        return x


def _unembed(params, cfg, x, rules):
    with jax.named_scope("unembed"):
        head = params.get("lm_head", params["embed"])
        logits = jnp.einsum("bsd,vd->bsv", x, head)
        if rules is not None:
            logits = rules.cs(logits, "logits_bsv")
        return logits


def _prepare_extra(params, cfg, extra, rules, use_pallas=False):
    """Resolve the cross-attention source (stub frontends)."""
    if cfg.encoder_layers:
        enc = _run_encoder(params, cfg, extra["audio_frames"], rules,
                           use_pallas)
        return {"cross_source": enc}
    if cfg.cross_attn_every:
        return {"cross_source": extra["image_embeds"]}
    return {}


def forward(params, cfg: ModelConfig, tokens, *, extra=None, rules=None,
            caches=None, use_pallas=False, remat=True):
    """Full-sequence forward (train / prefill when caches given).

    Returns (logits, aux_loss, new_caches).
    """
    extra = _prepare_extra(params, cfg, extra or {}, rules, use_pallas)
    specs = block_specs(cfg)
    x = _embed(params, cfg, tokens, rules)
    if cfg.encoder_layers:                      # whisper decoder abs pos
        x = x + _sinusoid(x.shape[1], cfg.d_model).astype(x.dtype)

    kv, scanned = _carried(caches, specs)

    def period_body(carry, xs):
        x, aux, kv, li = carry
        pp, cc = xs
        kv, new_cc = list(kv), []
        for i, spec in enumerate(specs):
            cache = None if cc is None else {**cc[i], **kv[i]}
            x, nc, a = _apply_block(pp[i], spec, x, cfg, rules, cache=cache,
                                    layer=li, mode="train", extra=extra,
                                    use_pallas=use_pallas)
            if nc is not None:
                kv[i], nc = _split(nc)
            new_cc.append(nc)
            aux = aux + a
        return (x, aux, kv, li + 1), new_cc

    body = period_body
    if remat:
        body = jax.checkpoint(
            period_body,
            policy=jax.checkpoint_policies.dots_with_no_batch_dims_saveable)
    (x, aux, kv, _), new_scanned = lax.scan(
        body, (x, jnp.zeros((), jnp.float32), kv, jnp.zeros((), jnp.int32)),
        (params["layers"], scanned))
    with jax.named_scope("norm"):
        x = L.rms_norm(x, params["final_norm"], cfg.norm_eps)
    logits = _unembed(params, cfg, x, rules)
    out_caches = None
    if caches is not None:
        out_caches = dict(caches)
        out_caches["layers"] = _joined(kv, new_scanned)
        out_caches["index"] = caches["index"] + tokens.shape[1]
    return logits, aux, out_caches


def decode_step(params, cfg: ModelConfig, token, caches, *, rules=None,
                use_pallas=False):
    """One-token decode. token (B,1) int32. Returns (logits, new_caches).

    The self-attention caches ride in the layer scan's carry: each layer
    writes its token's slot in place and reads its layer of the stack where
    it lies, so no layer's cache is sliced, copied or written back.
    """
    specs = block_specs(cfg)
    index = caches["index"]
    x = _embed(params, cfg, token, rules)
    if cfg.encoder_layers:
        D = cfg.d_model
        pos = index.astype(jnp.float32)
        i = jnp.arange(D // 2).astype(jnp.float32)
        ang = pos / jnp.power(10000.0, 2 * i / D)
        pe = jnp.concatenate([jnp.sin(ang), jnp.cos(ang)], -1)
        x = x + pe.astype(x.dtype)
    kv, scanned = _carried(caches, specs)

    def period_body(carry, xs):
        x, kv, li = carry
        pp, cc = xs
        kv, new_cc = list(kv), []
        for i, spec in enumerate(specs):
            x, nc, _ = _apply_block(pp[i], spec, x, cfg, rules,
                                    cache={**cc[i], **kv[i]}, layer=li,
                                    cache_index=index, mode="decode",
                                    use_pallas=use_pallas)
            kv[i], nc = _split(nc)
            new_cc.append(nc)
        return (x, kv, li + 1), new_cc

    (x, kv, _), new_scanned = lax.scan(
        period_body, (x, kv, jnp.zeros((), jnp.int32)),
        (params["layers"], scanned))
    with jax.named_scope("norm"):
        x = L.rms_norm(x, params["final_norm"], cfg.norm_eps)
    logits = _unembed(params, cfg, x, rules)
    new_caches = dict(caches)
    new_caches["layers"] = _joined(kv, new_scanned)
    new_caches["index"] = index + 1
    return logits, new_caches


def _split(c: dict) -> tuple:
    """One position's caches -> (the self-attention K/V, stacked over the
    layers, which the layer scan carries and updates in place; the rest,
    which it slices per layer: recurrent states and the read-only
    cross-attention K/V, all small)."""
    return ({n: t for n, t in c.items() if n == "kv"},
            {n: t for n, t in c.items() if n != "kv"})


def _carried(caches, specs) -> tuple:
    """`_split` of every position of a period: (carried, scanned), the
    latter None without caches."""
    if caches is None:
        return [{} for _ in specs], None
    kv, rest = zip(*map(_split, caches["layers"]))
    return list(kv), list(rest)


def _joined(kv, scanned) -> list:
    return [{**s, **k} for k, s in zip(kv, scanned)]


# ---------------------------------------------------------------------------
# Cache init
# ---------------------------------------------------------------------------


def init_caches(cfg: ModelConfig, batch: int, max_len: int, tp: int = 1,
                dtype=jnp.bfloat16, cross_len: Optional[int] = None) -> Pytree:
    """Zeroed caches for `batch` rows of up to `max_len` positions.

    A Mamba layer keeps its conv inputs and its ssm state; an MoE layer a
    counter of the tokens routed to each expert it holds (`_counted`).
    A self-attention cache `kv` is a ring of min(max_len, window) slots,
    held as (n_periods, batch, Kp, hd, Wp): the slots on the minor axis,
    padded to the decode kernel's `cache_width`; slots past the ring are
    never written and always masked.  An index back at 0 starts a new
    sequence: the slots it has not written yet are masked too.
    """
    specs = block_specs(cfg)
    P = period_of(cfg)
    n_periods = cfg.num_layers // P
    hd = cfg.head_dim_()
    _, Kp, _ = cfg.padded_heads(tp)
    Wp = cache_width(min(max_len, cfg.sliding_window or max_len))

    def one(spec):
        c: dict = {}
        if spec["kind"] == "attn":      # the decode kernel's layout
            c["kv"] = {"k": jnp.zeros((n_periods, batch, Kp, hd, Wp), dtype),
                       "v": jnp.zeros((n_periods, batch, Kp, hd, Wp), dtype)}
            if spec.get("cross"):
                T = cross_len or cfg.num_audio_frames
                c["xkv"] = {
                    "k": jnp.zeros((n_periods, batch, T, Kp, hd), dtype),
                    "v": jnp.zeros((n_periods, batch, T, Kp, hd), dtype)}
        elif spec["kind"] == "xattn":
            T = cross_len or cfg.num_image_tokens or cfg.num_audio_frames
            c["xkv"] = {"k": jnp.zeros((n_periods, batch, T, Kp, hd), dtype),
                        "v": jnp.zeros((n_periods, batch, T, Kp, hd), dtype)}
        elif spec["kind"] == "mamba":
            m = cfg.mamba
            I = m.expand * cfg.d_model
            c["mamba"] = {
                "conv": jnp.zeros((n_periods, batch, m.d_conv - 1, I), dtype),
                "ssm": jnp.zeros((n_periods, batch, I, m.d_state),
                                 jnp.float32)}
        elif spec["kind"] == "rwkv":
            H = cfg.d_model // hd
            c = {"tm": {"shift": jnp.zeros((n_periods, batch, 1, cfg.d_model),
                                           dtype),
                        "wkv": jnp.zeros((n_periods, batch, H, hd, hd),
                                         jnp.float32)},
                 "cm": jnp.zeros((n_periods, batch, 1, cfg.d_model), dtype)}
        if spec.get("ffn") == "moe":    # the routing counter (`_counted`)
            lo, hi = cfg.moe.held_range()
            c["moe"] = jnp.zeros((n_periods, hi - lo, 3), jnp.int32)
        return c

    return {"index": jnp.zeros((), jnp.int32),
            "layers": [one(s) for s in specs]}
