"""Single-token GQA decode attention — Pallas TPU kernel (flash-decode).

Memory-bound regime: one query token streams the whole KV cache through
VMEM once.  Grid = (B, K, n_w_blocks) with the cache-block axis innermost;
all G = H/K query heads of a kv group ride along in one (G, d) tile so the
cache is read exactly once per kv head.  Online softmax in fp32 scratch.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

DEFAULT_BW = 1024
NEG_INF = -1e30


def _kernel(q_ref, k_ref, v_ref, b_ref, o_ref, m_scr, l_scr, acc_scr, *,
            bw, nw, scale):
    iw = pl.program_id(2)

    @pl.when(iw == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    q = q_ref[0, 0].astype(jnp.float32)                 # (G, d)
    k = k_ref[0, 0].astype(jnp.float32)                 # (bw, d)
    v = v_ref[0, 0].astype(jnp.float32)
    bias = b_ref[0].astype(jnp.float32)                 # (1, bw)
    s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                            preferred_element_type=jnp.float32) * scale
    s = s + bias                                        # (G, bw)
    m_prev = m_scr[...]
    m_new = jnp.maximum(m_prev, jnp.max(s, axis=1))
    alpha = jnp.exp(m_prev - m_new)
    p = jnp.exp(s - m_new[:, None])
    l_scr[...] = l_scr[...] * alpha + jnp.sum(p, axis=1)
    acc_scr[...] = (acc_scr[...] * alpha[:, None]
                    + jax.lax.dot_general(p, v, (((1,), (0,)), ((), ())),
                                          preferred_element_type=jnp.float32))
    m_scr[...] = m_new

    @pl.when(iw == nw - 1)
    def _fini():
        l = jnp.maximum(l_scr[...], 1e-30)
        o_ref[0, 0] = (acc_scr[...] / l[:, None]).astype(o_ref.dtype)


def decode_attention_fwd(q, k, v, bias, *, bw=DEFAULT_BW, scale=None,
                         interpret=False):
    """q (B,K,G,d), k/v (B,K,W,d), bias (B,1,W) — W % bw == 0.

    The bias keeps a unit middle axis so that its (1, 1, bw) block spans the
    array's last two dims in full or in 128-lane multiples, as Mosaic
    requires for every batch size.
    """
    B, K, G, d = q.shape
    W = k.shape[2]
    assert W % bw == 0, (W, bw)
    nw = W // bw
    scale = scale or 1.0 / math.sqrt(d)
    kernel = functools.partial(_kernel, bw=bw, nw=nw, scale=scale)
    call = pl.pallas_call(
        kernel,
        name="decode_attention",
        grid=(B, K, nw),
        in_specs=[
            pl.BlockSpec((1, 1, G, d), lambda b, kk, iw: (b, kk, 0, 0)),
            pl.BlockSpec((1, 1, bw, d), lambda b, kk, iw: (b, kk, iw, 0)),
            pl.BlockSpec((1, 1, bw, d), lambda b, kk, iw: (b, kk, iw, 0)),
            pl.BlockSpec((1, 1, bw), lambda b, kk, iw: (b, 0, iw)),
        ],
        out_specs=pl.BlockSpec((1, 1, G, d), lambda b, kk, iw: (b, kk, 0, 0)),
        out_shape=jax.ShapeDtypeStruct((B, K, G, d), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((G,), jnp.float32),
            pltpu.VMEM((G,), jnp.float32),
            pltpu.VMEM((G, d), jnp.float32),
        ],
        interpret=interpret,
    )
    with jax.named_scope("kernel"):
        return call(q, k, v, bias)
