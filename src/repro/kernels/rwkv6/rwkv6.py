"""RWKV6 WKV chunked recurrence — Pallas TPU kernel.

TPU adaptation of the data-dependent-decay linear-attention scan: the
per-token recurrence (useless for the MXU) is re-blocked into a chunked
form where each chunk of c tokens does three (c x c x d)/(c x d x d)
einsum-shaped contractions — MXU-shaped work — plus a rank-c state update.
Grid = (B, H, n_chunks), chunk axis innermost; the (d, d) fp32 state lives
in VMEM scratch across chunk iterations.

All decay exponents are differences of a running cumulative sum and are
<= 0 by construction (w in (0,1]), so the chunked form needs no rescaling
tricks to be overflow-free.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

DEFAULT_CHUNK = 64


def _kernel(r_ref, k_ref, v_ref, lw_ref, u_ref, o_ref, sfin_ref, s_scr, *,
            c, nc):
    ic = pl.program_id(2)

    @pl.when(ic == 0)
    def _init():
        s_scr[...] = jnp.zeros_like(s_scr)

    r = r_ref[0, 0].astype(jnp.float32)                 # (c, d)
    k = k_ref[0, 0].astype(jnp.float32)
    v = v_ref[0, 0].astype(jnp.float32)
    lw = lw_ref[0, 0].astype(jnp.float32)
    u = u_ref[0].astype(jnp.float32)                    # (d,)
    S0 = s_scr[...]                                     # (d, d)

    clw = jnp.cumsum(lw, axis=0)                        # (c, d)
    clw_prev = clw - lw
    # intra-chunk: P[t,i,d] = exp(clw_prev[t,d] - clw[i,d]) for i < t
    diff = clw_prev[:, None, :] - clw[None, :, :]       # (c, c, d)
    tri = (jax.lax.broadcasted_iota(jnp.int32, (c, c), 0)
           > jax.lax.broadcasted_iota(jnp.int32, (c, c), 1))
    P = jnp.where(tri[:, :, None], jnp.exp(diff), 0.0)
    att = jnp.einsum("td,tid,id->ti", r, P, k)
    out = jnp.einsum("ti,ie->te", att, v,
                     preferred_element_type=jnp.float32)
    # diagonal bonus: (r_t . (u * k_t)) v_t
    out = out + jnp.sum(r * u[None, :] * k, axis=1)[:, None] * v
    # inter-chunk: r~_t = r_t * exp(clw_prev[t])
    out = out + jax.lax.dot_general((r * jnp.exp(clw_prev)), S0,
                                    (((1,), (0,)), ((), ())),
                                    preferred_element_type=jnp.float32)
    o_ref[0, 0] = out.astype(o_ref.dtype)
    # state update: S = exp(clw[-1]) S0 + sum_i exp(clw[-1]-clw[i]) k_i v_i^T
    wtot = clw[-1:, :]                                  # (1, d)
    Kdec = k * jnp.exp(wtot - clw)                      # (c, d)
    s_scr[...] = (jnp.exp(wtot)[0][:, None] * S0
                  + jax.lax.dot_general(Kdec, v, (((0,), (0,)), ((), ())),
                                        preferred_element_type=jnp.float32))

    @pl.when(ic == nc - 1)
    def _fini():
        sfin_ref[0, 0] = s_scr[...]


def wkv6_fwd(r, k, v, logw, u, *, chunk=DEFAULT_CHUNK, interpret=False):
    """r,k,v,logw (B,H,S,d), u (H,d). S % chunk == 0.

    Returns (o (B,H,S,d), S_final (B,H,d,d)).  Initial state is zero
    (training path); decode uses the single-step jnp form.
    """
    B, H, S, d = r.shape
    assert S % chunk == 0, (S, chunk)
    nc = S // chunk
    kernel = functools.partial(_kernel, c=chunk, nc=nc)
    call = pl.pallas_call(
        kernel,
        name="wkv6",
        grid=(B, H, nc),
        in_specs=[
            pl.BlockSpec((1, 1, chunk, d), lambda b, h, ic: (b, h, ic, 0)),
            pl.BlockSpec((1, 1, chunk, d), lambda b, h, ic: (b, h, ic, 0)),
            pl.BlockSpec((1, 1, chunk, d), lambda b, h, ic: (b, h, ic, 0)),
            pl.BlockSpec((1, 1, chunk, d), lambda b, h, ic: (b, h, ic, 0)),
            pl.BlockSpec((1, d), lambda b, h, ic: (h, 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, 1, chunk, d), lambda b, h, ic: (b, h, ic, 0)),
            pl.BlockSpec((1, 1, d, d), lambda b, h, ic: (b, h, 0, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((B, H, S, d), r.dtype),
            jax.ShapeDtypeStruct((B, H, d, d), jnp.float32),
        ],
        scratch_shapes=[pltpu.VMEM((d, d), jnp.float32)],
        interpret=interpret,
    )
    with jax.named_scope("kernel"):
        o, sfin = call(r, k, v, logw, u)
    return o, sfin
