"""Mamba-1 selective scan — Pallas TPU kernel.

    h_t = exp(dt_t * A) * h_{t-1} + (dt_t * x_t) * B_t
    y_t = C_t . h_t

The recurrence runs token by token with the state kept on chip: nothing of
size (B, S, I, N) is formed, and dt, x and y cross HBM once.
Grid = (B, n_i_blocks, n_s_blocks), the sequence axis innermost and
sequential: the (N, bI) float32 state lives in VMEM scratch across it.  The
state has its channels on the lanes and its N on the sublanes, so that dt_t
and x_t are lane rows, read `G` tokens at a time, and B_t and C_t are
sublane columns of (N, 128) blocks, rotated so that a group's G columns
come first.  All arithmetic is float32.

A sequence that is not a multiple of the block ends in a partial block: its
rows past the end are taken as dt = 0 and x = 0, which leave the state
exactly as it was (exp(0) = 1, the input term 0); their y is never written.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

DEFAULT_BS = 128        # tokens per grid step: the lanes of a B/C block
DEFAULT_BI = 1024       # channels per grid step: the lanes of the state
G = 16                  # tokens read from dt and x at a time (a bf16 tile)


def lanes(bs: int) -> int:
    """The width of a B/C block for `bs` tokens: whole 128-lane tiles."""
    return -(-bs // 128) * 128


def _kernel(dt_ref, x_ref, a_ref, b_ref, c_ref, h0_ref, y_ref, h_ref,
            h_scr, *, bs, seq_len, ns):
    f32 = jnp.float32
    s = pl.program_id(2)

    @pl.when(s == 0)
    def _init():
        h_scr[...] = h0_ref[0]

    a = a_ref[...]                                      # (N, bI)
    bm = b_ref[0]                                       # (N, bl)
    cm = c_ref[0]
    bl = bm.shape[1]

    def group(k, h):
        t0 = pl.multiple_of(k * G, G)
        dt = dt_ref[0, pl.ds(t0, G), :].astype(f32)     # (G, bI)
        x = x_ref[0, pl.ds(t0, G), :].astype(f32)
        if seq_len % bs:                                # a partial last block
            t = s * bs + t0 + jax.lax.broadcasted_iota(jnp.int32, dt.shape, 0)
            dt = jnp.where(t < seq_len, dt, 0.0)
            x = jnp.where(t < seq_len, x, 0.0)
        u = dt * x
        # the group's B and C columns at lanes 0..G-1
        bg = pltpu.roll(bm, (bl - t0) % bl, 1) if bs > G else bm
        cg = pltpu.roll(cm, (bl - t0) % bl, 1) if bs > G else cm
        ys = []
        for j in range(G):
            h = (jnp.exp(dt[j:j + 1] * a) * h
                 + u[j:j + 1] * bg[:, j:j + 1])
            ys.append(jnp.sum(h * cg[:, j:j + 1], axis=0, keepdims=True))
        y_ref[0, pl.ds(t0, G), :] = jnp.concatenate(ys).astype(y_ref.dtype)
        return h

    h = jax.lax.fori_loop(0, bs // G, group, h_scr[...])
    h_scr[...] = h

    @pl.when(s == ns - 1)
    def _fini():
        h_ref[0] = h


def selective_scan_fwd(dt, x, a, b, c, h0, *, bs=DEFAULT_BS, bi=DEFAULT_BI,
                       interpret=False):
    """dt, x (B, S, I) any float dtype; a (N, I) f32; b, c (B, N, Sb) f32,
    zeros past S, Sb a whole number of blocks of `lanes(bs)`; h0 (B, N, I)
    f32.  bs is a multiple of 128, or a multiple of G where S is shorter.

    Returns y (B, S, I) in x's dtype and the final state (B, N, I) f32.
    """
    Bsz, S, I = x.shape
    N = a.shape[0]
    bi = min(bi, I)
    ns = pl.cdiv(S, bs)
    bl = lanes(bs)
    assert b.shape == c.shape == (Bsz, N, ns * bl), (b.shape, ns * bl)
    kernel = functools.partial(_kernel, bs=bs, seq_len=S, ns=ns)
    rows = pl.BlockSpec((1, bs, bi), lambda r, i, s: (r, s, i))
    cols = pl.BlockSpec((1, N, bl), lambda r, i, s: (r, 0, s))
    state = pl.BlockSpec((1, N, bi), lambda r, i, s: (r, 0, i))
    call = pl.pallas_call(
        kernel,
        name="selective_scan",
        grid=(Bsz, pl.cdiv(I, bi), ns),
        in_specs=[rows, rows,
                  pl.BlockSpec((N, bi), lambda r, i, s: (0, i)),
                  cols, cols, state],
        out_specs=[rows, state],
        out_shape=[jax.ShapeDtypeStruct((Bsz, S, I), x.dtype),
                   jax.ShapeDtypeStruct((Bsz, N, I), jnp.float32)],
        scratch_shapes=[pltpu.VMEM((N, bi), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret,
    )
    return call(dt, x, a, b, c, h0)
