"""Flash attention forward — Pallas TPU kernel.

Block-tiled online softmax with explicit VMEM BlockSpecs.
Grid = (B, K, n_q_blocks, n_k_blocks): a step takes one kv head's (bk, d) K
and V tiles and the q tile of all G = H/K query heads that share them,
(G, bq, d), multiplied as one (G*bq, d) matrix.  So each K/V tile is fetched
once per (kv head, q tile), not once per query head, and the MXU's rows grow
G-fold.  The k-block axis is innermost, so the VMEM scratch (m, l, acc)
persists across it (TPU grids iterate sequentially); m and l are
lane-replicated (rows, 128) float32, so a row statistic meets the
(rows, bk) scores and the (rows, d) accumulator without a relayout.

Causal and sliding-window skip: the k tiles of a q tile outside `_k_span`
do no work, and the K/V index_map clamps the k-block index into that span,
so a skipped step names a block the kernel already holds and no DMA runs
for it.  Tiles wholly past `seq_len` are never fetched, nor computed for.
The k tiles go right to left, so a q tile's skipped steps come first and
the next q tile's blocks are fetched behind a working step.  The element
mask (iota, compare, select) is built only on tiles that cross the
diagonal, the window edge or the padded tail.

Operands go to the MXU in their own dtype (bf16 in serving) with float32
accumulation; the softmax state stays float32, and p is cast to v's dtype
for the PV product, as the reference does.  float32 inputs stay float32.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

LANES = 128
VMEM_BYTES = 32 << 20   # blocks, scratch and the (rows, bk) float32 scores
NEG_INF = -1e30


def _k_span(iq, *, bq, bk, nk, seq_len, causal, window):
    """First and last k tile a q tile attends (int32 scalars); the last is
    never a tile wholly past `seq_len`."""
    q_start = iq * bq
    last = jnp.int32(min(nk, pl.cdiv(seq_len, bk)) - 1)
    if causal:
        last = jnp.minimum(last, jax.lax.div(q_start + bq - 1, bk))
    first = jnp.int32(0)
    if window is not None:
        first = jnp.minimum(
            jax.lax.div(jnp.maximum(q_start - (window - 1), 0), bk), last)
    return first, last


def _lanes(x, n):
    """A lane-replicated (rows, 128) statistic as (rows, n)."""
    if n <= LANES:
        return x[:, :n]
    return jnp.tile(x, (1, pl.cdiv(n, LANES)))[:, :n]


def _kernel(q_ref, k_ref, v_ref, o_ref, m_scr, l_scr, acc_scr, *,
            bq, bk, nk, seq_len, causal, window, scale):
    iq, step_k = pl.program_id(2), pl.program_id(3)
    ik = nk - 1 - step_k
    g, d = q_ref.shape[1], q_ref.shape[3]
    rows = g * bq

    @pl.when(step_k == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    q_start, k_start = iq * bq, ik * bk
    first, last = _k_span(iq, bq=bq, bk=bk, nk=nk, seq_len=seq_len,
                          causal=causal, window=window)
    edge = None                          # the tile needs the element mask
    if causal:                           # some key right of some query
        edge = k_start + bk - 1 > q_start
    elif seq_len % bk:                   # the padded tail
        edge = k_start + bk > seq_len
    if window is not None:               # some key left of the window
        far = q_start + bq - 1 - k_start >= window
        edge = far if edge is None else edge | far

    def step(masked):
        q = q_ref[0].reshape(rows, d)
        k, v = k_ref[0, 0], v_ref[0, 0]
        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32) * scale
        if masked:
            qi = q_start + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 0)
            ki = k_start + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 1)
            # causal: a valid query never reaches the padded tail
            ok = qi >= ki if causal else ki < seq_len
            if window is not None:
                ok = jnp.logical_and(ok, qi - ki < window)
            s = jnp.where(ok, s.reshape(g, bq, bk), NEG_INF).reshape(rows, bk)
        m_prev = m_scr[...]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=1, keepdims=True))
        p = jnp.exp(s - _lanes(m_new, bk))
        alpha = jnp.exp(m_prev - m_new)
        l_scr[...] = alpha * l_scr[...] + jnp.sum(p, axis=1, keepdims=True)
        m_scr[...] = m_new
        acc_scr[...] = acc_scr[...] * _lanes(alpha, d) + jax.lax.dot_general(
            p.astype(v.dtype), v, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)

    run = (ik >= first) & (ik <= last)
    if nk * bk - seq_len >= bq:          # q tiles wholly in the padding
        run = run & (q_start < seq_len)
    if edge is None:
        pl.when(run)(lambda: step(False))
    else:
        pl.when(run & edge)(lambda: step(True))
        pl.when(run & ~edge)(lambda: step(False))

    @pl.when(step_k == nk - 1)
    def _fini():
        l = jnp.maximum(_lanes(l_scr[...], d), 1e-30)
        o_ref[0] = (acc_scr[...] / l).reshape(g, bq, d).astype(o_ref.dtype)


def flash_attention_fwd(q, k, v, *, bq, bk, causal=True, window=None,
                        seq_len=None, scale=None, interpret=False):
    """q (B,Sp,H,d), k/v (B,Sp,K,d); Sp must be a multiple of bq and bk.

    Returns o (B,Sp,H,d). seq_len: true (unpadded) length for key masking.
    d is the block's last dim whole: a multiple of 128 or any size Mosaic
    lays out.  The transposes run under the scope `kv`, the kernel under
    `kernel`.
    """
    B, Sp, H, d = q.shape
    K = k.shape[2]
    assert H % K == 0, (H, K)
    assert Sp % bq == 0 and Sp % bk == 0, (Sp, bq, bk)
    g = H // K
    seq_len = seq_len or Sp
    nq, nk = Sp // bq, Sp // bk
    scale = scale or 1.0 / math.sqrt(d)
    if window is not None and window >= Sp:
        window = None                    # no pair is that far apart

    with jax.named_scope("kv"):         # (B,S,H,d) -> (B,H,S,d) blocks
        qt = q.transpose(0, 2, 1, 3)
        kt = k.transpose(0, 2, 1, 3)
        vt = v.transpose(0, 2, 1, 3)

    span = functools.partial(_k_span, bq=bq, bk=bk, nk=nk, seq_len=seq_len,
                             causal=causal, window=window)

    def kv_block(b, h, iq, step_k):
        first, last = span(iq)
        return b, h, jnp.clip(nk - 1 - step_k, first, last), 0

    # heads are grouped by kv head, so block h of G heads is kv head h's
    q_block = pl.BlockSpec((1, g, bq, d), lambda b, h, iq, _: (b, h, iq, 0))
    kernel = functools.partial(
        _kernel, bq=bq, bk=bk, nk=nk, seq_len=seq_len, causal=causal,
        window=window, scale=scale)
    call = pl.pallas_call(
        kernel,
        name="flash_attention",
        grid=(B, K, nq, nk),
        in_specs=[q_block, pl.BlockSpec((1, 1, bk, d), kv_block),
                  pl.BlockSpec((1, 1, bk, d), kv_block)],
        out_specs=q_block,
        out_shape=jax.ShapeDtypeStruct((B, H, Sp, d), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((g * bq, LANES), jnp.float32),
            pltpu.VMEM((g * bq, LANES), jnp.float32),
            pltpu.VMEM((g * bq, d), jnp.float32),
        ],
        compiler_params=pltpu.CompilerParams(vmem_limit_bytes=VMEM_BYTES),
        interpret=interpret,
    )
    with jax.named_scope("kernel"):
        out = call(qt, kt, vt)
    with jax.named_scope("kv"):
        return out.transpose(0, 2, 1, 3)
