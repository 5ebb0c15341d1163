"""Flash attention forward — Pallas TPU kernel.

TPU adaptation: block-tiled online softmax with explicit
VMEM BlockSpecs.  Grid = (B, H, n_q_blocks, n_k_blocks); the k-block axis
is innermost, so VMEM scratch accumulators (m, l, acc) persist across it
(TPU grids iterate sequentially).  GQA is handled in the k/v index_map
(kv_head = q_head * K // H) — no materialized head repetition.  Causal and
sliding-window masks are applied in-kernel; fully-masked k-blocks are
skipped with pl.when (no wasted MXU work).

Block sizes default to (128, 512): q-tile 128 rows feeds the 128x128 MXU;
k-tile 512 keeps the (bq x bk) score tile + (bk x d) k/v tiles well under
VMEM (~0.7 MB at d=128, bf16).
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

DEFAULT_BQ = 128
DEFAULT_BK = 512
NEG_INF = -1e30


def _kernel(q_ref, k_ref, v_ref, o_ref, m_scr, l_scr, acc_scr, *,
            bq, bk, nk, seq_len, causal, window, scale):
    ik = pl.program_id(3)
    iq = pl.program_id(2)

    @pl.when(ik == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    q_start = iq * bq
    k_start = ik * bk
    # block-level skip: strictly-below-diagonal or out-of-window blocks
    relevant = jnp.asarray(True)
    if causal:
        relevant = k_start <= q_start + bq - 1
    if window is not None:
        relevant = jnp.logical_and(
            relevant, k_start + bk - 1 >= q_start - (window - 1))

    @pl.when(relevant)
    def _body():
        q = q_ref[0, 0].astype(jnp.float32)            # (bq, d)
        k = k_ref[0, 0].astype(jnp.float32)            # (bk, d)
        v = v_ref[0, 0].astype(jnp.float32)
        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32) * scale
        rows = q_start + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 0)
        cols = k_start + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 1)
        ok = cols < seq_len                            # seq padding
        if causal:
            ok = jnp.logical_and(ok, rows >= cols)
        if window is not None:
            ok = jnp.logical_and(ok, rows - cols < window)
        s = jnp.where(ok, s, NEG_INF)
        m_prev = m_scr[...]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=1))
        alpha = jnp.exp(m_prev - m_new)
        p = jnp.exp(s - m_new[:, None])
        l_scr[...] = l_scr[...] * alpha + jnp.sum(p, axis=1)
        acc_scr[...] = (acc_scr[...] * alpha[:, None]
                        + jax.lax.dot_general(
                            p, v, (((1,), (0,)), ((), ())),
                            preferred_element_type=jnp.float32))
        m_scr[...] = m_new

    @pl.when(ik == nk - 1)
    def _fini():
        l = jnp.maximum(l_scr[...], 1e-30)
        o_ref[0, 0] = (acc_scr[...] / l[:, None]).astype(o_ref.dtype)


def flash_attention_fwd(q, k, v, *, causal=True, window=None,
                        bq=DEFAULT_BQ, bk=DEFAULT_BK, seq_len=None,
                        scale=None, interpret=False):
    """q (B,Sp,H,d), k/v (B,Sp,K,d); Sp must be a multiple of bq and bk.

    Returns o (B,Sp,H,d). seq_len: true (unpadded) length for key masking.
    The transposes run under the scope `kv`, the kernel under `kernel`.
    """
    B, Sp, H, d = q.shape
    K = k.shape[2]
    assert H % K == 0, (H, K)
    assert Sp % bq == 0 and Sp % bk == 0, (Sp, bq, bk)
    seq_len = seq_len or Sp
    nq, nk = Sp // bq, Sp // bk
    scale = scale or 1.0 / math.sqrt(d)

    with jax.named_scope("kv"):         # (B,S,H,d) -> (B,H,S,d) blocks
        qt = q.transpose(0, 2, 1, 3)
        kt = k.transpose(0, 2, 1, 3)
        vt = v.transpose(0, 2, 1, 3)

    kernel = functools.partial(
        _kernel, bq=bq, bk=bk, nk=nk, seq_len=seq_len, causal=causal,
        window=window, scale=scale)
    call = pl.pallas_call(
        kernel,
        name="flash_attention",
        grid=(B, H, nq, nk),
        in_specs=[
            pl.BlockSpec((1, 1, bq, d), lambda b, h, iq, ik: (b, h, iq, 0)),
            pl.BlockSpec((1, 1, bk, d),
                         lambda b, h, iq, ik: (b, h * K // H, ik, 0)),
            pl.BlockSpec((1, 1, bk, d),
                         lambda b, h, iq, ik: (b, h * K // H, ik, 0)),
        ],
        out_specs=pl.BlockSpec((1, 1, bq, d),
                               lambda b, h, iq, ik: (b, h, iq, 0)),
        out_shape=jax.ShapeDtypeStruct((B, H, Sp, d), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((bq,), jnp.float32),
            pltpu.VMEM((bq,), jnp.float32),
            pltpu.VMEM((bq, d), jnp.float32),
        ],
        interpret=interpret,
    )
    with jax.named_scope("kernel"):
        out = call(qt, kt, vt)
    with jax.named_scope("kv"):
        return out.transpose(0, 2, 1, 3)
