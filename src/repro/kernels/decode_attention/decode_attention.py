"""Single-token GQA decode attention — Pallas TPU kernel (flash-decode).

Memory-bound regime: one query token streams the whole KV cache through
VMEM once.  The kernel works on the model's stacked cache `(L, B, K, d, Wp)`
where it lies: the ring of slots on the minor (lane) axis, the true head
size on the second-minor axis, and the layer and the token's slot as
scalar-prefetch operands.  It writes the token's K/V into its slot itself,
in place, as the one 128-lane tile that holds the slot (a write of one lane
per row from outside goes element by element), so that nothing outside the
kernel slices, transposes, pads or writes a layer's cache.
Grid = (B, n_w_blocks) with the cache-block axis innermost: a step reads a
row's block of all K kv heads, and each head's G = H/K query heads ride
along in one (G, d) tile, so the cache is read exactly once.  Operands go
to the MXU in their own dtype; the online softmax is kept in fp32 scratch.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

LANES = 128
MAX_BW = 2048           # widest ring one block spans, in slots
BLOCK_BYTES = 4 << 20   # most bytes of one cache block (all kv heads)
VMEM_BYTES = 32 << 20   # K and V blocks, double-buffered, and the rest
NEG_INF = -1e30


def _ceil_to(x, m):
    return (x + m - 1) // m * m


def cache_width(window: int) -> int:
    """Slots the cache holds for a ring of `window`: a multiple of 128 up to
    one block of `MAX_BW`, and of 512 past it, so that `block_width` finds a
    block of at least 512."""
    return _ceil_to(window, LANES if window <= MAX_BW else MAX_BW // 4)


def block_width(Wp: int, slot_bytes: int) -> int:
    """The widest multiple of 128 lanes, at most `MAX_BW`, that divides Wp
    and keeps a block of `slot_bytes` a slot within `BLOCK_BYTES`."""
    assert Wp % LANES == 0, Wp
    top = min(Wp, MAX_BW, max(LANES, BLOCK_BYTES // slot_bytes))
    return max(bw for bw in range(LANES, top + 1, LANES) if Wp % bw == 0)


def _kernel(li_ref, slot_ref, q_ref, k_ref, v_ref, kn_ref, vn_ref, b_ref,
            o_ref, ko_ref, vo_ref, m_scr, l_scr, acc_scr, *, bw, nw, scale):
    del li_ref                                          # used by index maps
    iw = pl.program_id(1)
    slot = slot_ref[0]
    heads = q_ref.shape[1]

    @pl.when(iw == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    @pl.when(iw == slot // bw)
    def _write():       # the token's K/V into its slot's 128-lane tile
        at = pl.ds(pl.multiple_of(slot % bw // LANES * LANES, LANES), LANES)
        mine = jax.lax.broadcasted_iota(jnp.int32, (1, LANES), 1) == \
            slot % LANES
        for new, cache, out in ((kn_ref, k_ref, ko_ref),
                                (vn_ref, v_ref, vo_ref)):
            for h in range(heads):
                tile = jnp.where(mine, new[0, h], cache[0, 0, h, :, at])
                cache[0, 0, h, :, at] = tile    # the block read below
                out[0, 0, h] = tile

    bias = b_ref[0].astype(jnp.float32)                 # (1, bw)
    for h in range(heads):
        v = v_ref[0, 0, h]                              # (d, bw)
        s = jax.lax.dot_general(q_ref[0, h], k_ref[0, 0, h],
                                (((1,), (0,)), ((), ())),
                                preferred_element_type=jnp.float32)
        s = s * scale + bias                            # (G, bw)
        m_prev = m_scr[h]                               # (G, 1)
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=1, keepdims=True))
        alpha = jnp.exp(m_prev - m_new)
        p = jnp.exp(s - m_new)
        l_scr[h] = l_scr[h] * alpha + jnp.sum(p, axis=1, keepdims=True)
        acc_scr[h] = acc_scr[h] * alpha + jax.lax.dot_general(
            p.astype(v.dtype), v, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32)         # (G, d)
        m_scr[h] = m_new

    @pl.when(iw == nw - 1)
    def _fini():
        for h in range(heads):
            l = jnp.maximum(l_scr[h], 1e-30)
            o_ref[0, h] = (acc_scr[h] / l).astype(o_ref.dtype)


def decode_attention_fwd(q, k_new, v_new, k, v, bias, layer, slot, *,
                         scale=None, interpret=False):
    """q (B,K,G,d), k_new/v_new (B,K,d,1) the token's K/V, k/v (L,B,K,d,Wp)
    the stacked caches, bias (B,1,Wp), layer and slot int32 scalars
    -> (out (B,K,G,d), k, v).

    Writes the token's K/V into slot `slot` of layer `layer` of the caches
    in place (k and v are aliased to the outputs; only the slot's 128-lane
    tile is written back) and attends over that layer, the token included.
    Wp is a multiple of 128.  The bias keeps a unit middle axis so that its
    (1, 1, bw) block spans the array's last two dims in full or in 128-lane
    multiples, as Mosaic requires for every batch size.
    """
    B, K, G, d = q.shape
    Wp = k.shape[-1]
    bw = block_width(Wp, K * d * k.dtype.itemsize)
    nw = Wp // bw
    scale = scale or 1.0 / math.sqrt(d)
    kernel = functools.partial(_kernel, bw=bw, nw=nw, scale=scale)
    cache_block = pl.BlockSpec((1, 1, K, d, bw),
                               lambda b, iw, li, sl: (li[0], b, 0, 0, iw))
    slot_tile = pl.BlockSpec(
        (1, 1, K, d, LANES),
        lambda b, iw, li, sl: (li[0], b, 0, 0, sl[0] // LANES))
    row = pl.BlockSpec((1, K, G, d), lambda b, iw, li, sl: (b, 0, 0, 0))
    token = pl.BlockSpec((1, K, d, 1), lambda b, iw, li, sl: (b, 0, 0, 0))
    call = pl.pallas_call(
        kernel,
        name="decode_attention",
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=(B, nw),
            in_specs=[
                row, cache_block, cache_block, token, token,
                pl.BlockSpec((1, 1, bw), lambda b, iw, li, sl: (b, 0, iw)),
            ],
            out_specs=[row, slot_tile, slot_tile],
            scratch_shapes=[
                pltpu.VMEM((K, G, 1), jnp.float32),
                pltpu.VMEM((K, G, 1), jnp.float32),
                pltpu.VMEM((K, G, d), jnp.float32),
            ]),
        out_shape=[jax.ShapeDtypeStruct((B, K, G, d), q.dtype),
                   jax.ShapeDtypeStruct(k.shape, k.dtype),
                   jax.ShapeDtypeStruct(v.shape, v.dtype)],
        input_output_aliases={3: 1, 4: 2},
        compiler_params=pltpu.CompilerParams(vmem_limit_bytes=VMEM_BYTES),
        interpret=interpret,
    )
    scalars = [jnp.reshape(x, (1,)).astype(jnp.int32) for x in (layer, slot)]
    with jax.named_scope("kernel"):
        return call(*scalars, q, k, v, k_new.astype(k.dtype),
                    v_new.astype(v.dtype), bias)
