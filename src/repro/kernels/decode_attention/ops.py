"""jit'd wrapper for the decode-attention kernel."""
from __future__ import annotations

import functools

import jax

from repro.kernels.backend import interpret_mode
from repro.kernels.decode_attention.decode_attention import \
    decode_attention_fwd


@functools.partial(jax.jit, static_argnames=("interpret",))
def decode_attention(q, k, v, cache_k, cache_v, bias, layer, slot, *,
                     interpret=None):
    """q (B,1,H,d), k/v (B,1,K,d) the token's, cache_k/cache_v
    (L,B,K,d,Wp), bias (B,Wp), layer and slot int32 scalars
    -> (out (B,1,H,d), cache_k, cache_v).

    Writes the token's K/V into slot `slot` of layer `layer` of the caches,
    in place, and attends over that layer.  Only q, the token's K/V and the
    bias, which are small, change shape here.
    interpret=None: interpreted on the CPU backend, compiled elsewhere.
    """
    B, _, H, d = q.shape
    K = k.shape[2]
    o, cache_k, cache_v = decode_attention_fwd(
        q.reshape(B, K, H // K, d), k.reshape(B, K, d, 1),
        v.reshape(B, K, d, 1), cache_k, cache_v, bias[:, None, :], layer,
        slot, scale=1.0 / (d ** 0.5), interpret=interpret_mode(interpret))
    return o.reshape(B, 1, H, d), cache_k, cache_v
