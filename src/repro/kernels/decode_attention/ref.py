"""Pure-jnp oracle for single-token GQA decode attention."""
import math

import jax
import jax.numpy as jnp
from jax import lax


def decode_attention_ref(q, k, v, cache_k, cache_v, bias, layer, slot):
    """q (B,1,H,d), k/v (B,1,K,d) the token's, cache_k/cache_v (L,B,K,d,W)
    stacked caches, bias (B,W) additive fp32 (mask), layer and slot int32
    scalars: writes the token's K/V into slot `slot` of layer `layer` and
    attends over that layer.

    Returns (out (B,1,H,d), cache_k, cache_v).
    """
    B, _, H, d = q.shape
    K = cache_k.shape[2]
    at = (layer, 0, 0, 0, slot)
    cache_k, cache_v = (lax.dynamic_update_slice(
        c, t.transpose(0, 2, 3, 1)[None].astype(c.dtype), at)
        for c, t in ((cache_k, k), (cache_v, v)))
    ck = lax.dynamic_index_in_dim(cache_k, layer, 0, keepdims=False)
    cv = lax.dynamic_index_in_dim(cache_v, layer, 0, keepdims=False)
    qg = q.reshape(B, K, H // K, d)
    s = jnp.einsum("bkgd,bkdt->bkgt", qg, ck).astype(jnp.float32)
    s = s / math.sqrt(d) + bias[:, None, None, :]
    p = jax.nn.softmax(s, axis=-1).astype(cv.dtype)
    o = jnp.einsum("bkgt,bkdt->bkgd", p, cv)
    return o.reshape(B, 1, H, d), cache_k, cache_v
