"""jit'd wrapper for the decode-attention kernel (layout + padding)."""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from repro.kernels.backend import interpret_mode
from repro.kernels.decode_attention.decode_attention import (
    DEFAULT_BW, decode_attention_fwd)


def _ceil_to(x, m):
    return (x + m - 1) // m * m


@functools.partial(jax.jit, static_argnames=("interpret",))
def decode_attention(q, k, v, bias, *, interpret=None):
    """q (B,1,H,d), k/v (B,W,K,d), bias (B,W) -> (B,1,H,d).

    interpret=None: interpreted on the CPU backend, compiled elsewhere.
    The transposes, pads and slice-back run under the scope `kv`, the
    kernel under `kernel`.
    """
    B, _, H, d = q.shape
    W, K = k.shape[1], k.shape[2]
    G = H // K
    bw = min(DEFAULT_BW, _ceil_to(W, 128))
    Wp = _ceil_to(W, bw)
    dp = _ceil_to(d, 128)
    with jax.named_scope("kv"):     # the kernel's layout, in and out
        qt = q.reshape(B, 1, K, G, d)[:, 0].transpose(0, 1, 2, 3)  # (B,K,G,d)
        qt = jnp.pad(qt, ((0, 0), (0, 0), (0, 0), (0, dp - d)))
        kt = jnp.pad(k.transpose(0, 2, 1, 3),
                     ((0, 0), (0, 0), (0, Wp - W), (0, dp - d)))
        vt = jnp.pad(v.transpose(0, 2, 1, 3),
                     ((0, 0), (0, 0), (0, Wp - W), (0, dp - d)))
        bp = jnp.pad(bias, ((0, 0), (0, Wp - W)),
                     constant_values=-1e30)[:, None, :]          # (B,1,Wp)
    o = decode_attention_fwd(qt, kt, vt, bp, bw=bw,
                             scale=1.0 / (d ** 0.5),
                             interpret=interpret_mode(interpret))
    with jax.named_scope("kv"):
        return o[..., :d].reshape(B, 1, H, d)
