"""jit'd wrapper + custom_vjp for the WKV6 kernel."""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from repro.kernels.backend import interpret_mode
from repro.kernels.rwkv6.ref import wkv6_ref
from repro.kernels.rwkv6.rwkv6 import DEFAULT_CHUNK, wkv6_fwd


def _ceil_to(x, m):
    return (x + m - 1) // m * m


def _padded(r, k, v, logw, u):
    B, H, S, d = r.shape
    c = min(DEFAULT_CHUNK, S) if S % DEFAULT_CHUNK else DEFAULT_CHUNK
    Sp = _ceil_to(S, c)
    pad = ((0, 0), (0, 0), (0, Sp - S), (0, 0))
    rp, kp, vp = (jnp.pad(x, pad) for x in (r, k, v))
    lwp = jnp.pad(logw, pad)          # logw=0 => w=1 keeps state unchanged
    o, sfin = wkv6_fwd(rp, kp, vp, lwp, u, chunk=c, interpret=True)
    return o[:, :, :S], sfin


@jax.custom_vjp
def _wkv(r, k, v, logw, u):
    return _padded(r, k, v, logw, u)


def _fwd(r, k, v, logw, u):
    return _padded(r, k, v, logw, u), (r, k, v, logw, u)


def _bwd(res, g):
    r, k, v, logw, u = res
    B, H, S, d = r.shape
    S0 = jnp.zeros((B, H, d, d), jnp.float32)
    _, vjp = jax.vjp(lambda *a: wkv6_ref(*a, S0), r, k, v, logw, u)
    return vjp(g)


_wkv.defvjp(_fwd, _bwd)


@functools.partial(jax.jit, static_argnames=("interpret",))
def wkv6(r, k, v, logw, u, *, interpret=None):
    """Chunked WKV6: r,k,v,logw (B,H,S,d), u (H,d) -> (o, S_final).

    Runs interpreted on the CPU backend only.  Mosaic does not compile the
    kernel for the TPU yet (the (1, d) block of `u`, `jnp.cumsum` and a
    three-operand einsum), so asking for it compiled raises.
    """
    if not interpret_mode(interpret):
        raise NotImplementedError(
            "the wkv6 Pallas kernel does not compile for the TPU yet "
            "(Mosaic refuses the (1, d) block of u, jnp.cumsum and the "
            "three-operand intra-chunk einsum); run rwkv configs with "
            "use_pallas=False")
    return _wkv(r, k, v, logw, u)
