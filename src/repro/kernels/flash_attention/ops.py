"""jit'd wrapper: padding, dtype handling, custom_vjp.

Forward runs the Pallas kernel: compiled on a TPU, interpreted on the CPU.
The pads and slice-back run under the scope `kv`, as do the kernel's own
transposes; the kernel runs under `kernel`.
Backward recomputes through the jnp oracle (the kernel is forward-only;
a backward flash kernel is still to be written).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from repro.kernels.backend import interpret_mode
from repro.kernels.flash_attention.flash_attention import (
    DEFAULT_BK, DEFAULT_BQ, flash_attention_fwd)
from repro.kernels.flash_attention.ref import flash_attention_ref


def _ceil_to(x, m):
    return (x + m - 1) // m * m


def _padded_call(q, k, v, causal, window, interpret):
    B, S, H, d = q.shape
    bq = min(DEFAULT_BQ, _ceil_to(S, 128))
    bk = min(DEFAULT_BK, _ceil_to(S, 128))
    Sp = _ceil_to(S, max(bq, bk))
    dp = _ceil_to(d, 128)

    def pad(x, s_to, d_to):
        return jnp.pad(x, ((0, 0), (0, s_to - x.shape[1]), (0, 0),
                           (0, d_to - x.shape[3])))
    with jax.named_scope("kv"):
        qp, kp, vp = (pad(x, Sp, dp) for x in (q, k, v))
    o = flash_attention_fwd(qp, kp, vp, causal=causal, window=window,
                            bq=bq, bk=bk, seq_len=S,
                            scale=1.0 / (d ** 0.5), interpret=interpret)
    with jax.named_scope("kv"):
        return o[:, :S, :, :d]


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5))
def _flash(q, k, v, causal, window, interpret):
    return _padded_call(q, k, v, causal, window, interpret)


def _fwd(q, k, v, causal, window, interpret):
    return _padded_call(q, k, v, causal, window, interpret), (q, k, v)


def _bwd(causal, window, interpret, res, g):
    q, k, v = res
    _, vjp = jax.vjp(
        lambda q, k, v: flash_attention_ref(q, k, v, causal=causal,
                                            window=window), q, k, v)
    return vjp(g)


_flash.defvjp(_fwd, _bwd)


@functools.partial(jax.jit, static_argnames=("causal", "window",
                                             "interpret"))
def flash_attention(q, k, v, *, causal=True, window=None, interpret=None):
    """Drop-in attention core: q (B,S,H,d), k/v (B,S,K,d) -> (B,S,H,d).

    interpret=None interprets the kernel on the CPU backend and compiles it
    everywhere else (`repro.kernels.backend.interpret_mode`).
    """
    return _flash(q, k, v, causal, window, interpret_mode(interpret))
