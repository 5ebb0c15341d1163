"""jit'd wrapper + custom_vjp for the selective-scan kernel.

The wrapper puts the small operands in the kernel's layout: A and the
state (I, N) -> (N, I), B and C (B, S, N) -> (B, N, S) in float32, padded
with zeros to whole blocks.  dt and x, the large ones, go in as they lie.
Backward differentiates the reference, the model's bounded chunked scan
(the kernel is forward-only).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from repro.kernels.backend import interpret_mode
from repro.kernels.selective_scan.ref import selective_scan_ref
from repro.kernels.selective_scan.selective_scan import (
    DEFAULT_BS, G, lanes, selective_scan_fwd)


def _call(dt, A, Bm, Cm, x, h0, interpret):
    S = x.shape[1]
    bs = min(DEFAULT_BS, -(-S // G) * G)
    Sb = -(-S // bs) * lanes(bs)

    def cols(t):                                  # (B, S, N) -> (B, N, Sb)
        return jnp.pad(t.astype(jnp.float32).transpose(0, 2, 1),
                       ((0, 0), (0, 0), (0, Sb - S)))
    y, h = selective_scan_fwd(
        dt, x, A.T, cols(Bm), cols(Cm), h0.transpose(0, 2, 1), bs=bs,
        interpret=interpret)
    return y, h.transpose(0, 2, 1)


@functools.partial(jax.custom_vjp, nondiff_argnums=(6,))
def _scan(dt, A, Bm, Cm, x, h0, interpret):
    return _call(dt, A, Bm, Cm, x, h0, interpret)


def _fwd(dt, A, Bm, Cm, x, h0, interpret):
    return _call(dt, A, Bm, Cm, x, h0, interpret), (dt, A, Bm, Cm, x, h0)


def _bwd(interpret, res, g):
    _, vjp = jax.vjp(selective_scan_ref, *res)
    return vjp(g)


_scan.defvjp(_fwd, _bwd)


@functools.partial(jax.jit, static_argnames=("interpret",))
def selective_scan(dt, A, Bm, Cm, x, h0, *, interpret=None):
    """h_t = exp(dt_t A) h_{t-1} + dt_t B_t x_t ; y_t = C_t . h_t.

    dt, x (B, S, I); A (I, N) f32; Bm, Cm (B, S, N); h0 (B, I, N) f32.
    Returns y (B, S, I) in x's dtype and h_S (B, I, N) f32, as
    `selective_scan_ref` does.
    interpret=None: interpreted on the CPU backend, compiled elsewhere.
    """
    return _scan(dt, A, Bm, Cm, x, h0, interpret_mode(interpret))
