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
from repro.kernels.flash_attention.flash_attention import flash_attention_fwd
from repro.kernels.flash_attention.ref import flash_attention_ref

ROWS = 2048     # most query rows (G heads x bq) one grid step multiplies
BK = 512        # most keys a K/V tile holds; a q tile holds no more


def _ceil_to(x, m):
    return (x + m - 1) // m * m


def _tiles(S, g):
    """(bq, bk) for a sequence of S and G query heads a kv head: powers of
    two times 128, no longer than S needs.  Besides its MXU work a grid step
    costs a fixed few hundred cycles (the scores' softmax sits between the
    two dots), so tiles are as large as VMEM keeps them: the q tile of the
    G heads holds at most ROWS rows, its f32 scores at most ROWS x BK."""
    top = 128
    while top < S:
        top *= 2
    bq = 128
    while g * bq * 2 <= ROWS and bq * 2 <= min(BK, top):
        bq *= 2
    bk = min(BK, top)
    while g * bq * bk > ROWS * BK and bk > 128:
        bk //= 2
    return bq, bk


def _padded_call(q, k, v, causal, window, interpret):
    B, S, H, d = q.shape
    bq, bk = _tiles(S, H // k.shape[2])
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
