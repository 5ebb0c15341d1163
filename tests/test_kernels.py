"""Per-kernel allclose vs the pure-jnp oracles, shape/dtype sweeps
(interpret=True executes the kernel bodies on CPU)."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from _hypothesis_compat import given, settings, st

from repro.kernels.decode_attention import cache_width, decode_attention, \
    decode_attention_ref
from repro.kernels.flash_attention import flash_attention, \
    flash_attention_ref
from repro.kernels.rwkv6 import wkv6, wkv6_ref
from repro.kernels.selective_scan import selective_scan, selective_scan_ref
from repro.kernels.selective_scan.selective_scan import DEFAULT_BI, \
    DEFAULT_BS
from repro.models import jamba_ref

KEY = jax.random.PRNGKey(0)


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("B,S,H,K,d,causal,win", [
    (2, 256, 4, 2, 64, True, None),
    (1, 384, 8, 8, 128, True, None),
    (2, 200, 4, 1, 80, True, 96),      # GQA + sliding window + padding
    (1, 128, 2, 2, 32, False, None),   # non-causal (whisper encoder)
    (1, 130, 6, 2, 112, True, None),   # ragged seq + kimi head_dim
])
def test_flash_attention_sweep(B, S, H, K, d, causal, win, dtype):
    ks = jax.random.split(KEY, 3)
    q = jax.random.normal(ks[0], (B, S, H, d), dtype)
    k = jax.random.normal(ks[1], (B, S, K, d), dtype)
    v = jax.random.normal(ks[2], (B, S, K, d), dtype)
    o = flash_attention(q, k, v, causal=causal, window=win)
    ref = flash_attention_ref(q, k, v, causal=causal, window=win)
    tol = 2e-5 if dtype == jnp.float32 else 2e-2
    np.testing.assert_allclose(
        np.asarray(o, np.float32), np.asarray(ref, np.float32), atol=tol)


@given(st.integers(1, 3), st.sampled_from([64, 100, 192]),
       st.sampled_from([(4, 2), (2, 2), (8, 1)]), st.sampled_from([32, 64]))
@settings(max_examples=10, deadline=None)
def test_flash_attention_property(B, S, HK, d):
    H, K = HK
    ks = jax.random.split(jax.random.PRNGKey(B * S + d), 3)
    q = jax.random.normal(ks[0], (B, S, H, d))
    k = jax.random.normal(ks[1], (B, S, K, d))
    v = jax.random.normal(ks[2], (B, S, K, d))
    o = flash_attention(q, k, v, causal=True)
    ref = flash_attention_ref(q, k, v, causal=True)
    np.testing.assert_allclose(np.asarray(o), np.asarray(ref), atol=3e-5)
    # causality: output at position t must not depend on tokens > t
    t = S // 2
    k2 = k.at[:, t + 1:].set(0.0)
    v2 = v.at[:, t + 1:].set(9.9)
    o2 = flash_attention(q, k2, v2, causal=True)
    np.testing.assert_allclose(np.asarray(o[:, :t + 1]),
                               np.asarray(o2[:, :t + 1]), atol=3e-5)


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("B,W,H,K,d,L,layer,index", [
    (2, 512, 4, 2, 64, 2, 1, 300),     # ring not full yet
    (1, 300, 8, 8, 128, 1, 0, 700),    # W < Wp 384, wrapped
    (2, 1000, 4, 1, 80, 3, 2, 1500),   # d 80, W < Wp 1024, wrapped
    (3, 1276, 8, 2, 80, 2, 1, 1275),   # danube-decode's ring, full
])
def test_decode_attention_sweep(B, W, H, K, d, L, layer, index, dtype):
    """The stacked ring cache (L,B,K,d,Wp): position t of the sequence
    sits in slot t % W of layer `layer`, and the token at `index` is
    written into its slot and attended to; slots the mask leaves out (past
    the ring, not yet written, other layers) hold garbage."""
    Wp = cache_width(W)
    ks = jax.random.split(KEY, 4)
    q = jax.random.normal(ks[0], (B, 1, H, d), dtype)
    k = 50.0 * jax.random.normal(ks[1], (L, B, K, d, Wp), dtype)
    v = 50.0 * jax.random.normal(ks[2], (L, B, K, d, Wp), dtype)
    seen = np.arange(max(0, index - W + 1), index + 1)     # positions kept
    ring = jax.random.normal(ks[3], (2, B, K, d, len(seen)), dtype)
    k = k.at[layer, ..., seen[:-1] % W].set(jnp.moveaxis(ring[0, ..., :-1],
                                                         -1, 0))
    v = v.at[layer, ..., seen[:-1] % W].set(jnp.moveaxis(ring[1, ..., :-1],
                                                         -1, 0))
    new_k, new_v = (r[..., -1][:, None] for r in ring)     # (B,1,K,d)
    slots = np.arange(Wp)
    pos = index - (index - slots) % W
    bias = jnp.broadcast_to(jnp.where((slots < W) & (pos >= 0), 0.0, -1e30),
                            (B, Wp)).astype(jnp.float32)
    args = (q, new_k, new_v, k, v, bias, jnp.int32(layer),
            jnp.int32(index % W))
    o, ck, cv = decode_attention(*args)
    ref, rk, rv = decode_attention_ref(*args)
    # the caches: the token's slot of that layer written, nothing else
    for c, new, old in ((ck, new_k, k), (cv, new_v, v)):
        want = old.at[layer, ..., index % W].set(new[:, 0])
        np.testing.assert_array_equal(np.asarray(c), np.asarray(want))
    np.testing.assert_array_equal(np.asarray(rk), np.asarray(ck))
    np.testing.assert_array_equal(np.asarray(rv), np.asarray(cv))
    # the plain attention of the query over the kept positions
    qf = np.asarray(q, np.float32).reshape(B, K, H // K, d)
    kf, vf = np.asarray(ring, np.float32)
    s = np.einsum("bkgd,bkdt->bkgt", qf, kf) / np.sqrt(d)
    p = np.exp(s - s.max(-1, keepdims=True))
    plain = np.einsum("bkgt,bkdt->bkgd", p / p.sum(-1, keepdims=True), vf)
    tol = 2e-5 if dtype == jnp.float32 else 2e-2
    np.testing.assert_allclose(
        np.asarray(o, np.float32), np.asarray(ref, np.float32), atol=tol)
    np.testing.assert_allclose(
        np.asarray(o, np.float32).reshape(plain.shape), plain,
        atol=tol if dtype == jnp.float32 else 5e-2)


@pytest.mark.parametrize("B,H,S,d", [
    (2, 2, 128, 32), (1, 4, 100, 64), (2, 1, 64, 16), (1, 2, 65, 64),
])
def test_wkv6_sweep(B, H, S, d):
    ks = jax.random.split(KEY, 5)
    r, k, v = (jax.random.normal(ks[i], (B, H, S, d)) * 0.5
               for i in range(3))
    logw = -jnp.exp(jax.random.normal(ks[3], (B, H, S, d)) * 0.5 - 1.0)
    u = jax.random.normal(ks[4], (H, d)) * 0.5
    o, sf = wkv6(r, k, v, logw, u)
    oref, sref = wkv6_ref(r, k, v, logw, u, jnp.zeros((B, H, d, d)))
    np.testing.assert_allclose(np.asarray(o), np.asarray(oref), atol=1e-4)
    np.testing.assert_allclose(np.asarray(sf), np.asarray(sref), atol=1e-4)


def _scan_inputs(n, S, I, N, dtype, h0_scale):
    ks = jax.random.split(jax.random.PRNGKey(S), 6)
    dt = jax.nn.softplus(jax.random.normal(ks[0], (n, S, I)) - 1.0)
    A = -jnp.exp(jax.random.normal(ks[1], (I, N)))
    Bm, Cm = (jax.random.normal(k, (n, S, N)) for k in ks[2:4])
    x = jax.random.normal(ks[4], (n, S, I))
    h0 = h0_scale * jax.random.normal(ks[5], (n, I, N))
    dt, Bm, Cm, x = (t.astype(dtype) for t in (dt, Bm, Cm, x))
    return dt, A, Bm, Cm, x, h0


@pytest.mark.parametrize("S,I,N,dtype,h0_scale", [
    (2 * DEFAULT_BS, 256, 16, jnp.float32, 0.0),     # whole blocks
    (DEFAULT_BS + 72, 128, 8, jnp.float32, 0.0),     # a partial last block
    (37, 200, 16, jnp.float32, 0.0),                 # less than a block
    (1, 128, 16, jnp.float32, 0.0),                  # one token
    (DEFAULT_BS + 72, 128, 16, jnp.float32, 1.0),    # a state carried in
    (DEFAULT_BS + 72, 256, 16, jnp.bfloat16, 1.0),   # served dtype
    (DEFAULT_BS, DEFAULT_BI + 128, 16, jnp.float32, 1.0),  # channel tiles
])
def test_selective_scan_sweep(S, I, N, dtype, h0_scale):
    """The kernel against the per-token recurrence of the float32
    reference and against the model's chunked scan: the state to 1e-5;
    y to 1e-5 in float32, and in bfloat16 within one rounding of the
    float32 result (the kernel computes in float32 whatever comes in)."""
    args = _scan_inputs(2, S, I, N, dtype, h0_scale)
    y, h = selective_scan(*args)
    f32 = [t.astype(jnp.float32) for t in args]
    with jax.default_matmul_precision("highest"):
        y_ref, h_ref = jamba_ref.selective_scan(*f32)
        y_xla, h_xla = selective_scan_ref(*args)
    assert y.dtype == y_xla.dtype == dtype and y.shape == (2, S, I)
    for other in (h_ref, h_xla):
        np.testing.assert_allclose(np.asarray(h), np.asarray(other),
                                   rtol=1e-5, atol=1e-5)
    y, y_xla = (np.asarray(t, np.float32) for t in (y, y_xla))
    rtol = 1e-5 if dtype == jnp.float32 else 2.0 ** -8
    np.testing.assert_allclose(y, np.asarray(y_ref), rtol=rtol, atol=1e-5)
    np.testing.assert_allclose(y, y_xla, rtol=2 * rtol, atol=1e-5)


def test_wkv6_strong_decay_stability():
    """Extreme decays must not produce inf/nan (exponents <= 0 by design)."""
    B, H, S, d = 1, 1, 128, 32
    ks = jax.random.split(KEY, 4)
    r, k, v = (jax.random.normal(ks[i], (B, H, S, d)) for i in range(3))
    logw = jnp.full((B, H, S, d), -30.0)    # near-instant forgetting
    u = jnp.zeros((H, d))
    o, sf = wkv6(r, k, v, logw, u)
    assert np.isfinite(np.asarray(o)).all()
    logw = jnp.full((B, H, S, d), -1e-6)    # near-perfect memory
    o, sf = wkv6(r, k, v, logw, u)
    assert np.isfinite(np.asarray(o)).all()


def test_kernel_grads_flow():
    B, S, H, K, d = 1, 128, 2, 2, 32
    ks = jax.random.split(KEY, 3)
    q = jax.random.normal(ks[0], (B, S, H, d))
    k = jax.random.normal(ks[1], (B, S, K, d))
    v = jax.random.normal(ks[2], (B, S, K, d))
    g = jax.grad(lambda q: jnp.sum(flash_attention(q, k, v) ** 2))(q)
    gref = jax.grad(lambda q: jnp.sum(
        flash_attention_ref(q, k, v) ** 2))(q)
    np.testing.assert_allclose(np.asarray(g), np.asarray(gref), atol=1e-3)


def test_kernels_interpret_only_on_the_cpu():
    from repro.kernels.backend import interpret_mode
    assert interpret_mode() is (jax.default_backend() == "cpu")
    assert interpret_mode(False) is False and interpret_mode(True) is True


def test_wkv6_refuses_to_compile():
    """Asked for compiled, wkv6 raises rather than interpret or fall back."""
    B, H, S, d = 1, 1, 64, 16
    x = jnp.zeros((B, H, S, d))
    with pytest.raises(NotImplementedError, match="does not compile"):
        wkv6(x, x, x, x, jnp.zeros((H, d)), interpret=False)
