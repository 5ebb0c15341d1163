"""Compile the Pallas kernels for a described TPU v5e at h2o-danube-1.8b
widths, with `interpret=False`.  Nothing runs: the TPU compiler, which is
installed with jax, refuses here what the chip would refuse (block shapes
off the (8, 128) tiling, VMEM over its limit, ops Mosaic cannot lower).

The topology is described inside a fixture, never at import: only one
process may load the TPU library, and each test worker imports this file.
"""
import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.kernels.decode_attention.ops import decode_attention
from repro.kernels.flash_attention.ops import flash_attention
from repro.kernels.rwkv6.rwkv6 import wkv6_fwd

KERNEL = 'custom_call_target="tpu_custom_call"'

# h2o-danube-1.8b: 32 query heads and 8 kv heads of 80, window 4096
H, K, D, WINDOW = 32, 8, 80, 4096


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 - any failure means "cannot"
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    # a compile for a described chip cannot be read back from the
    # persistent cache without that chip, so keep it out of the cache
    prev = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", prev)


def _sds(shape, sharding, dtype=jnp.bfloat16):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def _flash(q, k, v):
    return flash_attention(q, k, v, causal=True, window=WINDOW,
                           interpret=False)


def test_flash_forward_compiles(one_chip):
    q = _sds((1, 4096, H, D), one_chip)
    kv = _sds((1, 4096, K, D), one_chip)
    txt = jax.jit(_flash).lower(q, kv, kv).compile().as_text()
    assert KERNEL in txt


def test_flash_forward_backward_compiles(one_chip):
    q = _sds((1, 4096, H, D), one_chip)
    kv = _sds((1, 4096, K, D), one_chip)

    def loss(q, k, v):
        return jnp.sum(_flash(q, k, v).astype(jnp.float32))
    step = jax.jit(jax.value_and_grad(loss, argnums=(0, 1, 2)))
    txt = step.lower(q, kv, kv).compile().as_text()
    assert KERNEL in txt


def test_decode_attention_compiles_batch_8(one_chip):
    B, W = 8, 2176            # serve cache: prompt 2048 + 128 generated
    q = _sds((B, 1, H, D), one_chip)
    kv = _sds((B, W, K, D), one_chip)
    bias = _sds((B, W), one_chip, jnp.float32)
    txt = jax.jit(lambda q, k, v, b: decode_attention(
        q, k, v, b, interpret=False)).lower(q, kv, kv, bias).compile(
    ).as_text()
    assert KERNEL in txt


@pytest.mark.xfail(strict=True, reason=(
    "Mosaic refuses the wkv6 kernel: the (1, d) BlockSpec of u over (H, d) "
    "breaks the (8, 128) block rule, and past that jnp.cumsum and the "
    "three-operand einsum 'td,tid,id->ti' do not lower"))
def test_wkv6_compiles_rwkv6_7b(one_chip):
    B, NH, S, d = 1, 64, 4096, 64           # rwkv6-7b: 64 heads of 64
    x = _sds((B, NH, S, d), one_chip, jnp.float32)
    u = _sds((NH, d), one_chip, jnp.float32)
    txt = jax.jit(lambda r, k, v, w, u: wkv6_fwd(
        r, k, v, w, u, interpret=False)).lower(x, x, x, x, u).compile(
    ).as_text()
    assert KERNEL in txt
