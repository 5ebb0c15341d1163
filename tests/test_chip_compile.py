"""Compile the Pallas kernels for a described TPU v5e at h2o-danube-1.8b
widths, with `interpret=False`.  Nothing runs: the TPU compiler, which is
installed with jax, refuses here what the chip would refuse (block shapes
off the (8, 128) tiling, VMEM over its limit, ops Mosaic cannot lower).
The serving programs are compiled the same way, to check that the names a
profiler trace shows (each op's `jax.named_scope` path, the kernels' op
names) survive the TPU compiler.

The topology is described inside a fixture, never at import: only one
process may load the TPU library, and each test worker imports this file.
"""
import contextlib
import dataclasses
import functools
import importlib.util
import os
import pathlib
import re

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.configs import get_config
from repro.kernels.decode_attention import ops as decode_ops
from repro.kernels.decode_attention.ops import decode_attention
from repro.kernels.flash_attention import ops as flash_ops
from repro.kernels.flash_attention.ops import flash_attention
from repro.kernels.rwkv6.rwkv6 import wkv6_fwd
from repro.models import model as M
from repro.train.steps import make_prefill, make_serve_step

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


# --- the serving programs' scopes ------------------------------------------

TOP_SCOPES = ("embed", "norm", "attn", "mlp", "unembed")
_JAX_MARKS = frozenset({"while", "body", "cond", "closed_call"})


def scope_paths(hlo: str) -> set:
    """The `jax.named_scope` path of every op's `op_name` (the first, where
    ops merged): the components before the primitive's, less `jit(...)`
    wrappers, einsum specs and the control flow jax adds."""
    out = set()
    for name in re.findall(r'op_name="([^";]*)', hlo):
        parts = [p for p in name.split("/")[:-1]
                 if re.fullmatch(r"[A-Za-z_]\w*", p) and p not in _JAX_MARKS]
        out.add("/".join(parts))
    return out


def serving_programs(cfg, sharding=None, *, use_pallas, B=2, P=512, G=64):
    """`prefill` and `serve_step`, lowered as the server builds them."""
    sds = functools.partial(jax.ShapeDtypeStruct, sharding=sharding)
    params, caches = (
        jax.tree.map(lambda t: sds(t.shape, t.dtype), tree) for tree in (
            jax.eval_shape(lambda: M.init_params(jax.random.PRNGKey(0),
                                                 cfg)),
            jax.eval_shape(lambda: M.init_caches(cfg, B, P + G))))
    prefill = jax.jit(make_prefill(cfg, use_pallas=use_pallas),
                      donate_argnums=(1,)).lower(
        params, caches, {"tokens": sds((B, P), jnp.int32)})
    step = jax.jit(make_serve_step(cfg, use_pallas=use_pallas),
                   donate_argnums=(1,)).lower(
        params, caches, sds((B, 1), jnp.int32))
    return prefill, step


DANUBE_2L = dataclasses.replace(get_config("h2o-danube-1.8b"), num_layers=2)


def test_serving_programs_carry_every_scope():
    # on the CPU, the XLA attention path, at small widths
    cfg = dataclasses.replace(DANUBE_2L, d_model=256, num_heads=4,
                              num_kv_heads=2, head_dim=64, d_ff=512,
                              vocab_size=512)
    prefill, step = serving_programs(cfg, use_pallas=False, P=32, G=8)
    attn = {f"attn/{s}" for s in ("qkv", "rope", "kv", "core", "out")}
    for lowered, extra in ((prefill, set()), (step, {"sample"})):
        paths = scope_paths(lowered.as_text(dialect="hlo", debug_info=True))
        heads = {p.split("/")[0] for p in paths}
        assert set(TOP_SCOPES) | extra <= heads, sorted(paths)
        assert attn <= {"/".join(p.split("/")[:2]) for p in paths}


@pytest.fixture
def compiled_kernels(monkeypatch):
    """Programs traced here compile the kernels: the wrappers would pick
    interpretation from the default backend, the CPU.  Traced programs are
    cached by shape, not by that choice, so the caches are cleared around."""
    for mod in (decode_ops, flash_ops):
        monkeypatch.setattr(mod, "interpret_mode",
                            lambda i=None: False if i is None else i)
    jax.clear_caches()
    yield
    jax.clear_caches()


def test_serving_programs_keep_scopes_compiled(one_chip, compiled_kernels):
    # a 2-layer h2o-danube at published widths, with the Pallas kernels
    prefill, step = serving_programs(DANUBE_2L, one_chip, use_pallas=True)
    for lowered, kernel, write in ((prefill, "flash_attention", "scatter"),
                                   (step, "decode_attention",
                                    "dynamic-update-slice")):
        lines = lowered.compile().as_text().splitlines()
        # a trace shows a custom call by its instruction's name, and the
        # per-layer readers look the kernels up by that name
        calls = [ln for ln in lines if KERNEL in ln and " = " in ln]
        assert calls and all(
            re.match(rf"\s*%{kernel}\.\d+ = ", ln) for ln in calls), calls
        assert all("/attn/" in ln and "/kernel/" in ln for ln in calls)
        # the wrapper's pads and the layer's cache writes sit under attn/kv
        # (the layer scan's own write-back of its results carries no scope)
        pads = [ln for ln in lines
                if f"/jit({kernel})/" in ln and re.search(r" pad\(", ln)]
        writes = [ln for ln in lines
                  if f" {write}(" in ln and "/closed_call/" in ln]
        assert pads and writes, (len(pads), len(writes))
        assert all("/attn/kv/" in ln or "/attn/jit" in ln and "/kv/" in ln
                   for ln in pads + writes)


def _hlo_text():
    """`benchmarks/hlo_text.py`, whose `canonical` takes the metadata out of
    an optimized program's text."""
    path = pathlib.Path(__file__).resolve().parents[1] / "benchmarks" / \
        "hlo_text.py"
    spec = importlib.util.spec_from_file_location("hlo_text", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def test_scopes_are_metadata_only(one_chip, compiled_kernels, monkeypatch):
    # the optimized programs are the same without any jax.named_scope
    canonical = _hlo_text().canonical

    def programs():
        jax.clear_caches()
        return [canonical(lowered.compile().as_text()) for lowered in
                serving_programs(DANUBE_2L, one_chip, use_pallas=True)]
    scoped = programs()
    monkeypatch.setattr(jax, "named_scope",
                        lambda name: contextlib.nullcontext())
    assert programs() == scoped
