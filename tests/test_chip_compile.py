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
import math
import os
import pathlib
import re

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.configs import get_config
from repro.kernels.decode_attention import cache_width
from repro.kernels.decode_attention import ops as decode_ops
from repro.kernels.decode_attention.ops import decode_attention
from repro.kernels.flash_attention import ops as flash_ops
from repro.kernels.flash_attention.ops import flash_attention
from repro.kernels.rwkv6.rwkv6 import wkv6_fwd
from repro.kernels.selective_scan import ops as scan_ops
from repro.kernels.selective_scan.ops import selective_scan
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


@pytest.mark.parametrize("B,S,H_,K_,D_,window", [
    (32, 1020, 32, 8, 80, 4096),        # danube-decode's prefill
    (8, 1500, 56, 8, 128, None),        # deepseek-codecomp's prefill
])
def test_flash_forward_compiles_cell_prefill(one_chip, B, S, H_, K_, D_,
                                             window):
    # the tiles chosen for the cell's shape fit the chip's VMEM
    q = _sds((B, S, H_, D_), one_chip)
    kv = _sds((B, S, K_, D_), one_chip)
    txt = jax.jit(functools.partial(
        flash_attention, causal=True, window=window, interpret=False)).lower(
        q, kv, kv).compile().as_text()
    assert KERNEL in txt


def test_decode_attention_compiles_batch_8(one_chip):
    B, W = 8, 2176            # serve cache: prompt 2048 + 128 generated
    Wp = cache_width(W)
    q = _sds((B, 1, H, D), one_chip)
    new = _sds((B, 1, K, D), one_chip)
    kv = _sds((24, B, K, D, Wp), one_chip)     # the 24 layers' stacked ring
    bias = _sds((B, Wp), one_chip, jnp.float32)
    index = _sds((), one_chip, jnp.int32)
    txt = jax.jit(functools.partial(decode_attention, interpret=False),
                  donate_argnums=(3, 4)).lower(
        q, new, new, kv, kv, bias, index, index).compile().as_text()
    assert KERNEL in txt


def test_selective_scan_compiles_jamba_chat(one_chip):
    # jamba-chat's prefill: B 8, prompt 1020 (a partial last block),
    # I = 2 x 4096, N 16; dt, x and y in bf16
    B, S, I, N = 8, 1020, 8192, 16
    x = _sds((B, S, I), one_chip)
    bc = _sds((B, S, N), one_chip)
    a = _sds((I, N), one_chip, jnp.float32)
    h = _sds((B, I, N), one_chip, jnp.float32)
    txt = jax.jit(functools.partial(selective_scan, interpret=False)).lower(
        x, a, bc, bc, x, h).compile().as_text()
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
    attn = {f"attn/{s}" for s in ("qkv", "rope", "core", "out")}
    # the decode step writes its cache slot under attn/core
    for lowered, extra, kv in ((prefill, set(), {"attn/kv"}),
                               (step, {"sample"}, set())):
        paths = scope_paths(lowered.as_text(dialect="hlo", debug_info=True))
        heads = {p.split("/")[0] for p in paths}
        assert set(TOP_SCOPES) | extra <= heads, sorted(paths)
        assert attn | kv <= {"/".join(p.split("/")[:2]) for p in paths}


@pytest.fixture
def compiled_kernels(monkeypatch):
    """Programs traced here compile the kernels: the wrappers would pick
    interpretation from the default backend, the CPU.  Traced programs are
    cached by shape, not by that choice, so the caches are cleared around."""
    for mod in (decode_ops, flash_ops, scan_ops):
        monkeypatch.setattr(mod, "interpret_mode",
                            lambda i=None: False if i is None else i)
    jax.clear_caches()
    yield
    jax.clear_caches()


def test_serving_programs_keep_scopes_compiled(one_chip, compiled_kernels):
    # a 2-layer h2o-danube at published widths, with the Pallas kernels
    prefill, step = serving_programs(DANUBE_2L, one_chip, use_pallas=True)
    for lowered, kernel, outside in ((prefill, "flash_attention", True),
                                     (step, "decode_attention", False)):
        lines = lowered.compile().as_text().splitlines()
        # a trace shows a custom call by its instruction's name, and the
        # per-layer readers look the kernels up by that name
        calls = [ln for ln in lines if KERNEL in ln and " = " in ln]
        assert calls and all(
            re.match(rf"\s*%{kernel}\.\d+ = ", ln) for ln in calls), calls
        assert all("/attn/" in ln and "/kernel/" in ln for ln in calls)
        # prefill: the flash wrapper's pads and the cache fill sit under
        # attn/kv; decode: the kernel reads the cache as it lies, unpadded,
        # and writes the token's slot itself
        pads = [ln for ln in lines
                if f"/jit({kernel})/" in ln and re.search(r" pad\(", ln)]
        writes = [ln for ln in lines
                  if " dynamic-update-slice(" in ln and "/closed_call/" in ln]
        assert bool(pads) == bool(writes) == outside, (len(pads),
                                                       len(writes))
        assert all("/attn/kv/" in ln or "/attn/jit" in ln and "/kv/" in ln
                   for ln in pads + writes)


JAMBA_PERIOD = dataclasses.replace(
    get_config("jamba-v0.1-52b"), num_layers=8, d_model=1024, d_ff=1024,
    vocab_size=1024, moe=dataclasses.replace(
        get_config("jamba-v0.1-52b").moe, num_experts=4, d_ff=512))


def test_mamba_prefill_runs_the_scan_kernel(one_chip, compiled_kernels):
    """One Jamba period (7 Mamba mixers, I 2048, N 16), prefill with the
    Pallas kernels: each mixer's scan is one `selective_scan` kernel under
    `mamba/scan`, and no (B, chunk, I, N) tensor of the chunked associative
    scan is padded there; the decode step keeps the one-token update."""
    prefill, step = serving_programs(JAMBA_PERIOD, one_chip, use_pallas=True)
    lines = prefill.compile().as_text().splitlines()
    calls = [ln for ln in lines if "custom_call_target=" in ln
             and re.match(r"\s*%selective_scan\.\d+ = ", ln)]
    assert len(calls) == 7 and all(KERNEL in ln and "/mamba/scan/" in ln
                                   for ln in calls)
    pads = [ln for ln in lines if "/mamba/scan/" in ln
            and re.search(r"= f32\[\d+,\d+,\d+,\d+\]\S* pad\(", ln)]
    assert pads == []
    assert "selective_scan" not in step.compile().as_text()


def _instructions(hlo: str):
    """(computation, is root, name, opcode, output shapes, operand names,
    line) of every instruction of an optimized program."""
    comp = None
    for line in hlo.splitlines():
        head = re.match(r"(?:ENTRY )?%(\S+) \(.*\{$", line)
        if head:
            comp = head.group(1)
            continue
        m = re.match(r"\s*(ROOT )?%(\S+) = (.*)", line)
        if not m:
            continue
        rest = " " + m.group(3)
        op = re.search(r"\s([a-z][a-z0-9\-]*)\(", rest)
        shapes = [tuple(int(n) for n in dims.split(",") if n)
                  for dims in re.findall(r"\w+\[([\d,]*)\]",
                                         rest[:op.start()])]
        args = re.findall(r"%([\w.\-]+)", rest[op.end():].split(")")[0])
        yield comp, bool(m.group(1)), m.group(2), op.group(1), shapes, args, \
            line


_NO_DATA = {"parameter", "get-tuple-element", "tuple", "bitcast", "while",
            "constant", "custom-call"}


def cache_sized_ops(hlo: str, n: int, B: int, K: int) -> list:
    """Ops that the device runs (not the insides of a fusion) with an
    output shaped like a cache, with a batch axis of B and a kv-head axis of
    K, of n or more elements; less those that move no data and the in-place
    updates of fewer than n elements (a dynamic-update-slice, alone or as a
    fusion's root)."""
    def cache_like(shape):
        rest = list(shape)
        for a in (B, K):
            if a not in rest:
                return False
            rest.remove(a)
        return math.prod(shape) >= n
    ins = list(_instructions(hlo))
    size = {name: max(map(math.prod, s), default=0)
            for _, _, name, _, s, _, _ in ins}
    fused = {c for *_, ln in ins if " fusion(" in ln
             for c in re.findall(r"calls=%([\w.\-]+)", ln)}
    root = {c: (op, args) for c, r, _, op, _, args, _ in ins if r}
    out = []
    for comp, _, _, op, shapes, args, ln in ins:
        if comp in fused or op in _NO_DATA or not any(map(cache_like,
                                                          shapes)):
            continue
        if op == "fusion":
            op, args = root[re.search(r"calls=%([\w.\-]+)", ln).group(1)]
        if op == "dynamic-update-slice" and size[args[1]] < n:
            continue
        out.append(ln[:160])
    return out


@pytest.mark.parametrize("arch,layers,B,W", [
    ("h2o-danube-1.8b", 24, 32, 1276),        # danube-decode
    ("deepseek-coder-33b", 8, 8, 1560),       # deepseek-codecomp
])
def test_decode_step_moves_no_layer_cache(one_chip, compiled_kernels, arch,
                                          layers, B, W):
    """The decode step at a chip cell's cache shapes: outside the kernel, no
    op (copy, pad, transpose, slice, fusion) outputs as many elements as one
    layer's K or V cache, but for the in-place write of the token's slot.
    (The layer scan's slices of the weights are not the caches'.)"""
    cfg = dataclasses.replace(get_config(arch), num_layers=layers)
    sds = functools.partial(jax.ShapeDtypeStruct, sharding=one_chip)
    params, caches = (
        jax.tree.map(lambda t: sds(t.shape, t.dtype), tree) for tree in (
            jax.eval_shape(lambda: M.init_params(jax.random.PRNGKey(0),
                                                 cfg)),
            jax.eval_shape(lambda: M.init_caches(cfg, B, W))))
    hlo = jax.jit(make_serve_step(cfg, use_pallas=True),
                  donate_argnums=(1,)).lower(
        params, caches, sds((B, 1), jnp.int32)).compile().as_text()
    assert KERNEL in hlo
    K = cfg.num_kv_heads
    assert cache_sized_ops(hlo, B * K * cfg.head_dim_() * W, B, K) == []


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
