"""Sharding rules: every (arch, production-mesh) param/state/cache spec
must divide evenly.  Uses AbstractMesh — no devices required."""
import jax
import jax.numpy as jnp
import pytest
from jax.sharding import AbstractMesh, PartitionSpec as P

from repro.configs import ALL_ARCHS, SHAPES, get_config, smoke_variant, \
    supports_shape
from repro.models import model as M
from repro.optim import OptimizerConfig
from repro.sharding.rules import ShardingRules, param_specs, state_specs
from repro.train.steps import abstract_caches, abstract_state

SINGLE = AbstractMesh((16, 16), ("data", "model"))
MULTI = AbstractMesh((2, 16, 16), ("pod", "data", "model"))


def _axis_size(mesh, ax):
    size = 1
    for a in (ax if isinstance(ax, tuple) else ((ax,) if ax else ())):
        size *= dict(zip(mesh.axis_names, mesh.axis_sizes))[a]
    return size


def _check_divisible(tree, specs, mesh):
    leaves, _ = jax.tree_util.tree_flatten_with_path(tree)
    sleaves = jax.tree.leaves(specs, is_leaf=lambda x: isinstance(x, P))
    assert len(leaves) == len(sleaves)
    for (path, leaf), spec in zip(leaves, sleaves):
        for dim, ax in zip(leaf.shape, spec):
            sz = _axis_size(mesh, ax)
            assert dim % sz == 0, (jax.tree_util.keystr(path), leaf.shape,
                                   spec)


@pytest.mark.parametrize("mesh", [SINGLE, MULTI], ids=["single", "multi"])
@pytest.mark.parametrize("arch", ALL_ARCHS)
def test_param_specs_divide(arch, mesh):
    cfg = get_config(arch)
    tp = dict(zip(mesh.axis_names, mesh.axis_sizes))["model"]
    params = jax.eval_shape(
        lambda: M.init_params(jax.random.PRNGKey(0), cfg, tp))
    specs = param_specs(params, mesh)
    _check_divisible(params, specs, mesh)


@pytest.mark.parametrize("arch", ["kimi-k2-1t-a32b", "llama3-405b"])
def test_state_specs_divide_int8(arch):
    cfg = get_config(arch)
    state = abstract_state(cfg, OptimizerConfig(state_dtype="int8",
                                                master=False), 16)
    specs = state_specs(state, SINGLE)
    _check_divisible(state, specs, SINGLE)


@pytest.mark.parametrize("arch", ALL_ARCHS)
@pytest.mark.parametrize("shape", ["decode_32k", "long_500k"])
def test_cache_specs_divide(arch, shape):
    cfg = get_config(arch)
    sh = SHAPES[shape]
    if not supports_shape(cfg, sh)[0]:
        pytest.skip("cell skipped by design")
    caches = abstract_caches(cfg, sh, 16)
    rules = ShardingRules(SINGLE, seq_sharded=(sh.global_batch < 16))
    specs = rules.cache_specs(caches)
    _check_divisible(caches, specs, SINGLE)


@pytest.mark.parametrize("seq_sharded", [False, True])
def test_cache_specs_follow_the_kernel_layout(seq_sharded):
    """The self-attention ring (n_periods, B, K, hd, Wp) shards its slots,
    the last axis, where the sequence is sharded; the cross-attention
    source (n_periods, B, T, K, hd) keeps its own order."""
    mesh = AbstractMesh((2, 2), ("data", "model"))
    cfg = smoke_variant(get_config("whisper-large-v3"))
    caches = jax.eval_shape(lambda: M.init_caches(cfg, 4, 256, tp=2))
    specs = ShardingRules(mesh, seq_sharded=seq_sharded).cache_specs(caches)
    B, S = (None, "data") if seq_sharded else ("data", None)
    layer = specs["layers"][0]
    assert layer["kv"]["k"] == layer["kv"]["v"] == P(None, B, "model", None,
                                                     S)
    assert layer["xkv"]["k"] == layer["xkv"]["v"] == P(None, B, S, "model",
                                                       None)


def test_tp_weight_sharding_covers_big_tensors():
    """Every >= 1M-element param must actually be sharded (not replicated)
    on the production mesh — replicated big tensors blow HBM."""
    cfg = get_config("llama3-405b")
    params = jax.eval_shape(
        lambda: M.init_params(jax.random.PRNGKey(0), cfg, 16))
    specs = param_specs(params, SINGLE)
    leaves, _ = jax.tree_util.tree_flatten_with_path(params)
    sleaves = jax.tree.leaves(specs, is_leaf=lambda x: isinstance(x, P))
    for (path, leaf), spec in zip(leaves, sleaves):
        n = 1
        for d in leaf.shape:
            n *= d
        if n >= 2 ** 20:
            total = 1
            for ax in spec:
                total *= _axis_size(SINGLE, ax)
            assert total >= 16, (jax.tree_util.keystr(path), spec)
