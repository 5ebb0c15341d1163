"""Multi-device semantics, via subprocesses so the main pytest process
keeps 1 device (XLA locks device count at first jax init)."""
import os
import pathlib
import subprocess
import sys

import pytest

REPO = pathlib.Path(__file__).resolve().parents[1]

pytestmark = pytest.mark.slow          # each case compiles for minutes

def run_py(code: str, devices: int = 8, timeout: int = 420) -> str:
    env = dict(os.environ)
    env["XLA_FLAGS"] = f"--xla_force_host_platform_device_count={devices}"
    env["PYTHONPATH"] = str(REPO / "src")
    p = subprocess.run([sys.executable, "-c", code], capture_output=True,
                       text=True, timeout=timeout, env=env)
    assert p.returncode == 0, f"STDOUT:\n{p.stdout}\nSTDERR:\n{p.stderr}"
    return p.stdout


def test_hierarchical_allreduce_matches_flat():
    run_py("""
import jax, jax.numpy as jnp, numpy as np
from repro.launch.mesh import auto_mesh
from repro.core.collectives import flat_all_reduce, hierarchical_all_reduce
mesh = auto_mesh((2,2,2), ("pod","data","model"))
x = jax.random.normal(jax.random.PRNGKey(0), (4, 64))  # 4 replicas
a = flat_all_reduce(x, mesh)
b = hierarchical_all_reduce(x, mesh)
np.testing.assert_allclose(np.asarray(a), np.asarray(b), rtol=1e-5)
np.testing.assert_allclose(np.asarray(a)[0], np.asarray(x).sum(0), rtol=1e-5)
# traffic: hierarchical moves less DCN than flat in compiled HLO
from repro.launch.hlo_analysis import analyze_collectives
h_f = jax.jit(lambda x: flat_all_reduce(x, mesh)).lower(x).compile().as_text()
h_h = jax.jit(lambda x: hierarchical_all_reduce(x, mesh)).lower(x).compile().as_text()
f = analyze_collectives(h_f, pod_size=4, n_dev=8)
h = analyze_collectives(h_h, pod_size=4, n_dev=8)
assert h.dcn_bytes < f.dcn_bytes, (h.dcn_bytes, f.dcn_bytes)
print("OK", f.dcn_bytes, h.dcn_bytes)
""")


def test_quantized_psum_error_feedback_converges():
    run_py("""
import jax, jax.numpy as jnp, numpy as np
from repro.launch.mesh import auto_mesh
from jax.sharding import PartitionSpec as P
from repro.core.collectives import quantized_psum_pod
mesh = auto_mesh((2,4), ("pod","data"))
g = jax.random.normal(jax.random.PRNGKey(0), (2, 256))  # per-pod grads
def sync(g, ef):
    return quantized_psum_pod(g, ef)
f = jax.jit(jax.shard_map(sync, mesh=mesh, in_specs=(P('pod'), P('pod')),
                          out_specs=(P('pod'), P('pod')), check_vma=False))
ef = jnp.zeros_like(g)
true_mean = jnp.mean(g, axis=0, keepdims=True)
# single shot: quantization error bounded by scale/2
out, ef = f(g, ef)
err1 = float(jnp.max(jnp.abs(out[0] - true_mean[0])))
scale = float(jnp.max(jnp.abs(g)))/127
assert err1 <= scale, (err1, scale)
# repeated sync of the SAME gradient: error feedback drives mean error -> 0
acc = jnp.zeros((1,256)); accq = jnp.zeros((1,256))
ef = jnp.zeros_like(g)
for i in range(20):
    out, ef = f(g, ef)
    acc = acc + true_mean; accq = accq + out[:1]
rel = float(jnp.max(jnp.abs(acc-accq))/jnp.max(jnp.abs(acc)))
assert rel < 0.01, rel
print("OK", err1, rel)
""")


def test_compressed_pod_train_step_matches_gspmd():
    run_py("""
import jax, jax.numpy as jnp, numpy as np
from repro.launch.mesh import auto_mesh
from jax.sharding import NamedSharding, PartitionSpec as P
from repro.configs import get_config, smoke_variant
from repro.models import model as M
from repro.optim import OptimizerConfig, adamw_init
from repro.sharding.rules import ShardingRules, state_specs
from repro.train.steps import make_train_step
cfg = smoke_variant(get_config("h2o-danube-1.8b"))
mesh = auto_mesh((2,2,2), ("pod","data","model"))
rules = ShardingRules(mesh)
oc = OptimizerConfig(lr=1e-3)
toks = jax.random.randint(jax.random.PRNGKey(1), (8, 33), 0, cfg.vocab_size)
batch = {"tokens": toks[:, :-1], "labels": toks[:, 1:]}
outs = {}
for mode in ("gspmd", "compressed_pod"):
    params = M.init_params(jax.random.PRNGKey(0), cfg, tp=2)
    from repro.optim.adamw import adamw_init
    state = adamw_init(params, oc, with_ef=(mode=="compressed_pod"))
    sspec = state_specs(state, mesh, fsdp_pod=(mode!="compressed_pod"))
    state = jax.device_put(state, jax.tree.map(
        lambda s: NamedSharding(mesh, s), sspec,
        is_leaf=lambda x: isinstance(x, P)))
    step = jax.jit(make_train_step(cfg, oc, rules, grad_sync=mode))
    for _ in range(3):
        state, m = step(state, batch)
    outs[mode] = float(m["loss"])
assert np.isfinite(outs["gspmd"]) and np.isfinite(outs["compressed_pod"])
assert abs(outs["gspmd"] - outs["compressed_pod"]) < 0.05, outs
print("OK", outs)
""")


@pytest.mark.parametrize("shape,mk", [("train_4k", "single"),
                                      ("train_4k", "multi"),
                                      ("long_500k", "single")])
def test_small_mesh_dryrun_cell(shape, mk):
    run_py(f"""
import repro.launch.dryrun as DR
from repro.launch.mesh import auto_mesh
def small_mesh(*, multi_pod=False):
    return auto_mesh((2,2,2) if multi_pod else (2,4),
                     ("pod","data","model") if multi_pod
                     else ("data","model"))
DR.make_production_mesh = small_mesh
rec = DR.run_cell("h2o-danube-1.8b", "{shape}", "{mk}", save=False)
assert rec["status"] == "ok", rec
assert rec["roofline"]["flops"] > 0
print("OK", rec["roofline"]["bottleneck"])
""")


@pytest.mark.parametrize("arch", ["qwen3-32b", "llama4-scout-17b-a16e",
                                  "kimi-k2-1t-a32b"])
def test_dp_matches_single_device(arch):
    """Data-parallel sharded loss == single-device loss (same batch); the
    MoE configs route through the grouped experts under the mesh."""
    run_py(f"ARCH = {arch!r}\n" + """
import jax, jax.numpy as jnp, numpy as np
from repro.launch.mesh import auto_mesh
from jax.sharding import NamedSharding, PartitionSpec as P
from repro.configs import get_config, smoke_variant
from repro.models import model as M
from repro.optim import OptimizerConfig, adamw_init
from repro.sharding.rules import ShardingRules, state_specs
from repro.train.steps import make_train_step
cfg = smoke_variant(get_config(ARCH))
oc = OptimizerConfig()
toks = jax.random.randint(jax.random.PRNGKey(1), (8, 17), 0, cfg.vocab_size)
batch = {"tokens": toks[:, :-1], "labels": toks[:, 1:]}
losses = {}
for meshdims in [(1,1), (4,2)]:
    mesh = auto_mesh(meshdims, ("data","model"))
    rules = ShardingRules(mesh)
    params = M.init_params(jax.random.PRNGKey(0), cfg, tp=meshdims[1])
    state = adamw_init(params, oc)
    sspec = state_specs(state, mesh)
    state = jax.device_put(state, jax.tree.map(
        lambda s: NamedSharding(mesh, s), sspec,
        is_leaf=lambda x: isinstance(x, P)))
    step = jax.jit(make_train_step(cfg, oc, rules))
    state, m = step(state, batch)
    losses[meshdims] = float(m["loss"])
vals = list(losses.values())
assert abs(vals[0]-vals[1]) < 0.02, losses
print("OK", losses)
""")


def test_chip_smoke_four_chip_phase_on_host_devices():
    """chip_smoke.py --four-chips' comparison, on 4 CPU devices."""
    out = run_py(f"""
import dataclasses, sys
sys.path.insert(0, {str(REPO)!r})
import chip_smoke
from repro.configs import get_config, smoke_variant
cfg = dataclasses.replace(smoke_variant(get_config("h2o-danube-1.8b")),
                          num_layers=4)
chip_smoke.four_chip_phase(cfg, steps=3, batch=2, seq=64, seed=0)
""", devices=4)
    assert '"phase": "four_chips"' in out
