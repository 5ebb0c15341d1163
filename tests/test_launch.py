"""Launchers, the compile cache and `chip_smoke.py`, on the CPU at smoke
size (kernels interpreted)."""
import importlib.util
import json
import os
import pathlib
import subprocess
import sys

import jax
import numpy as np
import pytest

from repro.configs import get_config, smoke_variant
from repro.launch.compile_cache import REPO, enable_compile_cache
from repro.launch.serve import serve
from repro.launch.train import train_loop

CFG = smoke_variant(get_config("h2o-danube-1.8b"))


def _load_chip_smoke():
    spec = importlib.util.spec_from_file_location(
        "chip_smoke", REPO / "chip_smoke.py")
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


@pytest.fixture
def cache_dir_restored():
    prev = jax.config.jax_compilation_cache_dir
    yield
    jax.config.update("jax_compilation_cache_dir", prev)


def test_compile_cache_takes_the_env_dir(monkeypatch, tmp_path,
                                         cache_dir_restored):
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
    assert enable_compile_cache() == str(tmp_path)
    assert jax.config.jax_compilation_cache_dir == str(tmp_path)


def test_compile_cache_defaults_to_the_repo(monkeypatch, cache_dir_restored):
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    path = enable_compile_cache()
    assert path == str(REPO / ".jax_cache")
    assert jax.config.jax_compilation_cache_dir == path
    ignored = (REPO / ".gitignore").read_text().split()
    assert ".jax_cache/" in ignored


def test_compile_cache_lands_in_the_env_dir_only(tmp_path):
    """A fresh process writes its cache entries to the env dir alone."""
    default = REPO / ".jax_cache"
    before = sorted(os.listdir(default)) if default.exists() else None
    code = f"""
import sys
sys.path.insert(0, {str(REPO / "src")!r})
import jax, jax.numpy as jnp
from repro.launch.compile_cache import enable_compile_cache
print(enable_compile_cache())
jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
jax.jit(lambda x: jnp.sin(x) @ x.T)(jnp.ones((64, 64))).block_until_ready()
"""
    env = dict(os.environ, JAX_COMPILATION_CACHE_DIR=str(tmp_path / "jc"))
    p = subprocess.run([sys.executable, "-c", code], capture_output=True,
                       text=True, timeout=300, env=env)
    assert p.returncode == 0, p.stderr
    assert p.stdout.split() == [str(tmp_path / "jc")]
    assert os.listdir(tmp_path / "jc")
    after = sorted(os.listdir(default)) if default.exists() else None
    assert after == before


def test_serve_compiles_outside_the_timed_window():
    toks, stats = serve(CFG, batch=2, prompt_len=16, gen=4, seed=3,
                        use_pallas=True)
    assert toks.shape == (2, 4)
    assert ((np.asarray(toks) >= 0) & (np.asarray(toks) < CFG.vocab_size)
            ).all()
    assert stats["compile_s"] > 0 and stats["prefill_s"] > 0
    # the CPU interprets the kernels: nothing compiled into the programs
    assert stats["prefill_kernel_calls"] == 0
    assert stats["decode_kernel_calls"] == 0


def test_serve_is_a_function_of_the_seed():
    a, _ = serve(CFG, batch=2, prompt_len=16, gen=4, seed=5)
    b, _ = serve(CFG, batch=2, prompt_len=16, gen=4, seed=5)
    np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_train_loop_keeps_the_compiling_step_out():
    state, info = train_loop(CFG, steps=3, batch=1, seq=16, seed=0,
                             log_every=100)
    assert int(state.step) == 3
    assert len(info["losses"]) == 3
    assert all(np.isfinite(info["losses"]))
    assert info["first_step_s"] > 0
    assert len(info["step_times_s"]) == 2


def test_chip_smoke_refuses_the_cpu():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    p = subprocess.run([sys.executable, str(REPO / "chip_smoke.py")],
                       capture_output=True, text=True, timeout=300, env=env)
    assert p.returncode != 0
    assert '"ok"' not in p.stdout
    assert "no TPU found" in p.stderr


def test_chip_smoke_alone_fails(tmp_path):
    (tmp_path / "chip_smoke.py").write_text(
        (REPO / "chip_smoke.py").read_text())
    env = {k: v for k, v in os.environ.items() if k != "PYTHONPATH"}
    env["JAX_PLATFORMS"] = "cpu"
    p = subprocess.run([sys.executable, "chip_smoke.py"], cwd=tmp_path,
                       capture_output=True, text=True, timeout=300, env=env)
    assert p.returncode != 0
    assert '"ok"' not in p.stdout


def test_chip_smoke_phases_at_smoke_size():
    cs = _load_chip_smoke()
    stats = cs.serve_phase(CFG, batch=2, prompt_len=32, gen=4, seed=1)
    assert stats["decode_tokens_per_s"] > 0
    out = cs.logits_phase(CFG, batch=2, prompt_len=32, gen=4, seed=1)
    assert out["prefill_rel_err"] <= cs.LOGITS_REL_TOL
    assert out["decode_rel_err"] <= cs.LOGITS_REL_TOL
    _, info = cs.train_phase(CFG, steps=2, batch=1, seq=32, seed=1)
    assert len(info["step_times_s"]) == 1


def test_chip_smoke_lines_are_json(capsys):
    cs = _load_chip_smoke()
    cs.emit("x", a=1)
    assert json.loads(capsys.readouterr().out) == {"phase": "x", "a": 1}
    with pytest.raises(cs.SmokeFailure):
        cs.require(False, "boom")
