"""`correct` on the timed path at a small size on the CPU (kernels
interpreted), judged against `danube-decode`'s limits.

The weights have std 0.1 here, so that the logits spread as widely as at
the cells' own sizes (std ~1.6): the compared number is a gap in logits.

- the sound program is correct;
- the control, the reference in float8 put in the program's place, is not;
- a run with the timed path broken underneath is not, for each fault a
  one-chip serve cell can have: a decode step that returns its cache
  unchanged, half of the batch left out (its rows answered with the other
  half's tokens), and one token altered where it is produced.
"""
import math
import types

import jax
import jax.numpy as jnp
import numpy as np

import pytest

import calibrate
import run
from conftest import CHIP, tiny

C = tiny(initializer_range=0.1)
TRAFFIC = {"kind": "serve_static", "batch": 4, "prompt_len": 60,
           "output_median": 12, "output_sigma": 1.0, "max_new_tokens": 24,
           "check_requests": 4}
LIMITS = run.load_json(CHIP / "limits" / "danube-decode.json")
SEEDS = [2**33 + 1, 2, 3]


def drive(kind, mods, seed):
    cell = types.SimpleNamespace(name="tiny", config=C, traffic=TRAFFIC,
                                 seed=seed, seconds=0.3, chips=1,
                                 trace_dir=None)
    res = kind.run(cell, mods)
    return run.judge(res["checks"], LIMITS)


def plant(monkeypatch, kind, wrap):
    """Wrap the compiled decode step that the window drives."""
    init = kind.Server.__init__

    def broken_init(self, *a, **kw):
        init(self, *a, **kw)
        self.step = wrap(self.step)
    monkeypatch.setattr(kind.Server, "__init__", broken_init)


@pytest.mark.parametrize("seed", SEEDS)
def test_sound_program_is_correct(kind, mods, seed):
    ok, checks = drive(kind, mods, seed)
    assert ok, checks


@pytest.mark.parametrize("seed", SEEDS)
def test_fp8_control_is_not_correct(kind, mods, seed):
    params = mods.model.make_weights(C, seed)
    server = kind.Server(C, TRAFFIC, params, model=mods.model)
    server.warm()
    src = kind.prompt_source(seed, TRAFFIC["batch"], TRAFFIC["prompt_len"],
                              C["vocab_size"], mods.model)
    batch = server.serve_batch(0, next(src),
                               kind.batch_lengths(TRAFFIC, seed, 0))
    rows, _ = kind.sample_rows([batch], seed, TRAFFIC, C, mods.model)
    gap = calibrate.control_gap(mods, C, params, rows,
                                TRAFFIC["prompt_len"] - 1)
    ok, checks = run.judge({"max_logit_gap": gap, "bad_requests": 0},
                           LIMITS)
    assert not ok, checks


def _cache_unchanged(step):
    def f(p, c, t):
        kept = jax.tree.map(jnp.copy, c)     # the step donates its cache
        return step(p, c, t)[0], kept
    return f


def _half_batch(step):
    def f(p, c, t):
        tok, c = step(p, c, t)
        h = tok.shape[0] // 2
        return tok.at[h:].set(tok[:h]), c
    return f


def _one_token_altered(step):
    calls = []

    def f(p, c, t):
        tok, c = step(p, c, t)
        calls.append(1)
        if len(calls) == 7:          # the third window step of the batch
            tok = tok.at[0, 0].set((tok[0, 0] + 1) % C["vocab_size"])
        return tok, c
    return f


@pytest.mark.parametrize("fault", [_cache_unchanged, _half_batch,
                                   _one_token_altered],
                         ids=["cache-unchanged", "half-batch",
                              "token-altered"])
def test_broken_timed_path_is_not_correct(kind, mods, monkeypatch, fault):
    plant(monkeypatch, kind, fault)
    ok, checks = drive(kind, mods, SEEDS[0])
    assert not ok, checks
    assert checks["max_logit_gap"]["value"] > LIMITS["max_logit_gap"]


def test_window_close_sends_no_more_steps(kind, mods):
    """A batch whose deadline has passed sends only its first token in the
    window and serves the rest after it, the same tokens as without one."""
    seed = SEEDS[1]
    params = mods.model.make_weights(C, seed)
    server = kind.Server(C, TRAFFIC, params, model=mods.model)
    server.warm()
    src = kind.prompt_source(seed, TRAFFIC["batch"], TRAFFIC["prompt_len"],
                             C["vocab_size"], mods.model)
    prompts, lengths = next(src), kind.batch_lengths(TRAFFIC, seed, 0)
    whole = server.serve_batch(0, prompts, lengths)
    cut = server.serve_batch(0, prompts, lengths, deadline=-math.inf)
    assert (whole.n_window, cut.n_window) == (server.G, 1)
    assert (whole.tokens == cut.tokens).all()
    assert (np.diff(cut.times) >= 0).all()
