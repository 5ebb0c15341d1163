"""The hybrid (Jamba) serve cell's own modules at a small size on the CPU:
the weight tree is the program's layout, the float32 reference is the
program's forward in float32, the FLOP and byte counts match a hand count,
the traced work reads the routing counter, and one untraced run of the
kind goes through the window and the check."""
import dataclasses
import json
import types

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import run
from conftest import CHIP

TINY = {"name": "tiny-jamba", "hidden_size": 64, "intermediate_size": 96,
        "num_attention_heads": 4, "num_key_value_heads": 2,
        "num_hidden_layers": 8, "vocab_size": 512, "rms_norm_eps": 1e-6,
        "sliding_window": None, "attn_layer_period": 8,
        "attn_layer_offset": 4, "expert_layer_period": 2,
        "expert_layer_offset": 1, "mamba_d_state": 8, "mamba_d_conv": 4,
        "mamba_expand": 2, "mamba_dt_rank": 4, "mamba_conv_bias": True,
        "mamba_proj_bias": False, "num_experts": 4,
        "num_experts_published": 8, "experts_held": [2, 6],
        "num_experts_per_tok": 2, "initializer_range": 0.1}
TRAFFIC = {"kind": "serve_static_hybrid", "batch": 2, "prompt_len": 20,
           "output_median": 6, "output_sigma": 1.0, "max_new_tokens": 10,
           "check_requests": 2}


@pytest.fixture(scope="module")
def kind():
    return run.load_module(CHIP / "kinds" / "serve_static_hybrid.py",
                           "kind_serve_static_hybrid")


@pytest.fixture(scope="module")
def hy(kind):
    return kind.hybrid


def test_weights_are_init_params_layout(hy):
    from repro.models import model as M
    cfg = hy.model.program_config(TINY)
    want = jax.eval_shape(lambda: M.init_params(jax.random.PRNGKey(0), cfg))
    got = hy.model.make_weights(TINY, 2**40 + 7)
    assert jax.tree.structure(got) == jax.tree.structure(want)
    for g, w in zip(jax.tree.leaves(got), jax.tree.leaves(want)):
        assert (g.shape, g.dtype) == (w.shape, w.dtype)
    moe = got["layers"][1]["moe"]
    assert moe["router"].shape == (1, 64, 8)         # scores all 8
    assert moe["w_gate"].shape == (1, 4, 64, 96)     # holds 4
    assert "attn" in got["layers"][4] and "ffn" in got["layers"][4]


def test_big_leaves_made_a_matrix_at_a_time(hy, monkeypatch):
    a = hy.model.make_weights(TINY, 11)
    monkeypatch.setattr(hy.model, "LEAF_BYTES", 1024)
    b = hy.model.make_weights(TINY, 11)
    for x, y in zip(jax.tree.leaves(a), jax.tree.leaves(b)):
        assert x.shape == y.shape
    w = np.asarray(b["layers"][3]["moe"]["w_up"], np.float32)
    assert 0.08 < w.std() < 0.12 and w[0, 0].std() != w[0, 1].std()


def _program_logits(hy, c, weights, tokens):
    from repro.models import model as M
    cfg = dataclasses.replace(hy.model.program_config(c),
                              param_dtype="float32")
    w32 = jax.tree.map(lambda a: a.astype(jnp.float32), weights)
    with jax.default_matmul_precision("highest"):
        logits, _, _ = M.forward(w32, cfg, tokens, remat=False)
    return np.asarray(logits[..., :c["vocab_size"]], np.float32)


def test_reference_matches_program_forward(hy):
    w = hy.model.make_weights(TINY, 2**35 + 3)
    tokens = hy.model.prompts(5, 0, 2, 40, TINY["vocab_size"])
    want = _program_logits(hy, TINY, w, jnp.asarray(tokens))
    got = np.asarray(hy.reference.logits(w, TINY, tokens, first=0))
    assert np.abs(got - want).max() <= 1e-4 * np.abs(want).max()
    # the control rounds every linear, the experts among them, to fp8
    ctl = np.asarray(hy.reference.logits(w, TINY, tokens, first=0,
                                         quant="fp8"))
    assert np.abs(ctl - got).max() > 1e-2 * np.abs(want).max()


def test_flops_by_hand(hy):
    f = hy.flops
    c = TINY
    assert f.layer_counts(c) == {"attn": 1, "mamba": 7, "moe": 4,
                                 "dense": 4}
    D, I, R, N, F = 64, 128, 4, 8, 96
    mamba = D * 2 * I + I * (R + 2 * N) + R * I + I * D
    assert f.mamba_matmul_params(c) == mamba
    # one token after 3 cached: 1 held assignment a token on average
    per_token = 2 * (1 * D * 16 * (2 * 4 + 2 * 2) + 7 * mamba + 4 * 3 * D * F
                     + 4 * (D * 8 + 1.0 * 3 * D * F))
    per_token += 7 * (2 * 4 * I + 6 * I * N)
    attn = 1 * 4 * 16 * 4 * 4
    assert f.forward_flops(c, 1, 1, 1.0, past=3) == per_token + attn + \
        2 * D * 512
    static = (4 * D * 17 + 2 * D * 16 * 12 + 7 * (2 * (mamba + 4 * I + 3 * I)
                                                  + 4 * (I * N + R + 2 * N))
              + 2 * 4 * 3 * D * F + 4 * 4 * D * 8 + 2 * 512 * D)
    assert f.static_weight_bytes(c) == static
    state = 2 * 7 * 3 * (2 * 3 * I + 4 * I * N)
    kv = 2 * 3 * 1 * 2 * 2 * 16 * 10
    assert f.decode_step_bytes(c, 3, 10, 2.5) == pytest.approx(
        static + 2.5 * 2 * 3 * D * F + kv + state)


def test_traced_work_reads_the_counter(kind, hy):
    B, P = TRAFFIC["batch"], TRAFFIC["prompt_len"]
    G = int(kind.static.answer_lengths(TRAFFIC).max())
    counts = np.zeros((4, 4, 3), np.int64)    # 4 MoE layers, 4 held
    counts[..., 0] = B * P // 4               # half of 2 B P assignments
    counts[..., 1] = B * (G - 1) // 4
    counts[0, :, 2] = G - 1                   # layer 0: all 4 hit a step
    counts[1:, 0, 2] = G - 1                  # others: one expert a step
    w = kind.traced_work(TINY, TRAFFIC, hy.flops, counts)
    assert w["experts_hit_per_step"] == 4 + 3
    f = hy.flops
    assert w["decode_bytes"] == pytest.approx(sum(
        f.decode_step_bytes(TINY, B, P + k, 7) for k in range(1, G)))
    assert w["moe_decode_bytes"] == pytest.approx(
        7 * (G - 1) * f.expert_bytes(TINY) + (G - 1) * 4 * 4 * 64 * 8)
    assert w["kernels"]["flash_attention"] == [(*f.flash_fwd(B, P, 4, 2, 16),
                                                1)]
    assert len(w["kernels"]["decode_attention"]) == G - 1
    assert w["model_flops"] > f.forward_flops(TINY, B, P, 1.0)


def test_limits_file():
    lim = json.loads((CHIP / "limits" / "jamba-chat.json").read_text())
    assert set(lim) == {"mean_logit_gap", "bad_requests"}
    assert lim["bad_requests"] == 0 and 0 < lim["mean_logit_gap"] < 1


def test_one_run_through_the_window(kind, mods):
    cell = types.SimpleNamespace(name="tiny", config=TINY, traffic=TRAFFIC,
                                 seed=2**33 + 9, seconds=0.3, chips=1,
                                 trace_dir=None)
    res = kind.run(cell, mods)
    assert res["compiles_in_window"] == 0
    assert res["e2e"]["serve_tokens_per_s"] > 0
    assert res["checks"]["bad_requests"] == 0
    chk = res["checks"]
    assert chk["checked_tokens"] == 2 * 10
    assert 0 <= chk["mean_logit_gap"] <= chk["max_logit_gap"]
    assert chk["mean_logit_gap"] < 0.4          # jamba-chat's limit


def test_gaps(hy):
    ref = jnp.asarray([[[0.0, 3.0, 1.0], [2.0, 0.5, 0.0]]])
    g = hy.reference.gaps(ref, np.asarray([[2, 1]]))
    np.testing.assert_allclose(g, [[2.0, 1.5]])


def _reading(hy, scope_s, module_s, work):
    scopes = run.load_module(CHIP / "scopes.py", "bench_scopes")
    work = dict(work, scopes=scopes.Reduced(scope_s=scope_s,
                                            idle_gap_hosts=[]))
    return types.SimpleNamespace(
        reduced=types.SimpleNamespace(module_s=module_s), work=work,
        peaks={"hbm_bytes_per_s": 1e9})


def test_new_readers():
    mbu = run.load_module(CHIP / "metrics" / "moe_decode_mbu.py", "m_mbu")
    scan = run.load_module(CHIP / "metrics" / "mamba_scan_ms.py", "m_scan")
    r = _reading(None, {
        "jit_serve_step": {"mlp/moe/experts": 0.3, "mlp/moe/route": 0.1,
                           "mlp": 5.0, "mlp/moe": 0.1},
        "jit_prefill": {"mamba/scan": 0.2, "mamba/in_proj": 1.0}},
        {"jit_serve_step": (9.0, 255), "jit_prefill": (1.5, 2)},
        {"moe_decode_bytes": 2.5e8})
    assert mbu.read(r) == pytest.approx(100 * 2.5e8 / 0.5 / 1e9)
    assert scan.read(r) == pytest.approx(1e3 * 0.2 / 2)
    # the other kinds give neither scopes nor MoE bytes: nothing to read
    bare = types.SimpleNamespace(work={"decode_bytes": 1}, reduced=r.reduced,
                                 peaks=r.peaks)
    assert mbu.read(bare) is None and scan.read(bare) is None


def test_routers_are_balanced(hy):
    """Over the seeded calibration batch, every router gives all experts
    the same mean logit (0) and the same spread, with no two experts'
    logits correlated: the direction its input shares across tokens is
    projected out of it and its columns are whitened; without that, a
    random router favours a few experts, or half of them, by the seed's
    draw."""
    seed = 2**34 + 1
    w = hy.model.make_weights(TINY, seed)
    n, T = hy.model.CALIBRATION
    tokens = np.random.default_rng([seed, 4]).integers(
        0, TINY["vocab_size"], (n, T), dtype=np.int32)
    means, spread, covs = [], [], []

    def look(i, x):
        lw = w["layers"][i % 8]
        h = hy.reference._normed(x, lw["ln2"], 0, eps=1e-6)[:n * T]
        logits = h @ lw["moe"]["router"][0]
        means.append(np.abs(np.asarray(logits.mean(0))).max())
        spread.append(float(logits.std()))
        z = np.asarray(logits - logits.mean(0), np.float64)
        covs.append(z.T @ z / len(z))
    with jax.default_matmul_precision("highest"):
        hy.reference.residual(w, TINY, tokens, before_moe=look)
    assert len(means) == 4
    assert max(m / s for m, s in zip(means, spread)) < 1e-4
    for cov in covs:               # correlation ~ I; variances alike
        sd = np.sqrt(np.diag(cov))
        np.testing.assert_allclose(cov / np.outer(sd, sd), np.eye(len(sd)),
                                   atol=1e-3)
        assert sd.max() / sd.min() < 1.001
