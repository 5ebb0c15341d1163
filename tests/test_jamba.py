"""Jamba (AI21-Jamba2-Mini's block) through the serving path, against the
float32 reference `models/jamba_ref.py`, and that reference against
`transformers`' own `JambaForCausalLM`.

All at a tiny size on the CPU with seeded random weights: d 64, 4 q / 2 kv
heads of 16, one period of 8 layers (attention at position 4), 16 experts
of width 32, top-2, Mamba d_state 8.  The weights are drawn with std
1/sqrt(d) so that every part moves the logits (std ~1)."""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import ALL_ARCHS, get_config
from repro.configs.base import MambaConfig
from repro.models import jamba_ref as R
from repro.models import layers as L
from repro.models import model as M

JAMBA = get_config("jamba-v0.1-52b")
TINY = dataclasses.replace(
    JAMBA, name="jamba-tiny", d_model=64, num_heads=4, num_kv_heads=2,
    head_dim=16, d_ff=32, vocab_size=256, num_layers=8,
    mamba=MambaConfig(d_state=8, d_conv=4, expand=2),
    moe=dataclasses.replace(JAMBA.moe, num_experts=16, d_ff=32))
# the program computing in float32 (weights, activations and caches), so
# that it can be held to the reference's own precision
TINY32 = dataclasses.replace(TINY, param_dtype="float32")
DT_RANK, D_STATE = 4, 8         # dt_rank = d / 16
# Float32 rounding through 8 layers and the scans puts the system 1.2e-5
# from the reference (logits of std ~1); a bfloat16 step anywhere moves
# them by bf16's resolution, ~4e-3 of their size, and a dropped dt/B/C
# norm by ~1 (both asserted below).
TOL = 1e-4
B, S = 2, 24
reference = jax.jit(R.logits, static_argnums=(1,))


def tiny_params(cfg, seed):
    """`init_params`' tree with random norms and conv bias, and matrices
    of std 1/8 (= 1/sqrt(d)); A_log, dt_bias and D as initialised."""
    p = M.init_params(jax.random.PRNGKey(seed), cfg)
    flat, tdef = jax.tree_util.tree_flatten_with_path(p)
    keys = jax.random.split(jax.random.PRNGKey(seed + 1), len(flat))
    out = []
    for k, (path, a) in zip(keys, flat):
        name = jax.tree_util.keystr(path)
        if any(n in name for n in ("ln1", "ln2", "_norm", "conv_b")):
            a = (0.1 * jax.random.normal(k, a.shape)).astype(a.dtype)
        elif a.ndim >= 2 and "A_log" not in name and "conv_w" not in name:
            a = (0.125 * jax.random.normal(k, a.shape)).astype(a.dtype)
        out.append(a)
    return jax.tree.unflatten(tdef, out)


@pytest.fixture(scope="module")
def weights():
    return tiny_params(TINY32, 0)


@pytest.fixture(scope="module")
def tokens():
    return jax.random.randint(jax.random.PRNGKey(5), (B, S), 0,
                              TINY.vocab_size)


@pytest.fixture(scope="module")
def ref(weights, tokens):
    return np.asarray(reference(weights, TINY32, tokens))


def _err(logits, ref):
    return float(np.abs(np.asarray(logits, np.float32)[..., :ref.shape[-1]]
                        - ref).max())


def _prefill(params, cfg, toks, max_len):
    """Prefill through `init_caches` and the Pallas kernels (interpreted on
    the CPU), every position's logits kept."""
    caches = M.init_caches(cfg, toks.shape[0], max_len,
                           dtype=jnp.dtype(cfg.param_dtype))
    logits, _, caches = M.forward(params, cfg, toks, caches=caches,
                                  use_pallas=True, remat=False)
    return logits, caches


def test_block_layout_is_published():
    # attention at i % 8 == 4 (dense MLP there), MoE at odd positions
    specs = M.block_specs(JAMBA)
    assert [s["kind"] for s in specs] == ["mamba"] * 4 + ["attn"] + \
        ["mamba"] * 3
    assert [s["ffn"] for s in specs] == ["dense", "moe"] * 4
    assert not JAMBA.use_rope and not JAMBA.moe.renormalize


def _old_block_specs(cfg):
    """`block_specs` as it was before attention could sit anywhere in a
    period: attention last."""
    P = M.period_of(cfg)
    out = []
    for pos in range(P):
        if cfg.rwkv:
            out.append({"kind": "rwkv", "ffn": "rwkv"})
            continue
        if cfg.encoder_layers:
            out.append({"kind": "attn", "ffn": "dense", "cross": True})
            continue
        if cfg.attn_every > 1:
            kind = "attn" if pos == P - 1 else "mamba"
        elif cfg.cross_attn_every and pos == P - 1:
            kind = "xattn"
        else:
            kind = "attn"
        moe = cfg.moe is not None and pos % cfg.moe_every == cfg.moe_every - 1
        out.append({"kind": kind, "ffn": "moe" if moe else "dense"})
    return out


@pytest.mark.parametrize("arch", [a for a in ALL_ARCHS
                                  if a != "jamba-v0.1-52b"])
def test_block_specs_unchanged_for_other_configs(arch):
    cfg = get_config(arch)
    assert M.block_specs(cfg) == _old_block_specs(cfg)


def test_prefill_matches_reference(weights, tokens, ref):
    logits, _ = _prefill(weights, TINY32, tokens, S)
    assert _err(logits, ref) < TOL


def test_decode_matches_reference(weights, tokens, ref):
    """Prefill 16 tokens, then decode 8 through the caches: each step's
    logits are the reference's full forward at that position."""
    P = 16
    _, caches = _prefill(weights, TINY32, tokens[:, :P], S)
    step = jax.jit(lambda p, t, c: M.decode_step(p, TINY32, t, c,
                                                 use_pallas=True))
    errs = []
    for t in range(P, S):
        logits, caches = step(weights, tokens[:, t:t + 1], caches)
        errs.append(_err(logits[:, 0], ref[:, t]))
    assert max(errs) < TOL, errs


def test_tolerance_catches_bf16_and_a_dropped_norm(weights, tokens, ref,
                                                   monkeypatch):
    # logits rounded once to bfloat16 already miss the tolerance
    bf16 = np.asarray(jnp.asarray(ref).astype(jnp.bfloat16), np.float32)
    assert np.abs(bf16 - ref).max() > 10 * TOL
    # the mixer without its dt, B and C norms misses it by far
    norm = L.rms_norm
    monkeypatch.setattr(L, "rms_norm", lambda x, s, eps=1e-5: x if
                        x.shape[-1] in (DT_RANK, D_STATE) else norm(x, s, eps))
    logits, _ = _prefill(weights, TINY32, tokens, S)
    assert _err(logits, ref) > 1e3 * TOL


def test_served_dtype_stays_near_reference(tokens):
    """The program as served, bf16 weights and caches: prefill, and decode
    through the caches, agree with each other to within bf16's rounding on
    their two paths (the chunked scan and flash against the one-token
    update and the decode kernel; measured 0.16 of logits of std 1), and
    with the float32 reference to within bf16's drift over 8 layers
    (measured 0.2-0.8)."""
    params = tiny_params(TINY, 0)
    ref = np.asarray(reference(params, TINY, tokens))
    full, _ = _prefill(params, TINY, tokens, S)
    _, caches = _prefill(params, TINY, tokens[:, :S - 4], S)
    step = jax.jit(lambda p, t, c: M.decode_step(p, TINY, t, c,
                                                 use_pallas=True))
    for t in range(S - 4, S):
        logits, caches = step(params, tokens[:, t:t + 1], caches)
        assert _err(logits[:, 0], np.asarray(full[:, t], np.float32)) < 0.3
    assert _err(full, ref) < 1.5
    assert np.corrcoef(np.asarray(full, np.float32)[..., :256].ravel(),
                       ref.ravel())[0, 1] > 0.95


def test_moe_counter(weights, tokens):
    """Prefill sets each MoE layer's counter to the tokens routed to each
    held expert; each decode step adds its tokens and, where an expert got
    any, one step."""
    _, caches = _prefill(weights, TINY32, tokens[:, :20], S)
    counts = [c["moe"] for c in caches["layers"] if "moe" in c]
    assert len(counts) == 4
    for c in counts:        # (periods, experts, 3): top-2 of every token
        assert c.shape == (1, 16, 3)
        assert int(c[..., 0].sum()) == 2 * B * 20
        assert int(c[..., 1:].sum()) == 0
    before = [np.asarray(c) for c in counts]
    step = jax.jit(lambda p, t, c: M.decode_step(p, TINY32, t, c,
                                                 use_pallas=True))
    for t in range(20, 23):
        _, caches = step(weights, tokens[:, t:t + 1], caches)
    for c, c0 in zip((c["moe"] for c in caches["layers"] if "moe" in c),
                     before):
        c = np.asarray(c)
        assert (c[..., 0] == c0[..., 0]).all()
        assert c[..., 1].sum() == 3 * 2 * B
        assert (c[..., 2] <= np.minimum(c[..., 1], 3)).all()
        assert c[..., 2].sum() >= 3 * 2        # >= 2 experts a step


def _share(e, lo, hi):
    return {k: (v if k == "router" else v[lo:hi]) for k, v in e.items()}


def test_expert_shares_sum_to_the_whole_layer(weights):
    """The held experts 0-7 and 8-15, each computed by the program's
    grouped layer, add up to the uncut reference's whole layer."""
    e = jax.tree.map(lambda a: a[0], weights["layers"][1]["moe"])
    x = jax.random.normal(jax.random.PRNGKey(3), (B, S, TINY.d_model))
    whole = np.asarray(R.moe(e, x, TINY32.moe))
    parts = []
    for lo, hi in ((0, 8), (8, 16)):
        m = dataclasses.replace(TINY32.moe, held=(lo, hi))
        with jax.default_matmul_precision("highest"):
            out, _, routed = L.moe_ffn(_share(e, lo, hi), x, m)
        assert routed.shape == (8,)
        parts.append(np.asarray(out))
    np.testing.assert_allclose(parts[0] + parts[1], whole, atol=1e-5)
    assert np.abs(parts[0]).max() > 0.1 and np.abs(parts[1]).max() > 0.1


def test_grouped_rows_in_slices(weights, monkeypatch):
    # groups that straddle the row slices of a long prefill give the same
    # result as one slice
    e = jax.tree.map(lambda a: a[0], weights["layers"][3]["moe"])
    x = jax.random.normal(jax.random.PRNGKey(4), (B, S, TINY.d_model))
    m = dataclasses.replace(TINY32.moe, held=(4, 12))
    e = _share(e, 4, 12)
    one, _, r1 = L.moe_ffn(e, x, m)
    monkeypatch.setattr(L, "MOE_ROWS", 7)
    sliced, _, r2 = L.moe_ffn(e, x, m)
    np.testing.assert_allclose(np.asarray(sliced), np.asarray(one),
                               atol=1e-5)
    assert (np.asarray(r1) == np.asarray(r2)).all()


@pytest.mark.parametrize("S_,chunk", [(37, 16), (32, 16), (5, 16), (1, 16)])
def test_bounded_scan_is_the_per_token_recurrence(S_, chunk):
    ks = jax.random.split(jax.random.PRNGKey(S_), 6)
    n, I, N = 2, 12, 4
    dt = jax.nn.softplus(jax.random.normal(ks[0], (n, S_, I)) - 1.0)
    A = -jnp.exp(jax.random.normal(ks[1], (I, N)))
    Bm, Cm = (jax.random.normal(k, (n, S_, N)) for k in ks[2:4])
    x = jax.random.normal(ks[4], (n, S_, I))
    h0 = jax.random.normal(ks[5], (n, I, N))
    with jax.default_matmul_precision("highest"):
        y, h = L._mamba_ssm_chunked(dt, A, Bm, Cm, x, h0, chunk=chunk)
        y_ref, h_ref = R.selective_scan(dt, A, Bm, Cm, x, h0)
    np.testing.assert_allclose(np.asarray(y), np.asarray(y_ref), rtol=1e-5,
                               atol=1e-5)
    np.testing.assert_allclose(np.asarray(h), np.asarray(h_ref), rtol=1e-5,
                               atol=1e-5)


def test_prefill_kernel_is_the_xla_scan(weights):
    """Prefill through the Pallas kernels (interpreted) equals prefill
    through the chunked scan and XLA attention, to float32 rounding through
    8 layers: logits, and each Mamba layer's conv and ssm state, at a prompt
    of one whole block and a partial one."""
    toks = jax.random.randint(jax.random.PRNGKey(6), (B, 140), 0,
                              TINY.vocab_size)
    out = []
    for use_pallas in (True, False):
        caches = M.init_caches(TINY32, B, 144, dtype=jnp.float32)
        logits, _, caches = M.forward(weights, TINY32, toks, caches=caches,
                                      use_pallas=use_pallas, remat=False)
        out.append((logits, [c["mamba"] for c in caches["layers"]
                             if "mamba" in c]))
    (lk, mk), (lx, mx) = out
    assert len(mk) == 7
    assert _err(lk, np.asarray(lx, np.float32)) < TOL
    for a, b in zip(mk, mx):
        for k in ("conv", "ssm"):
            np.testing.assert_allclose(np.asarray(a[k]), np.asarray(b[k]),
                                       rtol=1e-5, atol=1e-5)


def test_kernel_scan_gradient_is_the_xla_scans(weights, tokens):
    """A training loss differentiates through the kernel's custom_vjp: its
    gradient with `use_pallas=True` is the XLA path's."""
    targets = jnp.roll(tokens, -1, axis=1)

    def loss(params, use_pallas):
        logits, _, _ = M.forward(params, TINY32, tokens,
                                 use_pallas=use_pallas)
        logp = jax.nn.log_softmax(logits[..., :TINY.vocab_size], -1)
        return -jnp.mean(jnp.take_along_axis(logp, targets[..., None], -1))
    grads = [jax.jit(jax.grad(loss), static_argnums=1)(weights, p)
             for p in (True, False)]
    flat_k, flat_x = (jax.tree_util.tree_leaves_with_path(g) for g in grads)
    mamba = [k for k, _ in flat_k if "mamba" in jax.tree_util.keystr(k)]
    assert mamba
    for (path, a), (_, b) in zip(flat_k, flat_x):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), rtol=1e-4,
                                   atol=1e-5, err_msg=jax.tree_util.keystr(
                                       path))


def _hf_model(params, cfg):
    """`transformers`' JambaForCausalLM at `cfg`'s sizes, float32, holding
    `params` (the program's layout) copied in."""
    torch = pytest.importorskip("torch")
    tf = pytest.importorskip("transformers")
    m = cfg.mamba
    hc = tf.JambaConfig(
        vocab_size=cfg.vocab_size, hidden_size=cfg.d_model,
        intermediate_size=cfg.d_ff, num_hidden_layers=cfg.num_layers,
        num_attention_heads=cfg.num_heads,
        num_key_value_heads=cfg.num_kv_heads, rms_norm_eps=cfg.norm_eps,
        num_experts=cfg.moe.num_experts, num_experts_per_tok=cfg.moe.top_k,
        expert_layer_period=2, expert_layer_offset=1, attn_layer_period=8,
        attn_layer_offset=4, use_mamba_kernels=False,
        mamba_d_state=m.d_state, mamba_d_conv=m.d_conv,
        mamba_expand=m.expand, mamba_dt_rank=DT_RANK,
        tie_word_embeddings=False, attn_implementation="eager")
    model = tf.JambaForCausalLM(hc).float().eval()
    P = len(params["layers"])

    def t(a):
        return torch.tensor(np.asarray(a, np.float32))

    def lin(mod, w):               # ours (in, out), torch's (out, in)
        mod.weight.data = t(w).T.contiguous()

    def norm(mod, s):              # ours 1 + s
        mod.weight.data = 1.0 + t(s)

    def mlp(mod, f):
        for n in ("gate", "up", "down"):
            lin(getattr(mod, f"{n}_proj"), f[f"w_{n}"])

    D = cfg.d_model
    model.model.embed_tokens.weight.data = t(params["embed"][:cfg.vocab_size])
    model.lm_head.weight.data = t(params["lm_head"][:cfg.vocab_size])
    norm(model.model.final_layernorm, params["final_norm"])
    for i, layer in enumerate(model.model.layers):
        p = jax.tree.map(lambda a: a[i // P], params["layers"][i % P])
        norm(layer.input_layernorm, p["ln1"])
        norm(layer.pre_ff_layernorm, p["ln2"])
        if "attn" in p:
            a = p["attn"]
            for n in ("q", "k", "v"):
                w = a[f"w{n}"]
                lin(getattr(layer.self_attn, f"{n}_proj"),
                    w.reshape(D, -1))
            lin(layer.self_attn.o_proj, a["wo"].reshape(-1, D))
        else:
            mx, mm = layer.mamba, p["mamba"]
            for n in ("in_proj", "x_proj", "out_proj"):
                lin(getattr(mx, n), mm[n])
            lin(mx.dt_proj, mm["dt_proj"])
            mx.dt_proj.bias.data = t(mm["dt_bias"])
            mx.conv1d.weight.data = t(mm["conv_w"]).T[:, None, :].contiguous()
            mx.conv1d.bias.data = t(mm["conv_b"])
            mx.A_log.data = t(mm["A_log"])
            mx.D.data = t(mm["Dskip"])
            norm(mx.dt_layernorm, mm["dt_norm"])
            norm(mx.b_layernorm, mm["b_norm"])
            norm(mx.c_layernorm, mm["c_norm"])
        if "moe" in p:
            e = p["moe"]
            lin(layer.feed_forward.router, e["router"])
            for j, ex in enumerate(layer.feed_forward.experts):
                mlp(ex, {k: e[k][j] for k in ("w_gate", "w_up", "w_down")})
        else:
            mlp(layer.feed_forward, p["ffn"])
    return model


def test_reference_is_transformers_jamba(weights, tokens, ref):
    torch = pytest.importorskip("torch")
    model = _hf_model(weights, TINY32)
    with torch.no_grad():
        out = model(torch.tensor(np.asarray(tokens)), use_cache=False)
    hf = out.logits.numpy()
    assert hf.shape == ref.shape
    assert np.abs(hf - ref).max() < TOL
