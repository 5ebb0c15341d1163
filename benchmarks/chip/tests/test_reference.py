"""The float32 reference against the program's own forward pass, both in
float32 on the same seeded weights, at small sizes on the CPU: they agree
to float32 rounding, so each computes the published block.  Also: the
reference's attention blocks and the fp8 control."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from conftest import tiny


def _program_logits(mods, c, weights, tokens):
    from repro.models import model as M
    cfg = mods.model.program_config(c)
    w32 = jax.tree.map(lambda a: a.astype(jnp.float32), weights)
    with jax.default_matmul_precision("highest"):
        logits, _, _ = M.forward(w32, cfg, tokens, use_pallas=False,
                                 remat=False)
    return np.asarray(logits[..., :c["vocab_size"]], np.float32)


@pytest.mark.parametrize("over", [
    {},                                             # danube's form: window
    {"sliding_window": None, "rope_theta": 1e5,     # deepseek's form
     "rms_norm_eps": 1e-6, "num_key_value_heads": 1},
], ids=["windowed-gqa4", "full-gqa8"])
def test_reference_matches_program_forward(mods, over):
    c = tiny(**over)
    w = mods.model.make_weights(c, 2**35 + 3)
    tokens = mods.model.prompts(5, 0, 2, 96, c["vocab_size"])
    want = _program_logits(mods, c, w, jnp.asarray(tokens))
    got = np.asarray(mods.reference.logits(w, c, tokens, first=0))
    scale = np.abs(want).max()
    assert np.abs(got - want).max() <= 1e-4 * scale


def test_attention_blocks_cover_all_rows(mods, monkeypatch):
    c = tiny()
    w = mods.model.make_weights(c, 7)
    tokens = mods.model.prompts(7, 0, 1, 70, c["vocab_size"])
    whole = np.asarray(mods.reference.logits(w, c, tokens, first=0))
    monkeypatch.setattr(mods.reference, "Q_BLOCK", 16)   # 70 = 4 x 16 + 6
    mods.reference._layer.clear_cache()
    blocked = np.asarray(mods.reference.logits(w, c, tokens, first=0))
    mods.reference._layer.clear_cache()
    np.testing.assert_allclose(blocked, whole, rtol=1e-5, atol=1e-5)


def test_widest_gap(mods):
    ref = jnp.asarray([[[0.0, 3.0, 1.0], [2.0, 0.5, 0.0]]])
    gaps = [mods.reference.widest_gap(ref, np.asarray(t))
            for t in ([[1, 0]], [[2, 1]])]
    np.testing.assert_allclose(gaps, [0.0, 2.0])


def test_fp8_rounding_is_coarser_than_bf16(mods):
    x = jax.random.normal(jax.random.PRNGKey(0), (64, 256))
    e8 = jnp.abs(mods.reference._f8(x, -1) - x).max() / jnp.abs(x).max()
    e16 = jnp.abs(x.astype(jnp.bfloat16).astype(jnp.float32) - x).max() \
        / jnp.abs(x).max()
    assert e8 > 4 * e16
