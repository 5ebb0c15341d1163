"""Operations and bytes of the hybrid block (Jamba), from shapes alone.

As `flops.py` (whose attention kernels' counts it reuses): what the
mathematics requires, no padding, no upcast, no recomputation; bf16 is 2
bytes; one multiply-add is 2 FLOPs.  Which layers are what comes from the
configuration's periods: attention where i % attn_layer_period ==
attn_layer_offset, Mamba elsewhere; the MoE where i % expert_layer_period ==
expert_layer_offset, a dense SwiGLU elsewhere.  An MoE layer's work depends
on the routing: `held_per_token` is the mean number of a token's top-k
assignments that go to the experts this chip holds (the program's routing
counter gives it), and a decode step reads the held experts that got a
token in it.
"""
from __future__ import annotations

import importlib.util
import pathlib
import sys

BF16, F32 = 2, 4


def _dense_flops():
    """`flops.py`, by path as `run.py` loads it."""
    if "bench_flops" not in sys.modules:
        path = pathlib.Path(__file__).resolve().parents[1] / "flops.py"
        spec = importlib.util.spec_from_file_location("bench_flops", path)
        mod = importlib.util.module_from_spec(spec)
        sys.modules["bench_flops"] = mod
        spec.loader.exec_module(mod)
    return sys.modules["bench_flops"]


_flops = _dense_flops()
causal_pairs, flash_fwd, decode_attn = (
    _flops.causal_pairs, _flops.flash_fwd, _flops.decode_attn)


def layer_counts(c: dict) -> dict:
    """How many layers of each kind: attn, mamba, moe, dense."""
    L = c["num_hidden_layers"]
    attn = sum(i % c["attn_layer_period"] == c["attn_layer_offset"]
               for i in range(L))
    moe = sum(i % c["expert_layer_period"] == c["expert_layer_offset"]
              for i in range(L))
    return {"attn": attn, "mamba": L - attn, "moe": moe, "dense": L - moe}


def dims(c: dict) -> tuple:
    D = c["hidden_size"]
    I = c["mamba_expand"] * D
    return (D, I, c["mamba_dt_rank"], c["mamba_d_state"], c["mamba_d_conv"],
            c["intermediate_size"])


def head(c: dict) -> tuple:
    H, K = c["num_attention_heads"], c["num_key_value_heads"]
    return H, K, c.get("head_dim") or c["hidden_size"] // H


def attn_params(c: dict) -> int:
    H, K, d = head(c)
    return c["hidden_size"] * d * (2 * H + 2 * K)


def mamba_matmul_params(c: dict) -> int:
    """in_proj, x_proj, dt_proj and out_proj: what a token multiplies
    through in one mixer."""
    D, I, R, N, _, _ = dims(c)
    return D * 2 * I + I * (R + 2 * N) + R * I + I * D


def expert_params(c: dict) -> int:
    """One expert's (or the dense MLP's) SwiGLU."""
    return 3 * c["hidden_size"] * c["intermediate_size"]


def forward_flops(c: dict, batch: int, seq: int, held_per_token: float,
                  past: int = 0, logits_per_row: int = 1) -> float:
    """Model FLOPs of a forward over `seq` new tokens per row after `past`
    cached ones: the matmuls (the router over every published expert, the
    held experts for `held_per_token` assignments a token), the causal
    conv, the selective scan (exp(dt A) h + dt B x, C . h: 6 FLOPs an
    (i, n) a token), attention over the attended positions, and the output
    head for the `logits_per_row` positions used."""
    D, I, R, N, dc, F = dims(c)
    n = layer_counts(c)
    H, K, d = head(c)
    E = c["num_experts_published"]
    w = c.get("sliding_window")
    per_token = 2 * (n["attn"] * attn_params(c)
                     + n["mamba"] * mamba_matmul_params(c)
                     + n["dense"] * expert_params(c)
                     + n["moe"] * (D * E + held_per_token * expert_params(c)))
    per_token += n["mamba"] * (2 * dc * I + 6 * I * N)
    pairs = causal_pairs(past + seq, w) - causal_pairs(past, w)
    attn = n["attn"] * 4 * d * H * pairs
    out = 2 * D * c["vocab_size"] * logits_per_row
    return batch * (per_token * seq + attn + out)


def static_weight_bytes(c: dict) -> int:
    """Bytes of the weights every decode step reads, whatever the routing:
    all but the experts (attention, the mixers with their f32 A_log, the
    dense MLPs, the f32 routers, the output head; the embedding is
    gathered, B rows)."""
    D, I, R, N, dc, F = dims(c)
    n = layer_counts(c)
    # conv weight and bias, dt_bias and D in bf16; A_log and the dt, B
    # and C norms in f32
    mixer = (BF16 * (mamba_matmul_params(c) + dc * I + 3 * I)
             + F32 * (I * N + R + 2 * N))
    norms = F32 * D * (2 * c["num_hidden_layers"] + 1)
    return (norms + n["attn"] * BF16 * attn_params(c) + n["mamba"] * mixer
            + n["dense"] * BF16 * expert_params(c)
            + n["moe"] * F32 * D * c["num_experts_published"]
            + BF16 * c["vocab_size"] * D)


def expert_bytes(c: dict) -> int:
    return BF16 * expert_params(c)


def state_bytes(c: dict, batch: int) -> int:
    """The Mamba state a decode step reads and writes: each mixer's conv
    inputs (bf16) and ssm state (f32), twice."""
    D, I, R, N, dc, F = dims(c)
    per = batch * (BF16 * (dc - 1) * I + F32 * I * N)
    return 2 * layer_counts(c)["mamba"] * per


def decode_step_bytes(c: dict, batch: int, attended: int,
                      experts_hit: float) -> float:
    """HBM bytes a decode step needs: the static weights, `experts_hit`
    held experts (over all MoE layers) once each, each attention layer's
    K and V of the attended positions once (the new slot among them), and
    the Mamba state read and written."""
    H, K, d = head(c)
    kv = BF16 * batch * layer_counts(c)["attn"] * 2 * K * d * attended
    return (static_weight_bytes(c) + experts_hit * expert_bytes(c) + kv
            + state_bytes(c, batch))
