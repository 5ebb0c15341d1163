"""Operations and bytes the algorithm needs, from shapes alone.

These count what the mathematics requires of a call, never what an
implementation adds: no padding of the head size or of the cache, no
upcast, no recomputation.  bf16 is 2 bytes.  One multiply-add is 2 FLOPs.
"""
from __future__ import annotations

BF16 = 2


def causal_pairs(s: int, window=None) -> int:
    """(query, key) pairs a causal pass over s positions attends, each query
    seeing at most `window` keys (itself included)."""
    if window is None or window >= s:
        return s * (s + 1) // 2
    w = window
    return w * (w + 1) // 2 + (s - w) * w


def flash_fwd(batch: int, seq: int, heads: int, kv_heads: int, d: int,
              window=None) -> tuple:
    """(FLOPs, bytes) of one causal attention forward over a layer: QK^T and
    PV over the attended pairs; q, k, v and o read or written once."""
    flops = 4 * d * heads * batch * causal_pairs(seq, window)
    nbytes = BF16 * batch * seq * d * (2 * heads + 2 * kv_heads)
    return flops, nbytes


def decode_attn(batch: int, attended: int, heads: int, kv_heads: int,
                d: int) -> tuple:
    """(FLOPs, bytes) of one single-token attention over a layer's cache:
    K and V of the `attended` positions at their true size, q and o."""
    flops = 4 * d * heads * batch * attended
    nbytes = BF16 * batch * (2 * kv_heads * attended * d + 2 * heads * d)
    return flops, nbytes


def layer_matmul_params(hidden: int, heads: int, kv_heads: int, d: int,
                        ffn: int) -> int:
    """Weights one token multiplies through in a dense block."""
    return hidden * d * (2 * heads + 2 * kv_heads) + 3 * hidden * ffn


def forward_flops(c: dict, batch: int, seq: int, past: int = 0,
                  logits_per_row: int = 1) -> int:
    """Model FLOPs of a forward over `seq` new tokens per row after `past`
    cached ones: the block matmuls, attention over the attended positions,
    and the output head for the `logits_per_row` positions whose logits are
    used."""
    L, D = c["num_hidden_layers"], c["hidden_size"]
    H, K = c["num_attention_heads"], c["num_key_value_heads"]
    d = c.get("head_dim") or D // H
    w = c.get("sliding_window")
    mm = 2 * layer_matmul_params(D, H, K, d, c["intermediate_size"])
    pairs = causal_pairs(past + seq, w) - causal_pairs(past, w)
    attn = 4 * d * H * pairs
    head = 2 * D * c["vocab_size"] * logits_per_row
    return batch * (L * (mm * seq + attn) + head)


def weight_bytes(c: dict) -> int:
    """Bytes of the weights one decode step reads: every block matrix and
    the output head (the embedding is gathered, B rows)."""
    L, D = c["num_hidden_layers"], c["hidden_size"]
    H, K = c["num_attention_heads"], c["num_key_value_heads"]
    d = c.get("head_dim") or D // H
    per = layer_matmul_params(D, H, K, d, c["intermediate_size"])
    return BF16 * (L * per + c["vocab_size"] * D)


def decode_step_bytes(c: dict, batch: int, attended: int) -> int:
    """HBM bytes a decode step needs: weights once, and each layer's K and
    V of the attended positions once (the new slot among them, written)."""
    L, D = c["num_hidden_layers"], c["hidden_size"]
    H, K = c["num_attention_heads"], c["num_key_value_heads"]
    d = c.get("head_dim") or D // H
    kv = BF16 * batch * L * 2 * K * d * attended
    return weight_bytes(c) + kv
