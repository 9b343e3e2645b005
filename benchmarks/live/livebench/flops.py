"""Operations and bytes worked out from shapes: the yardstick of every
utilization and roofline share the benchmark reports.

Counts are of the work the algorithm needs, not of what an implementation
happens to do: recomputation under remat, padding rows of the output
matrix and blocks a kernel loads but masks are not counted.  ``sz`` is a
reference's size dict (L, d, H, K, Dh, F, V, P).
"""
from __future__ import annotations


def layer_matmul_params(sz: dict) -> int:
    """Weights one token multiplies by in one decoder layer."""
    d, H, K, Dh, F = sz["d"], sz["H"], sz["K"], sz["Dh"], sz["F"]
    return d * H * Dh + 2 * d * K * Dh + H * Dh * d + 3 * d * F


def causal_pairs(s: int) -> int:
    """(query, key) pairs of causal attention over ``s`` positions."""
    return s * (s + 1) // 2


def attention_fwd_flops(batch: int, s: int, sz: dict) -> int:
    """Scores and weighted values of causal attention in all layers'
    query heads: 2 multiply-adds of Dh per pair per head."""
    return 4 * sz["Dh"] * sz["H"] * causal_pairs(s) * batch * sz["L"]


def train_step_flops(batch: int, text_len: int, sz: dict) -> int:
    """Forward and backward (3x forward) of one training step over
    ``batch`` sequences of ``P`` patch embeddings and ``text_len`` tokens;
    the loss is taken on the text positions only."""
    s = sz["P"] + text_len
    fwd = 2 * batch * (s * sz["L"] * layer_matmul_params(sz)
                       + sz["P"] * sz["d"] * sz["d"]
                       + text_len * sz["V"] * sz["d"])
    return 3 * (fwd + attention_fwd_flops(batch, s, sz))


def decode_step_flops(kv_lens, sz: dict) -> int:
    """One greedy decode step of a batch whose rows attend to ``kv_lens``
    keys each (their own tokens: left padding is not work)."""
    per_tok = sz["L"] * layer_matmul_params(sz) + sz["V"] * sz["d"]
    attn = 4 * sz["Dh"] * sz["H"] * sum(kv_lens) * sz["L"]
    return len(kv_lens) * 2 * per_tok + attn


# ------------------------------------------------------- kernel rooflines
BF16 = 2
F32 = 4


def flash_attention_costs(batch: int, s: int, sz: dict) -> dict:
    """Per call of each causal flash-attention kernel on one layer:
    (flops, bytes).  The forward needs the scores and the weighted values
    (2 products per pair); the dq kernel dP and dQ, the dkv kernel dV and
    dK (2 products each).  Bytes are one read of each operand and one
    write of each result, bf16, with the f32 log-sum-exp per row."""
    H, K, Dh = sz["H"], sz["K"], sz["Dh"]
    prod = 2 * Dh * causal_pairs(s) * H * batch
    q = batch * H * s * Dh * BF16
    kv = batch * K * s * Dh * BF16
    lse = batch * H * s * F32
    return {"flash_attention_fwd": (2 * prod, q + 2 * kv + q + lse),
            "flash_attention_dq": (2 * prod, 3 * q + 2 * kv + lse + q),
            "flash_attention_dkv": (2 * prod, 3 * q + 2 * kv + lse + 2 * kv)}


def flash_decode_cost(kv_lens, sz: dict) -> tuple:
    """One call of the decode kernel on one layer: (flops, bytes).  Each
    row's own keys and values (``kv_lens``) are read once, and its query
    and output."""
    H, K, Dh = sz["H"], sz["K"], sz["Dh"]
    flops = 4 * Dh * H * sum(kv_lens)
    byts = 2 * K * sum(kv_lens) * Dh * BF16 + 2 * len(kv_lens) * H * Dh * BF16
    return flops, byts


def roofline_s(flops: float, byts: float, peak: dict) -> float:
    """The least time the chip needs for the work: the larger of the
    compute and the memory bound."""
    return max(flops / peak["bf16_flops_per_s"],
               byts / peak["hbm_bytes_per_s"])
