"""Operations and bytes of the latent-attention and dropless-expert
configuration (deepseek_v2_lite), worked out from shapes and from the
rows the experts were routed: the yardstick of its roofline shares and
its utilization.

As in :mod:`livebench.flops`, counts are of the work the algorithm
needs: recomputation under remat and the rows of the static bound that
no token was routed to are not counted.  ``sz`` is the deepseek_v2_lite
reference's size dict; ``rows`` are assignments computed by held
experts (the program's ``moe.rows``).
"""
from __future__ import annotations

from .flops import BF16, F32, causal_pairs


def _qk(sz: dict) -> int:
    return sz["d_nope"] + sz["d_rope"]


def dense_matmul_params(sz: dict) -> int:
    """Weights every token multiplies by in one step's forward, over all
    layers and the head: MLA's projections in every layer, the dense
    layer's SwiGLU, and each MoE layer's router and shared experts."""
    d, H = sz["d"], sz["H"]
    mla = (d * H * _qk(sz) + d * sz["kv_lora"] + d * sz["d_rope"]
           + sz["kv_lora"] * H * (sz["d_nope"] + sz["d_v"])
           + H * sz["d_v"] * d)
    moe = d * sz["E"] + 3 * d * sz["n_shared"] * sz["Fe"]
    n_moe = sz["L"] - sz["dense"]
    return (sz["L"] * mla + sz["dense"] * 3 * d * sz["F"] + n_moe * moe
            + sz["Vp"] * d)


def attention_fwd_flops(batch: int, s: int, sz: dict) -> int:
    """Causal attention's scores (q/k width) and weighted values (v
    width) in all layers' heads."""
    return (2 * (_qk(sz) + sz["d_v"]) * sz["H"] * causal_pairs(s) * batch
            * sz["L"])


def train_step_flops(batch: int, text_len: int, rows: float,
                     sz: dict) -> float:
    """Forward and backward (3x forward) of one step over ``batch``
    sequences of ``text_len`` tokens whose held experts computed
    ``rows`` assignments, summed over layers and microbatches."""
    tokens = batch * text_len
    fwd = (2 * tokens * dense_matmul_params(sz)
           + 2 * rows * 3 * sz["d"] * sz["Fe"]
           + attention_fwd_flops(batch, text_len, sz))
    return 3 * fwd


def gmm_call_cost(rows: float, sz: dict) -> tuple:
    """(flops, bytes) of one grouped product of the expert layer over
    ``rows`` routed rows: every ``moe_gmm`` (x W1, x W3, h W2, or a
    transposed one in the backward) and every ``moe_tgmm`` (a weight's
    gradient) multiplies rows of one width, d or Fe, by the held
    experts' (d, Fe) matrices.  Bytes: the rows in and out, bf16, and the
    held experts' matrix read (or its gradient written) once."""
    d, Fe = sz["d"], sz["Fe"]
    return (2 * rows * d * Fe,
            BF16 * (rows * (d + Fe) + sz["E_held"] * d * Fe))


def mla_attention_costs(batch: int, s: int, sz: dict) -> dict:
    """Per call of each causal flash-attention kernel on one layer, at
    q/k width ``d_nope + d_rope`` and v width ``d_v`` (K = H heads):
    (flops, bytes), as :func:`livebench.flops.flash_attention_costs`
    counts them."""
    H, dq, dv = sz["H"], _qk(sz), sz["d_v"]
    pairs = causal_pairs(s) * H * batch
    q = batch * H * s * dq * BF16          # q, k, dq and dk alike
    v = batch * H * s * dv * BF16          # v, o, dO and dv alike
    lse = batch * H * s * F32
    return {"flash_attention_fwd": (2 * (dq + dv) * pairs,
                                    2 * q + 2 * v + lse),
            "flash_attention_dq": (2 * (dv + dq) * pairs,
                                   3 * q + 3 * v + 2 * lse),
            "flash_attention_dkv": (2 * (dv + dq) * pairs,
                                    4 * q + 4 * v + 2 * lse)}


def window_rows_per_step(run):
    """The mean over the window's train steps of the assignments held
    experts computed (the program's ``moe.rows``, one
    ``repro.telemetry`` row per step); None without such rows, or where
    the recorder let go of one the window needs."""
    try:
        from repro import telemetry
    except ImportError:
        return None
    if telemetry.dropped_since(run.t0):
        return None
    rows = [r.n for r in telemetry.rows("moe.rows", since=run.t0)
            if r.t0 < run.t1]
    return sum(rows) / len(rows) if rows else None
