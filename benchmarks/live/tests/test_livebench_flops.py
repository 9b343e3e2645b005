"""Operation and byte counts against hand counts at a small shape."""
import sys
from pathlib import Path

LIVE = Path(__file__).resolve().parents[1]
sys.path.insert(0, str(LIVE))

from livebench import flops  # noqa: E402

SZ = {"L": 2, "d": 8, "H": 4, "K": 2, "Dh": 2, "F": 16, "V": 10, "P": 3}


def test_layer_params_and_pairs():
    # wq 8*4*2, wk and wv 8*2*2 each, wo 4*2*8, ffn 3*8*16
    assert flops.layer_matmul_params(SZ) == 64 + 32 + 32 + 64 + 384
    assert flops.causal_pairs(4) == 10


def test_train_step_by_hand():
    # 2 sequences of 3 patches + 5 text tokens, S = 8
    per_layer_tokens = 8 * 2 * 576          # tokens x layers x weights
    fwd = 2 * 2 * (per_layer_tokens + 3 * 64 + 5 * 80)
    attn = 4 * 2 * 4 * 36 * 2 * 2           # 4 Dh H pairs B L
    assert flops.train_step_flops(2, 5, SZ) == 3 * (fwd + attn)


def test_decode_by_hand():
    per_tok = 2 * 576 + 80
    attn = 4 * 2 * 4 * (7 + 5 + 3) * 2      # 4 Dh H keys L
    assert flops.decode_step_flops([7, 5, 3], SZ) == 3 * 2 * per_tok + attn
    f, b = flops.flash_decode_cost([7, 5, 3], SZ)
    assert f == 4 * 2 * 4 * 15
    assert b == 2 * 2 * 15 * 2 * 2 + 2 * 3 * 4 * 2 * 2


def test_flash_attention_costs_and_roofline():
    c = flops.flash_attention_costs(1, 4, SZ)
    prod = 2 * 2 * 10 * 4                   # 2 Dh pairs H
    assert {k: v[0] for k, v in c.items()} == {
        "flash_attention_fwd": 2 * prod, "flash_attention_dq": 2 * prod,
        "flash_attention_dkv": 2 * prod}
    q, kv, lse = 4 * 4 * 2 * 2, 2 * 4 * 2 * 2, 4 * 4 * 4
    assert c["flash_attention_fwd"][1] == 2 * q + 2 * kv + lse
    peak = {"bf16_flops_per_s": 100.0, "hbm_bytes_per_s": 10.0}
    assert flops.roofline_s(1000, 50, peak) == 10.0
    assert flops.roofline_s(100, 50, peak) == 5.0
