"""Model FLOPs of qwen3_14b as run (8 layers), against counts worked by
hand from its published widths."""
import json
import pathlib

from benchmarks.chip import flops

M = json.loads((pathlib.Path(__file__).resolve().parents[1] / "configs"
                / "qwen3_14b.json").read_text())["model"]

# attention: wq and wo 5120 x 5120, wk and wv 5120 x 1024
ATTN = 5120 * 5120 * 2 + 5120 * 1024 * 2            # 62,914,560
# SwiGLU: w_in, w_gate, w_out, 5120 x 17408 each
FFN = 3 * 5120 * 17408                               # 267,386,880
HEAD = 2 * 5120 * 151936                             # 1,555,824,640


def test_weights_per_layer():
    assert ATTN == 62_914_560 and FFN == 267_386_880
    assert flops.matmul_params_per_layer(M) == 330_301_440


def test_decode_token():
    # 8 layers x (2 x 330,301,440 + 4 x 40 heads x 128 x context) + head
    assert flops.token_flops(M, 1) == 6_840_811_520
    assert flops.token_flops(M, 2048) == 6_840_647_680 + 163_840 * 2048


def test_prefill():
    # per row: 8 x (2 x 330,301,440 x 256 + 4 x 40 x 128 x 256 x 257 / 2)
    #          + the head at the last position
    row = 8 * (169_114_337_280 + 673_710_080) + HEAD
    assert row == 1_359_860_203_520
    assert flops.prefill_flops(M, 256, 16) == 16 * row


def test_relu2_has_no_gate():
    m = dict(M, ffn_act="relu2")
    assert flops.matmul_params_per_layer(m) == ATTN + 2 * 5120 * 17408
