"""Model FLOPs and decode bytes of the configurations as run (qwen3_14b at
8 layers, nemotron_4_15b at 4), from the counts in
``reference/dense_lm.py``, against counts worked by hand from their
published widths."""
import json
import pathlib

import pytest

from benchmarks.chip import harness

HERE = pathlib.Path(__file__).resolve().parents[1]
counts = harness.piece("reference", "dense_lm")


def model(name):
    return json.loads((HERE / "configs" / f"{name}.json").read_text()
                      )["model"]


M = model("qwen3_14b")
NEMOTRON = model("nemotron_4_15b")

# attention: wq and wo 5120 x 5120, wk and wv 5120 x 1024
ATTN = 5120 * 5120 * 2 + 5120 * 1024 * 2            # 62,914,560
# SwiGLU: w_in, w_gate, w_out, 5120 x 17408 each
FFN = 3 * 5120 * 17408                               # 267,386,880
HEAD = 2 * 5120 * 151936                             # 1,555,824,640


def test_weights_per_layer():
    assert ATTN == 62_914_560 and FFN == 267_386_880
    assert counts.matmul_params_per_layer(M) == 330_301_440


def test_decode_token():
    # 8 layers x (2 x 330,301,440 + 4 x 40 heads x 128 x context) + head
    assert counts.token_flops(M, 1) == 6_840_811_520
    assert counts.token_flops(M, 2048) == 6_840_647_680 + 163_840 * 2048


def test_prefill():
    # per row: 8 x (2 x 330,301,440 x 256 + 4 x 40 x 128 x 256 x 257 / 2)
    #          + the head at the last position
    row = 8 * (169_114_337_280 + 673_710_080) + HEAD
    assert row == 1_359_860_203_520
    assert counts.prefill_flops(M, 256, 16) == 16 * row


def test_relu2_has_no_gate():
    m = dict(M, ffn_act="relu2")
    assert counts.matmul_params_per_layer(m) == ATTN + 2 * 5120 * 17408


def old_counts(m):
    """The formulas the counts were first written as (a module of their
    own, for dense models only), kept as a second witness."""
    hd = m.get("head_dim") or m["d_model"] // m["n_heads"]
    d = m["d_model"]
    per = (d * m["n_heads"] * hd * 2 + d * m["n_kv_heads"] * hd * 2
           + d * m["d_ff"] * (3 if m["ffn_act"] in ("silu", "geglu") else 2))

    def token(context):
        return (m["n_layers"] * (2 * per + 4 * m["n_heads"] * hd * context)
                + 2 * d * m["vocab_size"])

    def prefill(S, rows):
        return rows * (m["n_layers"] * (2 * per * S + 4 * m["n_heads"] * hd
                                        * S * (S + 1) // 2)
                       + 2 * d * m["vocab_size"])
    return per, token, prefill


@pytest.mark.parametrize("name", ["qwen3_14b", "nemotron_4_15b"])
def test_counts_equal_the_first_formulas(name):
    m = model(name)
    per, token, prefill = old_counts(m)
    assert counts.matmul_params_per_layer(m) == per
    for c in (1, 257, 513, 2048):
        assert counts.token_flops(m, c) == token(c)
    for S, rows in ((256, 16), (512, 3), (1, 1)):
        assert counts.prefill_flops(m, S, rows) == prefill(S, rows)


def test_decode_bytes_qwen3_14b():
    # the matrices 8 x 330,301,440 x 2 B and the head, 178,176 B of norm
    # gains (8 x (5120 + 5120 + 128 + 128) + 5120, 2 B each)
    weights = 8 * 330_301_440 * 2 + HEAD + 178_176
    assert weights == 6_840_825_856
    assert counts.decode_bytes(M, []) == weights
    # a row at context c: its embedding row (5120 x 2 B) and 4,096 B of
    # K + V (2 x 8 heads x 128 x 2 B) per position and layer
    assert counts.decode_bytes(M, [1]) == weights + 10_240 + 8 * 4_096
    ctx = [513 + j for j in range(16)]
    assert counts.decode_bytes(M, ctx) == \
        weights + 16 * 10_240 + 8 * 4_096 * sum(ctx)


def test_decode_bytes_nemotron_4_15b():
    # per layer: wq, wo 6144 x 6144; wk, wv 6144 x 1024; w_in, w_out
    # 6144 x 24576; the head 6144 x 256,000
    matrices = 2 * (4 * (2 * 6144 * 6144 + 2 * 6144 * 1024
                         + 2 * 6144 * 24576) + 6144 * 256_000)
    assert matrices == 6_266_290_176
    gains = 2 * (4 * 2 * 6144 + 6144)                   # 110,592
    assert counts.decode_bytes(NEMOTRON, []) == 6_266_400_768 == \
        matrices + gains
    assert counts.decode_bytes(NEMOTRON, [2048] * 16) == \
        6_266_400_768 + 16 * 6144 * 2 + 4 * 4_096 * 2048 * 16
