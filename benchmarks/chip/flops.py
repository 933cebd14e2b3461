"""Model FLOPs of a dense decoder LM, from a configuration's ``model``
block (the sizes as run).

Model FLOPs count the work the architecture needs, two per multiply-add:
the projections, the feed-forward, the head at the positions whose logits
are used, and causal attention over the live context (QK^T and PV).  They
do not count work an implementation adds: padded rows, masked-out scores,
recomputation.  Norms, RoPE, softmax and the embedding gather are left
out; at these widths they are far below a tenth of a percent.
"""
from __future__ import annotations


def head_dim(m: dict) -> int:
    return m.get("head_dim") or m["d_model"] // m["n_heads"]


def matmul_params_per_layer(m: dict) -> int:
    """Weights one token multiplies through in one layer."""
    d, hd = m["d_model"], head_dim(m)
    attn = d * m["n_heads"] * hd * 2 + d * m["n_kv_heads"] * hd * 2
    gated = m["ffn_act"] in ("silu", "geglu")
    ffn = d * m["d_ff"] * (3 if gated else 2)
    return attn + ffn


def token_flops(m: dict, context: int) -> float:
    """One token through every layer and the head, attending to
    ``context`` positions (itself included)."""
    per_layer = (2 * matmul_params_per_layer(m)
                 + 4 * m["n_heads"] * head_dim(m) * context)
    return m["n_layers"] * per_layer + 2 * m["d_model"] * m["vocab_size"]


def prefill_flops(m: dict, prompt_len: int, rows: int) -> float:
    """Prefill of ``rows`` prompts of ``prompt_len`` tokens: every
    position through the layers with causal attention, the head at the
    last position only."""
    S = prompt_len
    per_layer = (2 * matmul_params_per_layer(m) * S
                 + 4 * m["n_heads"] * head_dim(m) * S * (S + 1) // 2)
    return rows * (m["n_layers"] * per_layer
                   + 2 * m["d_model"] * m["vocab_size"])
