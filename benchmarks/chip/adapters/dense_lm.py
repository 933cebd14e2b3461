"""The program's side of ``reference/dense_lm.py``: which of the program's
configurations the reference fits, and the reference's weights in the
program's parameter tree.

An engine finds it by the configuration file's ``reference`` name, as
``adapters/<reference>.py``, and asks it two things:

- ``check(cfg, m)``: what differs between the program's ModelConfig
  ``cfg`` and the file's ``model`` block ``m``, as {key: (program, file)};
  empty where the reference's equations are the program's;
- ``to_program(cfg, weights)``: the same arrays in the program's tree.
"""
from __future__ import annotations

#: model keys whose value the program's configuration must hold as run
CHECKED_KEYS = ("n_layers", "d_model", "n_heads", "n_kv_heads", "d_ff",
                "vocab_size", "qk_norm", "ffn_act", "rope_theta",
                "tie_embeddings", "norm", "kv_block_tokens")


def check(cfg, m: dict) -> dict:
    wrong = {k: (getattr(cfg, k), m[k]) for k in CHECKED_KEYS
             if getattr(cfg, k) != m[k]}
    if cfg.family != "dense" or cfg.local_global_ratio or cfg.use_rope is \
            False or cfg.attn_logit_softcap:
        wrong["family"] = (cfg.family, "dense, global RoPE attention")
    return wrong


def to_program(cfg, w: dict) -> dict:
    """The benchmark's weights in the program's parameter tree (the same
    arrays; the program's norms scale by 1 + scale, so a gain g is passed
    as g - 1, which bfloat16 holds exactly for gains in [0.5, 2])."""
    L = w["layers"]
    attn = {k: L[k] for k in ("wq", "wk", "wv", "wo")}
    if "q_norm" in L:
        attn.update(q_norm=L["q_norm"] - 1, k_norm=L["k_norm"] - 1)
    ffn = {k: L[k] for k in ("w_in", "w_out", "w_gate") if k in L}
    return {"groups": [{"norm1": {"scale": L["attn_norm"] - 1},
                        "attn": attn,
                        "norm2": {"scale": L["ffn_norm"] - 1},
                        "ffn": ffn}],
            "final_norm": {"scale": w["final_norm"] - 1},
            "embedding": w["embed"], "lm_head": w["head"]}
