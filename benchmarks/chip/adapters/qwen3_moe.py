"""The program's side of ``reference/qwen3_moe.py``: which of the
program's configurations the reference fits, and the reference's weights
in the program's parameter tree (``check`` and ``to_program``, as
``adapters/dense_lm.py`` describes them)."""
from __future__ import annotations

#: model keys whose value the program's configuration must hold as run
CHECKED_KEYS = ("n_layers", "d_model", "n_heads", "n_kv_heads",
                "n_experts", "experts_per_token", "first_held_expert",
                "moe_d_ff", "n_shared_experts", "vocab_size", "qk_norm",
                "ffn_act", "rope_theta", "tie_embeddings", "norm",
                "kv_block_tokens")


def check(cfg, m: dict) -> dict:
    wrong = {k: (getattr(cfg, k), m[k]) for k in CHECKED_KEYS
             if getattr(cfg, k) != m[k]}
    if cfg.n_held_experts != m["experts_held"]:
        wrong["experts_held"] = (cfg.n_held_experts, m["experts_held"])
    # the program always renormalises the top choices' weights
    if not m["norm_topk_prob"]:
        wrong["norm_topk_prob"] = (True, m["norm_topk_prob"])
    if cfg.family != "moe" or cfg.first_dense_layers or \
            cfg.local_global_ratio or cfg.use_rope is False or \
            cfg.attn_logit_softcap:
        wrong["family"] = (cfg.family, "moe in every layer, global RoPE "
                           "attention")
    return wrong


def to_program(cfg, w: dict) -> dict:
    """The same arrays in the program's tree; norm gains g pass as g - 1,
    as in ``adapters/dense_lm.py``."""
    L = w["layers"]
    return {"groups": [{
        "norm1": {"scale": L["attn_norm"] - 1},
        "attn": {"wq": L["wq"], "wk": L["wk"], "wv": L["wv"],
                 "wo": L["wo"], "q_norm": L["q_norm"] - 1,
                 "k_norm": L["k_norm"] - 1},
        "norm2": {"scale": L["ffn_norm"] - 1},
        "moe": {k: L[k] for k in ("router", "we_in", "we_gate", "we_out")}}],
        "final_norm": {"scale": w["final_norm"] - 1},
        "embedding": w["embed"], "lm_head": w["head"]}
