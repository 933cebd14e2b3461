"""Smoke-size cells for the CPU tests: the wave engine and the reference
at the program's CPU preset widths, driven by the real harness."""
import copy

from benchmarks.chip.harness import Cell, load_benchmark
from benchmarks.chip.peaks import PEAKS

MODEL = {"n_layers": 2, "d_model": 64, "n_heads": 4, "n_kv_heads": 2,
         "head_dim": 16, "d_ff": 160, "vocab_size": 512, "qk_norm": True,
         "ffn_act": "silu", "rope_theta": 10000.0, "tie_embeddings": False,
         "norm": "rmsnorm", "norm_eps": 1e-06, "embed_scale": "sqrt_d_model",
         "kv_block_tokens": 16, "dtype": "bfloat16",
         "param_dtype": "bfloat16"}
PEAK = PEAKS["TPU v5 lite"]


#: the limit on ``token_gap`` at smoke size: the program reads 0.002-0.012
#: there on the CPU, the fp8 control 0.04-0.09
SMOKE_GAP_LIMIT = 0.025


def cell(*, arrival="offline", batch=4, mesh_pods=1, prompt_len=32,
         gen_len=24, rate=40.0, limit=SMOKE_GAP_LIMIT,
         name="smoke.decode_long"):
    doc = {"program_config": "qwen3_14b", "program_size": "smoke",
           "engine": "waves", "reference": "dense_lm",
           "model": copy.deepcopy(MODEL),
           "reduced": {"n_layers": "the preset's own depth"},
           "server": {"batch": batch, "n_pods": 4, "mode": "numapte",
                      "mesh_pods": mesh_pods},
           "limits": {"token_gap": limit}}
    mix = {"name": "smoke", "arrival": arrival, "prompt_len": prompt_len,
           "gen_len": gen_len, "check_requests": 2, "rate_per_s": rate,
           "gap_seed": 3, "order_block": 16}
    bench = load_benchmark()
    kind = "chat_poisson" if arrival == "poisson" else "decode_long"
    ref = next(w["name"] for w in bench["workloads"]
               if w["traffic"] == kind)

    def mine(m):
        return ref in m.get("workloads", [ref])

    return Cell(name, {"chips": mesh_pods}, doc, mix,
                [m for m in bench["end_to_end"] if mine(m)],
                [m for m in bench["per_layer"] if mine(m)])
