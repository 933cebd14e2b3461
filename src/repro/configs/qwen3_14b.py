"""Qwen3-14B [dense] (hf:Qwen/Qwen3-14B): 40L d_model=5120 40H (GQA kv=8)
d_ff=17408 (SwiGLU) vocab=151936, qk-norm, head_dim=128."""
import dataclasses

import jax.numpy as jnp
from ..models.common import ModelConfig

CONFIG = ModelConfig(
    name="qwen3-14b", family="dense",
    n_layers=40, d_model=5120, n_heads=40, n_kv_heads=8, d_ff=17408,
    vocab_size=151_936, head_dim=128, qk_norm=True, ffn_act="silu",
    rope_theta=1_000_000.0, tie_embeddings=False,
    rule_overrides=(("kv_heads", None), ("heads", ("model",))),
)

#: One TPU v5e chip (16 GB HBM) serving Qwen3-14B at every published width
#: (d_model, heads, KV heads, head_dim, d_ff, the whole 151,936-row
#: vocabulary, qk-norm) with bf16 weights, as the published checkpoint
#: ships them.
#:
#: reduced: n_layers 40 -> 8.  A layer holds 330M parameters (660 MB in
#: bf16) and the embedding plus the untied head 1.56B (3.1 GB), so all 40
#: layers (29.5 GB) cannot sit on one chip.  8 layers make 8.4 GB of
#: weights and leave about 7 GB for the paged KV pool (32 KB per token
#: over the 8 layers) and the step's activations, which is what a
#: deployment keeps beside the weights.  The deployment this stands for is
#: a 5-stage pipeline of such chips, 8 layers each: the 32 layers left out
#: would be the 4 further stages.  Every layer is the same dense block, so
#: 8 of them hold the whole layer pattern.
#:
#: Weights are random, generated from the run's ``--seed``: no checkpoint
#: is in the repository and none is downloaded.
ONE_CHIP_CONFIG = dataclasses.replace(
    CONFIG, name="qwen3-14b-1chip", n_layers=8, param_dtype=jnp.bfloat16)

SMOKE_CONFIG = ModelConfig(
    name="qwen3-smoke", family="dense",
    n_layers=2, d_model=64, n_heads=4, n_kv_heads=2, d_ff=160,
    vocab_size=512, head_dim=16, qk_norm=True, ffn_act="silu",
    tie_embeddings=False,
)
