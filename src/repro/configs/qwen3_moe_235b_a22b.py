"""Qwen3-235B-A22B [moe] (hf:Qwen/Qwen3-235B-A22B): 94L d_model=4096
64H (GQA kv=4) per-expert d_ff=1536, 128 experts top-8 (renormalised, no
shared expert, no leading dense layer), vocab=151936 untied, qk-norm,
RoPE theta 1e6.  Experts shard over the model axis (EP): 128/16 = 8 per
shard.

Published (``CONFIG``): 94 layers, every one an expert layer of 128
experts.  One chip (``ONE_CHIP_CONFIG``): 8 layers, each holding 8 of its
128 experts (0-7); every width, the router's 128 outputs and its 8
experts per token as published."""
import dataclasses

import jax.numpy as jnp
from ..models.common import ModelConfig

CONFIG = ModelConfig(
    name="qwen3-moe-235b-a22b", family="moe",
    n_layers=94, d_model=4096, n_heads=64, n_kv_heads=4, d_ff=0,
    vocab_size=151_936, head_dim=128, qk_norm=True, ffn_act="silu",
    n_experts=128, experts_per_token=8, moe_d_ff=1536,
    rope_theta=1_000_000.0, tie_embeddings=False,
    rule_overrides=(("kv_heads", None),),
)

#: One TPU v5e chip (16 GB HBM) serving Qwen3-235B-A22B at one chip's
#: share of a stated deployment, with bf16 weights as published.
#:
#: The deployment: each layer's 128 experts are divided over 16 chips, 8
#: each, and attention runs data-parallel over those 16; this chip is one
#: 8-layer stage of a pipeline whose further stages hold the other 86
#: layers.  So: ``experts_held`` 128 -> 8 (experts 0-7; the router still
#: scores all 128 and routes each token to its top 8, of which this chip
#: computes those it holds) and ``n_layers`` 94 -> 8 (every layer is the
#: same expert block).  A layer is 71.3M attention, 0.52M router and 8 x
#: 18.87M expert parameters (446 MB in bf16); 8 layers and the embedding
#: plus the untied 151,936-row head make 6.05 GB, which leaves room for
#: the paged KV pool of 64 rows at 2,048 tokens.
#:
#: Weights are random, generated from the run's ``--seed``.
ONE_CHIP_CONFIG = dataclasses.replace(
    CONFIG, name="qwen3-moe-235b-a22b-1chip", n_layers=8, experts_held=8,
    param_dtype=jnp.bfloat16)

SMOKE_CONFIG = ModelConfig(
    name="qwen3-moe-smoke", family="moe",
    n_layers=2, d_model=64, n_heads=4, n_kv_heads=2, d_ff=0,
    vocab_size=512, head_dim=16, qk_norm=True, ffn_act="silu",
    n_experts=8, experts_per_token=2, moe_d_ff=96, tie_embeddings=False,
    moe_capacity_factor=8.0,
)
