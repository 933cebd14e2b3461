"""Plain reference of Qwen3's sparse-expert decoder (Qwen3-235B-A22B) at
one chip's share of its experts, the benchmark's weights, and the model's
FLOP and byte counts, the expert layer's among them.

It imports nothing of the program under test.  The attention block is
``reference/dense_lm.py``'s (qk-norm GQA, RoPE), whose helpers it reads
through ``harness.piece``; the weights are made from the seed as there and
read in float32 with every matmul at ``Precision.HIGHEST``.

Layer equations, for one sequence x [S, d]:

    x = x + attention(rms(x) * attn_norm)          (as dense_lm)
    h = rms(x) * ffn_norm
    p = softmax(h @ router)                        (all n_experts logits)
    for each token: its top experts_per_token of p, renormalised to sum 1
    x = x + sum over held experts e of g[e] * (silu(h @ we_gate[e])
                                               * (h @ we_in[e])) @ we_out[e]

where g[e] is the token's renormalised weight for e if e is among its top
choices, else 0, and the held experts are ``experts_held`` of them from
``first_held_expert`` on.  What the experts held on other chips would add
is left out, as the program leaves it out.  The sum is written per token
and per held expert, every held expert applied to every token and
weighted by g: nothing is sorted or grouped.

``control=True`` rounds every matmul operand to float8 (e4m3), the
router's too, as dense_lm does.

The counts are pure functions of the ``model`` block.  Routing depends on
the data, so the counts that follow it take a token's expected share: of
its ``experts_per_token`` choices, ``experts_held / n_experts`` land here
under a balanced router.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
import numpy as np

from benchmarks.chip import harness

dense = harness.piece("reference", "dense_lm")
F32, HIGHEST = dense.F32, dense.HIGHEST
head_dim, _rms, _rope, _mm, _fp8 = (dense.head_dim, dense._rms, dense._rope,
                                    dense._mm, dense._fp8)


def weight_shapes(m: dict) -> dict:
    d, hd, L = m["d_model"], head_dim(m), m["n_layers"]
    H, K, V = m["n_heads"], m["n_kv_heads"], m["vocab_size"]
    E, G, f = m["n_experts"], m["experts_held"], m["moe_d_ff"]
    layers = {"attn_norm": (L, d), "wq": (L, d, H * hd), "wk": (L, d, K * hd),
              "wv": (L, d, K * hd), "wo": (L, H * hd, d), "q_norm": (L, hd),
              "k_norm": (L, hd), "ffn_norm": (L, d), "router": (L, d, E),
              "we_in": (L, G, d, f), "we_gate": (L, G, d, f),
              "we_out": (L, G, f, d)}
    return {"embed": (V, d), "head": (d, V), "final_norm": (d,),
            "layers": layers}


def init_weights(m: dict, seed: int, sharding=None) -> dict:
    """Every weight from ``seed``, in ``m["param_dtype"]``, made on the
    device by one jitted program: matrices normal with std 1/sqrt(fan_in)
    (the router's fan-in is d_model), norm gains 1 + 0.1 * normal."""
    leaves, treedef = jax.tree_util.tree_flatten_with_path(
        weight_shapes(m), is_leaf=lambda s: isinstance(s, tuple))
    dtype = jnp.dtype(m["param_dtype"])

    def make(key):
        out = []
        for k, (path, shape) in zip(jax.random.split(key, len(leaves)),
                                    leaves):
            z = jax.random.normal(k, shape, F32)
            w = 1.0 + 0.1 * z if path[-1].key.endswith("norm") else \
                z / math.sqrt(shape[-2])
            out.append(w.astype(dtype))
        return jax.tree.unflatten(treedef, out)

    kw = {} if sharding is None else {"out_shardings": sharding}
    return jax.jit(make, **kw)(dense._key(seed))


# ------------------------------------------------------------------ math
def routing(m: dict, h, router, control: bool = False):
    """Each token's renormalised weight for each held expert, [S, held]:
    softmax over all ``n_experts`` logits, the top ``experts_per_token``
    renormalised, 0 for an expert the token did not choose."""
    p = jax.nn.softmax(_mm(h, router, control), axis=-1)
    top, idx = jax.lax.top_k(p, m["experts_per_token"])
    top = top / jnp.sum(top, axis=-1, keepdims=True)
    held = m["first_held_expert"] + jnp.arange(m["experts_held"])
    return jnp.sum(jnp.where(idx[:, :, None] == held, top[:, :, None], 0.0),
                   axis=1)


def expert_share(m: dict, h, w: dict, control: bool = False):
    """The held experts' part of the layer's output for h [S, d]."""
    g = routing(m, h, w["router"], control)
    y = jnp.zeros_like(h)
    for e in range(m["experts_held"]):
        a = jax.nn.silu(_mm(h, w["we_gate"][e], control)) * \
            _mm(h, w["we_in"][e], control)
        y = y + g[:, e:e + 1] * _mm(a, w["we_out"][e], control)
    return y


def _attention(m: dict, x, w: dict, control: bool):
    eps, S = m["norm_eps"], x.shape[0]
    H, K, hd = m["n_heads"], m["n_kv_heads"], head_dim(m)
    h = _rms(x, w["attn_norm"], eps)
    q = _mm(h, w["wq"], control).reshape(S, H, hd)
    k = _mm(h, w["wk"], control).reshape(S, K, hd)
    v = _mm(h, w["wv"], control).reshape(S, K, hd)
    q, k = _rms(q, w["q_norm"], eps), _rms(k, w["k_norm"], eps)
    q, k = _rope(q, m["rope_theta"]), _rope(k, m["rope_theta"])
    qg = q.reshape(S, K, H // K, hd)
    if control:
        qg, k, v = _fp8(qg, -1), _fp8(k, -1), _fp8(v, -1)
    s = jnp.einsum("qkgd,skd->kgqs", qg, k, precision=HIGHEST) * hd ** -0.5
    causal = jnp.arange(S)[:, None] >= jnp.arange(S)[None, :]
    p = jax.nn.softmax(jnp.where(causal, s, -jnp.inf), axis=-1)
    if control:
        p = _fp8(p, -1)
    o = jnp.einsum("kgqs,skd->qkgd", p, v, precision=HIGHEST)
    return x + _mm(o.reshape(S, H * hd), w["wo"], control)


@functools.partial(jax.jit, static_argnums=(0, 4))
def _layer(mk, x, layers, li, control):
    m = dict(mk)
    w = jax.tree.map(lambda a: a[li].astype(F32), layers)
    x = _attention(m, x, w, control)
    return x + expert_share(m, _rms(x, w["ffn_norm"], m["norm_eps"]), w,
                            control)


def hidden(m: dict, weights: dict, tokens, control: bool = False):
    """Final-norm hidden states [S, d] of one sequence, in float32."""
    mk = tuple(sorted(m.items()))
    x = dense._embed(mk, weights["embed"], jnp.asarray(tokens, jnp.int32))
    for li in range(m["n_layers"]):
        x = _layer(mk, x, weights["layers"], li, control)
    return dense._final(mk, x, weights["final_norm"])


def logit_gaps(m: dict, weights: dict, prompt, served,
               control: bool = False) -> np.ndarray:
    """Relative logit gap of every served token of one request, as
    dense_lm's: (best logit - the served token's) / max |logit| at each
    position, all from the float32 reference; with ``control``, the gap
    of the token the fp8 reference puts first instead.  Each side routes
    by its own router: the program's choices are never used here."""
    P = len(prompt)
    seq = np.concatenate([np.asarray(prompt), np.asarray(served)[:-1]])
    x = hidden(m, weights, seq)[P - 1:]
    x8 = hidden(m, weights, seq, control=True)[P - 1:] if control else x
    toks = jnp.asarray(served, jnp.int32)
    head, chunk = weights["head"], dense.VOCAB_CHUNK
    stats = [dense._chunk_stats(x, x8, head[:, b:b + chunk], toks, control,
                                jnp.int32(b))
             for b in range(0, head.shape[1], chunk)]
    best = functools.reduce(jnp.maximum, [s[0] for s in stats])
    scale = functools.reduce(jnp.maximum, [s[1] for s in stats])
    chosen = functools.reduce(jnp.maximum, [s[2] for s in stats])
    if control:
        best8 = jnp.stack([s[3] for s in stats])
        at8 = jnp.stack([s[4] for s in stats])
        chosen = jnp.take_along_axis(at8, jnp.argmax(best8, 0)[None], 0)[0]
    return np.asarray((best - chosen) / scale)


# ------------------------------------------------------------------ counts
# Model FLOPs, two per multiply-add, as dense_lm counts them: the
# projections, the router, the expert FFNs a token's choices run here (on
# average experts_per_token * experts_held / n_experts of them), the head
# at the positions whose logits are used, and causal attention over the
# live context.
def _expert_params(m: dict) -> int:
    """Weights of one expert: its gate, up and down matrices."""
    return 3 * m["d_model"] * m["moe_d_ff"]


def held_choices_per_token(m: dict) -> float:
    """A token's choices that land on the experts held here, on average
    under a balanced router."""
    return m["experts_per_token"] * m["experts_held"] / m["n_experts"]


def matmul_params_per_layer(m: dict) -> float:
    """Weights one token multiplies through in one layer, on average."""
    d, hd = m["d_model"], head_dim(m)
    attn = d * hd * (2 * m["n_heads"] + 2 * m["n_kv_heads"])
    return (attn + d * m["n_experts"]
            + held_choices_per_token(m) * _expert_params(m))


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


def decode_bytes(m: dict, contexts) -> int:
    """Bytes one decode step has to move to or from HBM, one row at each
    of ``contexts``: every weight once (every held expert's among them),
    of the embedding table only the rows' own rows, and in every layer
    each row's earlier K and V read and its new ones written."""
    shapes = jax.tree.leaves(weight_shapes(m),
                             is_leaf=lambda s: isinstance(s, tuple))
    weights = sum(math.prod(s) for s in shapes) - \
        m["vocab_size"] * m["d_model"]
    kv_token = 2 * m["n_kv_heads"] * head_dim(m) * \
        jnp.dtype(m["dtype"]).itemsize
    return (jnp.dtype(m["param_dtype"]).itemsize
            * (weights + len(contexts) * m["d_model"])
            + m["n_layers"] * kv_token * sum(contexts))


def expert_rows(m: dict, tokens: int) -> float:
    """(token, choice) rows a layer routes to the experts held here for
    ``tokens`` tokens, on average."""
    return tokens * held_choices_per_token(m)


def expert_flops(m: dict, rows: float) -> float:
    """FLOPs of the held experts' FFNs over every layer for ``rows``
    routed rows a layer."""
    return m["n_layers"] * 2 * _expert_params(m) * rows


def expert_bytes(m: dict, rows: float) -> float:
    """Bytes the held experts' FFNs have to move over every layer for
    ``rows`` routed rows a layer: each held expert's three matrices once a
    layer, and each row read in and its output written."""
    size = jnp.dtype(m["param_dtype"]).itemsize
    return m["n_layers"] * size * (m["experts_held"] * _expert_params(m)
                                   + 2 * rows * m["d_model"])
