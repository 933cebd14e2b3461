"""Plain reference of a dense decoder LM (Qwen3, Nemotron-4), the
benchmark's weights, and the model's FLOP and byte counts.

It imports nothing of the program under test.  ``init_weights`` makes the
weights from the run's seed in one jitted program, in the layout below,
and the engine adapter hands the same arrays to the program; the
reference reads them in float32 with every matmul at
``Precision.HIGHEST``.

Layer equations, for one sequence x [S, d]:

    h = rms(x) * attn_norm
    q, k, v = h @ wq, h @ wk, h @ wv           (heads of head_dim)
    q, k = rms(q) * q_norm, rms(k) * k_norm    (qk_norm only)
    q, k = rope(q), rope(k)                    (half-split rotation)
    x = x + softmax(q k^T / sqrt(hd) + causal) v @ wo   (head h reads KV
                                                       head h // (H / K))
    h = rms(x) * ffn_norm
    x = x + act(h) @ w_out,  act = silu(h @ w_gate) * (h @ w_in)  (silu)
                                 = relu(h @ w_in) ** 2            (relu2)
    logits = (rms(x) * final_norm) @ head

Departures from the published models, which the program makes and the
reference therefore follows (listed under ``assumed`` in each
configuration file): the embedding is scaled by sqrt(d_model)
(``embed_scale``), and Nemotron-4's LayerNorm is an RMSNorm (``norm``).

``control=True`` computes the same equations in float8 (e4m3): every
matmul operand is rounded to fp8 with a scale per row of activations and
per output channel of weights.  That is the precision step below the
configuration's bfloat16 which the benchmark's comparison has to catch.

The counts (``token_flops``, ``prefill_flops``, ``decode_bytes``) are pure
functions of the ``model`` block, read from the shapes of these equations:
the work and the traffic the architecture needs, not what an
implementation does.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
import numpy as np

F32 = jnp.float32
HIGHEST = jax.lax.Precision.HIGHEST
#: logits are reduced over the vocabulary in chunks of this many rows, so
#: a float32 copy of the head never has to exist whole
VOCAB_CHUNK = 16384


def head_dim(m: dict) -> int:
    return m.get("head_dim") or m["d_model"] // m["n_heads"]


def gated(m: dict) -> bool:
    return m["ffn_act"] == "silu"


def weight_shapes(m: dict) -> dict:
    d, hd, L = m["d_model"], head_dim(m), m["n_layers"]
    H, K, f, V = m["n_heads"], m["n_kv_heads"], m["d_ff"], m["vocab_size"]
    layers = {"attn_norm": (L, d), "wq": (L, d, H * hd), "wk": (L, d, K * hd),
              "wv": (L, d, K * hd), "wo": (L, H * hd, d), "ffn_norm": (L, d),
              "w_in": (L, d, f), "w_out": (L, f, d)}
    if m["qk_norm"]:
        layers.update(q_norm=(L, hd), k_norm=(L, hd))
    if gated(m):
        layers["w_gate"] = (L, d, f)
    return {"embed": (V, d), "head": (d, V), "final_norm": (d,),
            "layers": layers}


def _key(seed: int):
    """A key for any non-negative seed, 64-bit ones included."""
    return jax.random.fold_in(jax.random.PRNGKey(seed & 0x7FFFFFFF),
                              seed >> 31)


def init_weights(m: dict, seed: int, sharding=None) -> dict:
    """Every weight from ``seed``, in ``m["param_dtype"]``, made on the
    device by one jitted program.  Matrices are normal with std
    1/sqrt(fan_in) (the embedding's fan-in is its row count); norm gains
    are 1 + 0.1 * normal, so a path that drops a gain is seen."""
    shapes = weight_shapes(m)
    dtype = jnp.dtype(m["param_dtype"])
    leaves, treedef = jax.tree_util.tree_flatten_with_path(
        shapes, is_leaf=lambda s: isinstance(s, tuple))

    def make(key):
        keys = jax.random.split(key, len(leaves))
        out = []
        for k, (path, shape) in zip(keys, leaves):
            z = jax.random.normal(k, shape, F32)
            if path[-1].key.endswith("norm"):
                w = 1.0 + 0.1 * z
            else:
                w = z / math.sqrt(shape[-2])
            out.append(w.astype(dtype))
        return jax.tree.unflatten(treedef, out)

    kw = {} if sharding is None else {"out_shardings": sharding}
    return jax.jit(make, **kw)(_key(seed))


# ------------------------------------------------------------------ math
def _fp8(a, axis):
    """Round to float8 e4m3 with one scale per slice along ``axis``."""
    amax = jnp.max(jnp.abs(a), axis=axis, keepdims=True)
    scale = jnp.where(amax > 0, amax / 448.0, 1.0)
    return (a / scale).astype(jnp.float8_e4m3fn).astype(F32) * scale


def _mm(a, w, control):
    """a [..., n] @ w [n, m] in float32 (HIGHEST), or in fp8 operands."""
    if control:
        a, w = _fp8(a, -1), _fp8(w, 0)
    return jnp.matmul(a, w, precision=HIGHEST)


def _rms(x, gain, eps):
    x = x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps)
    return x * gain.astype(F32)


def _rope(x, theta):
    """x [S, heads, hd], positions 0..S-1."""
    S, _, hd = x.shape
    freqs = 1.0 / (theta ** (jnp.arange(0, hd, 2, dtype=F32) / hd))
    ang = jnp.arange(S, dtype=F32)[:, None, None] * freqs
    x1, x2 = jnp.split(x, 2, axis=-1)
    c, s = jnp.cos(ang), jnp.sin(ang)
    return jnp.concatenate([x1 * c - x2 * s, x2 * c + x1 * s], axis=-1)


def _embed_scale(m: dict) -> float:
    return math.sqrt(m["d_model"]) if m.get("embed_scale") == \
        "sqrt_d_model" else 1.0


@functools.partial(jax.jit, static_argnums=(0, 4))
def _layer(mk, x, layers, li, control):
    m = dict(mk)
    eps = m["norm_eps"]
    S = x.shape[0]
    H, K, hd = m["n_heads"], m["n_kv_heads"], head_dim(m)
    w = jax.tree.map(lambda a: a[li].astype(F32), layers)
    h = _rms(x, w["attn_norm"], eps)
    q = _mm(h, w["wq"], control).reshape(S, H, hd)
    k = _mm(h, w["wk"], control).reshape(S, K, hd)
    v = _mm(h, w["wv"], control).reshape(S, K, hd)
    if m["qk_norm"]:
        q, k = _rms(q, w["q_norm"], eps), _rms(k, w["k_norm"], eps)
    q, k = _rope(q, m["rope_theta"]), _rope(k, m["rope_theta"])
    G = H // K
    qg = q.reshape(S, K, G, hd)
    if control:
        qg, k, v = _fp8(qg, -1), _fp8(k, -1), _fp8(v, -1)
    s = jnp.einsum("qkgd,skd->kgqs", qg, k, precision=HIGHEST) * hd ** -0.5
    causal = jnp.arange(S)[:, None] >= jnp.arange(S)[None, :]
    p = jax.nn.softmax(jnp.where(causal, s, -jnp.inf), axis=-1)
    if control:
        p = _fp8(p, -1)
    o = jnp.einsum("kgqs,skd->qkgd", p, v, precision=HIGHEST)
    x = x + _mm(o.reshape(S, H * hd), w["wo"], control)
    h = _rms(x, w["ffn_norm"], eps)
    u = _mm(h, w["w_in"], control)
    if gated(m):
        a = jax.nn.silu(_mm(h, w["w_gate"], control)) * u
    else:
        a = jnp.square(jax.nn.relu(u))
    return x + _mm(a, w["w_out"], control)


@functools.partial(jax.jit, static_argnums=(0,))
def _embed(mk, embed, tokens):
    return embed[tokens].astype(F32) * _embed_scale(dict(mk))


def hidden(m: dict, weights: dict, tokens, control: bool = False):
    """Final-norm hidden states [S, d] of one sequence, in float32."""
    mk = tuple(sorted(m.items()))
    x = _embed(mk, weights["embed"], jnp.asarray(tokens, jnp.int32))
    for li in range(m["n_layers"]):
        x = _layer(mk, x, weights["layers"], li, control)
    return _final(mk, x, weights["final_norm"])


@functools.partial(jax.jit, static_argnums=(0,))
def _final(mk, x, gain):
    return _rms(x, gain, dict(mk)["norm_eps"])


@functools.partial(jax.jit, static_argnums=(4,))
def _chunk_stats(x32, x8, head_chunk, tokens, control, base):
    """Per position, over one chunk of the vocabulary: the float32 max,
    max |logit|, the served token's logit (or -inf when it lies outside the
    chunk) and, with ``control``, the fp8 argmax's value and its float32
    logit."""
    w = head_chunk.astype(F32)
    l32 = jnp.matmul(x32, w, precision=HIGHEST)
    n = w.shape[1]
    local = tokens - base
    inside = (local >= 0) & (local < n)
    served = jnp.where(
        inside, jnp.take_along_axis(l32, jnp.clip(local, 0, n - 1)[:, None],
                                    axis=1)[:, 0], -jnp.inf)
    out = [l32.max(-1), jnp.abs(l32).max(-1), served]
    if control:
        l8 = _mm(x8, w, True)
        i8 = jnp.argmax(l8, axis=-1)
        out += [l8.max(-1), jnp.take_along_axis(l32, i8[:, None], 1)[:, 0]]
    return out


def logit_gaps(m: dict, weights: dict, prompt, served,
               control: bool = False) -> np.ndarray:
    """Relative logit gap of every served token of one request.

    The reference runs once over the prompt and the served tokens (the
    last one not fed back).  At each position that produced a served
    token: (best logit - that token's logit) / max |logit|, all from the
    float32 reference.  With ``control``, the gap of the token that the
    fp8 reference puts first, at the same positions, instead."""
    P = len(prompt)
    seq = np.concatenate([np.asarray(prompt), np.asarray(served)[:-1]])
    x = hidden(m, weights, seq)[P - 1:]
    x8 = hidden(m, weights, seq, control=True)[P - 1:] if control else x
    toks = jnp.asarray(served, jnp.int32)
    head = weights["head"]
    V = head.shape[1]
    best = scale = pick = best8 = at8 = None
    for base in range(0, V, VOCAB_CHUNK):
        st = _chunk_stats(x, x8, head[:, base:base + VOCAB_CHUNK], toks,
                          control, jnp.int32(base))
        if best is None:
            best, scale, pick = st[:3]
            if control:
                best8, at8 = st[3:]
            continue
        best = jnp.maximum(best, st[0])
        scale = jnp.maximum(scale, st[1])
        pick = jnp.maximum(pick, st[2])
        if control:
            better = st[3] > best8
            best8 = jnp.where(better, st[3], best8)
            at8 = jnp.where(better, st[4], at8)
    chosen = at8 if control else pick
    return np.asarray((best - chosen) / scale)


# ------------------------------------------------------------------ counts
# Model FLOPs count the work the architecture needs, two per multiply-add:
# the projections, the feed-forward, the head at the positions whose
# logits are used, and causal attention over the live context (QK^T and
# PV).  They do not count work an implementation adds: padded rows,
# masked-out scores, recomputation.  Norms, RoPE, softmax and the
# embedding gather are left out; at these widths they are far below a
# tenth of a percent.
def matmul_params_per_layer(m: dict) -> int:
    """Weights one token multiplies through in one layer."""
    layer = weight_shapes(dict(m, n_layers=1))["layers"]
    return sum(math.prod(s) for s in layer.values() if len(s) == 3)


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
    of ``contexts`` (the positions a row attends to, itself included):
    every weight once, the head and the norm gains with them, but of the
    embedding table only the rows' own rows; and in every layer each row's
    K and V of its earlier positions read and of its new token written.
    An implementation that gathers whole block tables, or serves padding
    rows, moves more; that is not counted."""
    shapes = jax.tree.leaves(weight_shapes(m),
                             is_leaf=lambda s: isinstance(s, tuple))
    table = m["vocab_size"] * m["d_model"]
    weights = sum(math.prod(s) for s in shapes) - table
    kv_token = 2 * m["n_kv_heads"] * head_dim(m) * \
        jnp.dtype(m["dtype"]).itemsize
    return (jnp.dtype(m["param_dtype"]).itemsize
            * (weights + len(contexts) * m["d_model"])
            + m["n_layers"] * kv_token * sum(contexts))
