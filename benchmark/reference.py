"""The plain reference: the Qwen2 / Llama decoder forward in straightforward
jax.numpy, float32, matmuls at `highest` precision. No kernels, no cache, no
remat, no scan, no sharding rules, and nothing imported from the program: it
reads the program's parameter tree (layer weights stacked on a leading axis,
`[in, out]` matrices, `embedding`, `final_norm`, optional `lm_head`) and the
configuration's sizes, and follows the published architecture: pre-norm
RMSNorm, q/k/v projections (+ bias where the tree has one), rotate-half RoPE,
grouped-query causal softmax attention, SwiGLU, tied or untied head.

Run under plain `jax.jit`; on several chips partitioning is GSPMD's.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

F32 = jnp.float32
# the sizes this file reads from a configuration's `model` block
SIZES = ("vocab_size", "hidden_size", "intermediate_size", "num_hidden_layers",
         "num_attention_heads", "num_key_value_heads", "rope_theta", "rms_norm_eps",
         "attention_bias", "tie_word_embeddings")


def _norm(x, w, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * w.astype(F32)


def _rope(x, theta):
    # x [S, H, D]; rotate-half, position p uses angles p * theta^(-2i/D)
    s, _, d = x.shape
    inv = 1.0 / (theta ** (jnp.arange(0, d, 2, dtype=F32) / d))
    ang = jnp.arange(s, dtype=F32)[:, None] * inv[None, :]
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    x1, x2 = x[..., : d // 2], x[..., d // 2:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)


def hidden_states(params, ids, m: dict, *, causal: bool = True, skip_layers=()):
    """ids [S] -> final-norm hidden states [S, h], float32. `causal=False`
    and `skip_layers` exist for the tolerance probe only (what a wrong mask
    or a dropped layer would do to the number `correct` compares)."""
    n_q, n_kv = m["num_attention_heads"], m["num_key_value_heads"]
    d = m.get("head_dim") or m["hidden_size"] // n_q
    eps, theta = m["rms_norm_eps"], m["rope_theta"]
    if m.get("rope_scaling"):
        raise NotImplementedError("reference.py has no scaled RoPE")
    L = params["layers"]
    s = ids.shape[0]
    x = params["embedding"][ids].astype(F32)
    mask = jnp.tril(jnp.ones((s, s), bool)) if causal else jnp.ones((s, s), bool)
    for i in range(m["num_hidden_layers"]):
        if i in skip_layers:
            continue
        w = {k: v[i].astype(F32) for k, v in L.items()}
        h = _norm(x, w["input_norm"], eps)
        q, k, v = h @ w["q"], h @ w["k"], h @ w["v"]
        if "b_q" in w:
            q, k, v = q + w["b_q"], k + w["b_k"], v + w["b_v"]
        q = _rope(q.reshape(s, n_q, d), theta)
        k = _rope(k.reshape(s, n_kv, d), theta)
        v = v.reshape(s, n_kv, d)
        g = n_q // n_kv
        qg = q.reshape(s, n_kv, g, d)
        sc = jnp.einsum("qkgd,skd->kgqs", qg, k) / jnp.sqrt(F32(d))
        sc = jnp.where(mask[None, None], sc, -jnp.inf)
        p = jax.nn.softmax(sc, axis=-1)
        o = jnp.einsum("kgqs,skd->qkgd", p, v).reshape(s, n_q * d)
        x = x + o @ w["o"]
        h = _norm(x, w["post_norm"], eps)
        x = x + (jax.nn.silu(h @ w["gate"]) * (h @ w["up"])) @ w["down"]
    return _norm(x, params["final_norm"], eps)


def _head(params):
    w = params.get("lm_head")
    return (w if w is not None else params["embedding"].T).astype(F32)


def nll_sum(params, ids, targets, m: dict, precision: str = "highest", **kw):
    """Sum over the sequence of -log p(target) and the token count.
    (`precision` other than `highest` is the tolerance probe's.)"""
    with jax.default_matmul_precision(precision):
        logits = hidden_states(params, ids, m, **kw) @ _head(params)
        lse = jax.nn.logsumexp(logits, axis=-1)
        picked = jnp.take_along_axis(logits, targets[:, None], axis=-1)[:, 0]
        return jnp.sum(lse - picked), targets.shape[0]


def logits_at(params, ids, rows, m: dict):
    """Logits [len(rows), V] at the given positions of `ids` [S]."""
    with jax.default_matmul_precision("highest"):
        return hidden_states(params, ids, m)[rows] @ _head(params)


MATRICES = ("q", "k", "v", "o", "gate", "up", "down")


def rounded_to(params, bits: int):
    """The control of a served cell's `correct`: the same tree with every
    matrix rounded to `bits`-bit integers and back, one scale an output
    channel (symmetric, largest magnitude / (2^(bits-1) - 1)). 8 bits is the
    nearest precision below the bfloat16 the configurations state. Norm
    weights and biases stay as they are."""
    top = 2.0 ** (bits - 1) - 1

    def rnd(w, axis):
        w32 = w.astype(F32)
        scale = jnp.max(jnp.abs(w32), axis=axis, keepdims=True) / top
        return (jnp.round(w32 / jnp.where(scale > 0, scale, 1.0)) * scale).astype(w.dtype)

    out = dict(params, layers=dict(params["layers"]))
    for k in MATRICES:  # [L, in, out]: a scale a layer and output column
        out["layers"][k] = rnd(params["layers"][k], -2)
    # [V, h]: a scale a token, which is the tied head's output channel
    out["embedding"] = rnd(params["embedding"], -1)
    if params.get("lm_head") is not None:  # [h, V]
        out["lm_head"] = rnd(params["lm_head"], -2)
    return out


CONTROLS = {"int8": 8, "int4": 4}  # a cell file's `control` -> bits
