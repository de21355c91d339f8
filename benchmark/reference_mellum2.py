"""The plain reference for Mellum2-12B-A2.5B-Instruct's decoder (`model_type:
mellum`, https://huggingface.co/JetBrains/Mellum2-12B-A2.5B-Instruct/blob/main/config.json):
sparse experts in every layer, sliding-window and full-attention layers side by
side, a RoPE law a layer kind. Straightforward jax.numpy, float32, matmuls at
`highest` precision. No kernels, no cache, no batching, no capacity, and nothing
imported from the program: it reads the program's parameter tree (layer weights
stacked on a leading axis, `[in, out]` matrices, `router` `[h, E]`, expert banks
`w_gate`, `w_up` `[E, h, f]`, `w_down` `[E, f, h]`, `embedding`, `final_norm`,
`lm_head`) and the configuration file's published keys.

One layer `l` of kind `layer_types[l]`, for a sequence of S tokens:

    h  = RMSNorm(x)
    q = h Wq, k = h Wk, v = h Wv, split into heads of `head_dim`
        (32 query heads, 4 KV heads of 128: q is 2304 -> 4096, NOT hidden / heads)
    rotate-half RoPE on q and k with the cos / sin of the layer's kind
    scores q k^T / sqrt(head_dim), causal; on a `sliding_attention` layer position
        i sees j only where 0 <= i - j < sliding_window; softmax; o = (P v) Wo
    x1 = x + o
    h  = RMSNorm(x1)
    p  = softmax(h Wr) over all E experts, the k largest, gates p_e / sum of the k
        (`norm_topk_prob` true)
    y  = x1 + sum_e gate_e * (silu(h Wgate_e) * (h Wup_e)) Wdown_e

RoPE, `sliding_attention` (`rope_type: default`): inv_freq_i = theta^(-2i/d).
RoPE, `full_attention` (`rope_type: yarn`; transformers' `_compute_yarn_parameters`,
`truncate` at its default): dim(r) = d ln(L0 / (2 pi r)) / (2 ln theta) with L0 the
original length, low = max(floor(dim(beta_fast)), 0), high = min(ceil(dim(beta_slow)),
d - 1), ramp_i = clip((i - low) / (high - low), 0, 1) for i = 0 .. d/2 - 1,
inv_freq_i = (1 - ramp_i) theta^(-2i/d) + ramp_i theta^(-2i/d) / factor, and cos and
sin are both multiplied by `attention_factor`.

Departures (the configuration file's `assumed`): no QK-norm, no multi-token-
prediction head, `layer_types` alone decides which layers slide.

So that a 14k-token request fits one chip beside the bfloat16 weights: a layer
is computed at a time from its own slice of the (bfloat16-rounded) weights, cast
to float32 inside; attention runs in blocks of `Q_BLOCK` queries against all the
keys; every token goes through EVERY expert densely, one expert at a time, and
the outputs are summed with the gate as the weight, 0 for an expert not chosen.

The keyword arguments `no_band`, `one_rope`, `drop_last_expert` exist for the
tolerance probe only (`tools/tolerance_probe_mellum2.py`): what a missing band,
the sliding layers' RoPE on the full layers, or one expert of every token left
out do to the numbers `correct` compares. `rounded_to` is its fourth control.
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
import numpy as np

F32 = jnp.float32
Q_BLOCK = 512  # queries a block: [32 heads, 512, 16384] float32 scores are 1 GiB
# the published keys this file reads from a configuration file's top level
KEYS = ("vocab_size", "hidden_size", "num_hidden_layers", "num_attention_heads",
        "num_key_value_heads", "head_dim", "rms_norm_eps", "layer_types", "sliding_window",
        "rope_parameters", "num_experts", "num_experts_per_tok", "moe_intermediate_size",
        "norm_topk_prob", "tie_word_embeddings")


def inv_freq(law: dict, d: int) -> np.ndarray:
    """[d / 2] rotation frequencies of one `rope_parameters` section, float64."""
    theta = float(law["rope_theta"])
    base = theta ** (-np.arange(0, d, 2, dtype=np.float64) / d)
    if law.get("rope_type", "default") == "default":
        return base
    if law["rope_type"] != "yarn":
        raise NotImplementedError(f"reference_mellum2: rope_type {law['rope_type']!r}")
    l0 = float(law["original_max_position_embeddings"])

    def dim(rotations):
        return d * math.log(l0 / (2.0 * math.pi * rotations)) / (2.0 * math.log(theta))

    low = max(math.floor(dim(float(law["beta_fast"]))), 0)
    high = min(math.ceil(dim(float(law["beta_slow"]))), d - 1)
    ramp = np.clip((np.arange(d // 2, dtype=np.float64) - low) / max(high - low, 0.001), 0, 1)
    return (1.0 - ramp) * base + ramp * base / float(law["factor"])


def amplitude(law: dict) -> float:
    """What cos and sin are multiplied by: YaRN's attention factor, 1 otherwise."""
    if law.get("rope_type", "default") != "yarn":
        return 1.0
    return float(law.get("attention_factor") or 0.1 * math.log(float(law["factor"])) + 1.0)


def _norm(x, w, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * w.astype(F32)


def _rope(x, freq, amp):
    # x [S, H, D], position p rotates by p * freq
    d = x.shape[-1]
    ang = jnp.arange(x.shape[0], dtype=F32)[:, None] * freq[None, :]
    cos, sin = (jnp.cos(ang) * amp)[:, None, :], (jnp.sin(ang) * amp)[:, None, :]
    x1, x2 = x[..., : d // 2], x[..., d // 2:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)


def _attention(q, k, v, window):
    """q [S, n_q, d], k / v [S, n_kv, d] -> [S, n_q * d]; causal, banded where
    `window` is a number; Q_BLOCK queries at a time against every key."""
    s, n_q, d = q.shape
    n_kv = k.shape[1]
    blocks = -(-s // Q_BLOCK)
    qb = jnp.pad(q, ((0, blocks * Q_BLOCK - s), (0, 0), (0, 0)))
    qb = qb.reshape(blocks, Q_BLOCK, n_kv, n_q // n_kv, d)
    j = jnp.arange(s)[None, :]

    def block(args):
        qi, b = args
        i = (b * Q_BLOCK + jnp.arange(Q_BLOCK))[:, None]
        seen = j <= i
        if window is not None:
            seen &= i - j < window
        sc = jnp.einsum("qkgd,skd->kgqs", qi, k) / jnp.sqrt(F32(d))
        p = jax.nn.softmax(jnp.where(seen[None, None], sc, -jnp.inf), axis=-1)
        return jnp.einsum("kgqs,skd->qkgd", p, v).reshape(Q_BLOCK, n_q * d)

    return jax.lax.map(block, (qb, jnp.arange(blocks))).reshape(blocks * Q_BLOCK, -1)[:s]


def _experts(h, w, k: int, renormalise: bool, drop_last: bool):
    """h [S, hidden] -> the expert block's output [S, hidden]."""
    p = jax.nn.softmax(h @ w["router"].astype(F32), axis=-1)          # [S, E]
    top_p, top_i = jax.lax.top_k(p, k)
    gate = top_p / jnp.sum(top_p, axis=-1, keepdims=True) if renormalise else top_p
    if drop_last:  # the probe's control: the least of a token's k experts left out
        gate = gate.at[:, -1].set(0.0)
    dense = jnp.zeros_like(p).at[jnp.arange(h.shape[0])[:, None], top_i].set(gate)

    def one(out, e):
        wg, wu, wd, g = e
        return out + g[:, None] * ((jax.nn.silu(h @ wg.astype(F32)) * (h @ wu.astype(F32)))
                                   @ wd.astype(F32)), None

    out, _ = jax.lax.scan(one, jnp.zeros_like(h),
                          (w["w_gate"], w["w_up"], w["w_down"], dense.T))
    return out


@functools.partial(jax.jit, static_argnames=("rope_kind", "n_q", "n_kv", "d", "eps", "window",
                                             "k", "renormalise", "drop_last", "laws"))
def _layer(x, w, *, rope_kind, n_q, n_kv, d, eps, window, k, renormalise, drop_last, laws):
    with jax.default_matmul_precision("highest"):
        law = dict(dict(laws)[rope_kind])
        freq, amp = jnp.asarray(inv_freq(law, d), F32), amplitude(law)
        s = x.shape[0]
        h = _norm(x, w["input_norm"], eps)
        q = _rope((h @ w["q"].astype(F32)).reshape(s, n_q, d), freq, amp)
        kk = _rope((h @ w["k"].astype(F32)).reshape(s, n_kv, d), freq, amp)
        v = (h @ w["v"].astype(F32)).reshape(s, n_kv, d)
        x = x + _attention(q, kk, v, window) @ w["o"].astype(F32)
        h = _norm(x, w["post_norm"], eps)
        return x + _experts(h, w, k, renormalise, drop_last)


def _frozen(m: dict):
    return tuple(sorted((kind, tuple(sorted(law.items())))
                        for kind, law in m["rope_parameters"].items()))


def hidden_states(params, ids, m: dict, *, no_band=False, one_rope=False,
                  drop_last_expert=False):
    """ids [S] -> final-norm hidden states [S, hidden], float32; `m`: the
    configuration file's published keys. A layer at a time."""
    x = params["embedding"][ids].astype(F32)
    layers = params["layers"]
    for i, kind in enumerate(m["layer_types"][: m["num_hidden_layers"]]):
        sliding = kind == "sliding_attention"
        x = _layer(
            x, {n: v[i] for n, v in layers.items()},
            rope_kind="sliding_attention" if one_rope else kind,
            n_q=m["num_attention_heads"], n_kv=m["num_key_value_heads"], d=m["head_dim"],
            eps=m["rms_norm_eps"],
            window=m["sliding_window"] if sliding and not no_band else None,
            k=m["num_experts_per_tok"], renormalise=bool(m["norm_topk_prob"]),
            drop_last=drop_last_expert, laws=_frozen(m))
    with jax.default_matmul_precision("highest"):
        return _norm(x, params["final_norm"], m["rms_norm_eps"])


@jax.jit
def _head_rows(hidden, rows, head):
    with jax.default_matmul_precision("highest"):
        return hidden[rows] @ head.astype(F32)


def logits_at(params, ids, rows, m: dict, **faults):
    """Logits [len(rows), V] float32 at the given positions of `ids` [S]."""
    w = params.get("lm_head")
    return _head_rows(hidden_states(params, ids, m, **faults), rows,
                      w if w is not None else params["embedding"].T)


MATRICES = ("q", "k", "v", "o", "router", "w_gate", "w_up", "w_down")


def rounded_to(params, bits: int, only=None):
    """The control of the cell's `correct`: the same tree with every matrix
    rounded to `bits`-bit integers and back, one scale an output channel
    (symmetric, largest magnitude / (2^(bits-1) - 1)). 8 bits is the nearest
    precision below the bfloat16 the configuration states. Norm weights stay.
    `only`: the names to round, of those the tree holds (the probe rounds a
    matrix at a time, so that no second copy of the weights is held)."""
    top = 2.0 ** (bits - 1) - 1

    @functools.partial(jax.jit, static_argnums=1)
    def rnd(w, axis):
        w32 = w.astype(F32)
        scale = jnp.max(jnp.abs(w32), axis=axis, keepdims=True) / top
        return (jnp.round(w32 / jnp.where(scale > 0, scale, 1.0)) * scale).astype(w.dtype)

    def wanted(n, tree):
        return tree.get(n) is not None and (only is None or n in only)

    out = dict(params, layers=dict(params["layers"]))
    for n in MATRICES:  # [L, (E,) in, out]: a scale a layer (an expert) and output column
        if wanted(n, out["layers"]):
            out["layers"][n] = rnd(out["layers"][n], -2)
    if wanted("embedding", out):  # [V, h]: a scale a token
        out["embedding"] = rnd(out["embedding"], -1)
    if wanted("lm_head", out):    # [h, V]: a scale an output column
        out["lm_head"] = rnd(out["lm_head"], -2)
    return out
