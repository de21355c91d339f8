"""The plain reference for EvaByte's decoder (`model_type: evabyte`,
`attention_class: eva`,
https://huggingface.co/EvaByte/EvaByte/blob/main/config.json): a byte-level
model whose attention is EVA ("efficient attention via control variates",
ICLR 2023, in the form the release describes). Straightforward jax.numpy,
float32, matmuls at `highest` precision. No kernels, no cache, no batching,
no blocks, and nothing imported from the program: it reads the program's
parameter tree (`layers` stacked on a leading axis; `[in, out]` matrices;
`embedding`, `final_norm`, `lm_head` with the prediction heads side by side)
and the configuration file's published keys.

    N(x) = x / rms(x) * (1 + w)                       eps rms_norm_eps
    block: x' = x + Attn(N1(x));  y = x' + W_d(silu(W_g N2(x')) * W_u N2(x'))

Attention, a head (no grouping at the published widths; grouped heads share
their KV head's keys, values and pooling vectors): q_i, k_i, v_i from W_q,
W_k, W_v; q and k rotated (rotate-half over the whole head, theta
rope_theta, at the true position i). Positions fall into windows w(i) = i //
window_size and chunks c(i) = i // chunk_size. For every COMPLETE chunk C,
with the head's learned vectors mu, phi and s = head_dim^-1/2:

    k~_C = sum_{j in C} softmax_{j in C}(s k_j . mu)  k_j
    v~_C = sum_{j in C} softmax_{j in C}(s k_j . phi) v_j

A query at i sees the singletons S_i = {j : w(j) = w(i), j <= i} and the
summaries R_i = {C : C < (window_size / chunk_size) w(i)} (every chunk of
every closed window, none of its own), under one softmax over the scores
s q_i . k_j and s q_i . k~_C, a dense mask over [k | k~]; then W_o. Final N,
head [hidden -> num_pred_heads x vocab_size], head j at position t scoring
byte t + 1 + j; a served byte is head 0's.

`assumed` (config.json pins none of these; the configuration's file gives
each its reason): (a) the pooling weights as above, a softmax over the chunk
of s k . mu and of s k . phi with one learned vector a head each (the paper's
estimator also carries -|k|^2 / 2 in the exponent and draws its vector at
random); (b) keys are pooled after rotation; (c) a window's summaries are
seen from the next window on; (d) one scale s on both kinds of score and no
count term on a summary's score; (e) mu, phi drawn from the seed as a unit
normal clamped to [-1, 1] an element; (f) pre-norm with two norms a layer;
(g) head j predicts byte t + 1 + j.

So that a 32k-byte request fits one chip beside the bfloat16 weights: a layer
is computed at a time from its own slice of the (bfloat16-rounded) weights,
cast to float32 inside; attention runs one KV head at a time (its query
heads' `(P v) Wo_heads` summed, which is `concat_heads(P v) Wo`), Q_BLOCK
queries at a time against all the keys and summaries; the MLP runs
TOKEN_BLOCK tokens at a time.

The keyword arguments of `hidden_states` exist for the tolerance probe only
(`tools/tolerance_probe_evabyte.py`): what each of FAULTS does to the numbers
`correct` compares. `logits_at(head=1)` and `rounded_to` are its two others.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

F32 = jnp.float32
Q_BLOCK = 512       # queries a block: [512, 32768 + 2048] float32 scores are 71 MB
TOKEN_BLOCK = 4096  # tokens a block of the MLP: [4096, 11008] float32 is 0.18 GB
# the keys this file reads from a configuration file's top level: published ones
KEYS = ("vocab_size", "hidden_size", "num_hidden_layers", "num_attention_heads",
        "num_key_value_heads", "intermediate_size", "rms_norm_eps", "rope_theta",
        "attention_class", "window_size", "chunk_size", "num_pred_heads",
        "norm_add_unit_offset", "fp32_skip_add", "attention_bias", "tie_word_embeddings")
FAULTS = ("no_summaries", "open_window_summaries", "sliding_window", "mean_pooling",
          "no_unit_offset")


def as_program(pub: dict) -> dict:
    """The same keys under the names and in the forms of the program's
    ModelConfig (a plain mapping: nothing of the program is imported). The
    cell's runner checks the model the program built against it."""
    return dict(
        vocab_size=pub["vocab_size"], hidden_size=pub["hidden_size"],
        num_hidden_layers=pub["num_hidden_layers"],
        num_attention_heads=pub["num_attention_heads"],
        num_key_value_heads=pub["num_key_value_heads"],
        head_dim=pub["hidden_size"] // pub["num_attention_heads"],
        intermediate_size=pub["intermediate_size"], rms_norm_eps=pub["rms_norm_eps"],
        rope_theta=float(pub["rope_theta"]), attention_class=pub["attention_class"],
        window_size=pub["window_size"], chunk_size=pub["chunk_size"],
        num_pred_heads=pub["num_pred_heads"],
        norm_add_unit_offset=pub["norm_add_unit_offset"],
        fp32_skip_add=pub["fp32_skip_add"], attention_bias=pub["attention_bias"],
        tie_word_embeddings=pub["tie_word_embeddings"])


def _norm(x, w, eps, offset: bool):
    scale = w.astype(F32) + 1.0 if offset else w.astype(F32)
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * scale


def _rope(x, theta: float):
    # x [S, H, D], position p rotates pair (i, i + D/2) by p * theta^(-2i/D)
    d = x.shape[-1]
    freq = jnp.asarray(theta ** (-np.arange(0, d, 2, dtype=np.float64) / d), F32)
    ang = jnp.arange(x.shape[0], dtype=F32)[:, None] * freq[None, :]
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    x1, x2 = x[..., : d // 2], x[..., d // 2:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)


def summaries(k, v, mu, phi, chunk: int, mean: bool = False):
    """k, v [S, D] (one KV head's rotated keys and values), mu, phi [D] ->
    (k~, v~) [S // chunk, D]: the pooled key and value of every complete
    chunk. `mean`: the probe's control, a plain mean over the chunk."""
    n, d = k.shape[0] // chunk, k.shape[1]
    kc, vc = k[:n * chunk].reshape(n, chunk, d), v[:n * chunk].reshape(n, chunk, d)
    s = 1.0 / jnp.sqrt(F32(d))
    a = jax.nn.softmax(kc @ mu.astype(F32) * s, axis=-1)      # [n, chunk]
    b = jax.nn.softmax(kc @ phi.astype(F32) * s, axis=-1)
    if mean:
        a = b = jnp.full_like(a, 1.0 / chunk)
    return jnp.einsum("nc,ncd->nd", a, kc), jnp.einsum("nc,ncd->nd", b, vc)


def _attention(q, k, v, ks, vs, wo, m: dict, faults: frozenset):
    """q [S, G, D] (one KV head's query heads), k / v [S, D], ks / vs
    [S // chunk, D], wo [G, D, hidden] -> those heads' share of the attention
    output [S, hidden]: one softmax over the singletons and the summaries a
    query may see, a dense mask over [k | k~], Q_BLOCK queries at a time."""
    s, g, d = q.shape
    window, chunk = m["window_size"], m["chunk_size"]
    blocks = -(-s // Q_BLOCK)
    qb = jnp.pad(q, ((0, blocks * Q_BLOCK - s), (0, 0), (0, 0))).reshape(blocks, Q_BLOCK, g, d)
    j = jnp.arange(s)[None, :]
    c = jnp.arange(ks.shape[0])[None, :]
    keys, vals = jnp.concatenate([k, ks]), jnp.concatenate([v, vs])

    def block(args):
        qi, b = args
        i = (b * Q_BLOCK + jnp.arange(Q_BLOCK))[:, None]
        if "sliding_window" in faults:
            single = (j <= i) & (i - j < window)
        else:
            single = (j <= i) & (j // window == i // window)
        if "no_summaries" in faults:
            summary = jnp.zeros((Q_BLOCK, c.shape[1]), bool)
        elif "open_window_summaries" in faults:
            summary = (c + 1) * chunk - 1 <= i       # every complete chunk
        else:
            summary = c < (window // chunk) * (i // window)
        seen = jnp.concatenate([single, summary], axis=1)
        sc = jnp.einsum("qgd,sd->gqs", qi, keys) / jnp.sqrt(F32(d))
        p = jax.nn.softmax(jnp.where(seen[None], sc, -jnp.inf), axis=-1)
        return jnp.einsum("qgd,gdh->qh", jnp.einsum("gqs,sd->qgd", p, vals), wo)

    return jax.lax.map(block, (qb, jnp.arange(blocks))).reshape(blocks * Q_BLOCK, -1)[:s]


def _eva(u, w, m: dict, faults: frozenset):
    """u [S, hidden] (normed) -> Attn(u) [S, hidden]."""
    s = u.shape[0]
    heads, kv = m["num_attention_heads"], m["num_key_value_heads"]
    d = m["hidden_size"] // heads
    theta = float(m["rope_theta"])
    q = _rope((u @ w["q"].astype(F32)).reshape(s, heads, d), theta)
    k = _rope((u @ w["k"].astype(F32)).reshape(s, kv, d), theta)
    v = (u @ w["v"].astype(F32)).reshape(s, kv, d)
    g = heads // kv
    qg = q.reshape(s, kv, g, d).transpose(1, 0, 2, 3)            # [kv, S, G, D]
    wo = w["o"].astype(F32).reshape(kv, g, d, -1)

    def head(out, xs):
        q_h, k_h, v_h, wo_h, mu, phi = xs
        ks, vs = summaries(k_h, v_h, mu, phi, m["chunk_size"], "mean_pooling" in faults)
        return out + _attention(q_h, k_h, v_h, ks, vs, wo_h, m, faults), None

    out, _ = jax.lax.scan(head, jnp.zeros_like(u),
                          (qg, k.transpose(1, 0, 2), v.transpose(1, 0, 2), wo,
                           w["eva_mu"], w["eva_phi"]))
    return out


def _swiglu(z, wg, wu, wd):
    s = z.shape[0]
    tb = min(TOKEN_BLOCK, s)
    blocks = -(-s // tb)
    zb = jnp.pad(z, ((0, blocks * tb - s), (0, 0))).reshape(blocks, tb, -1)
    out = jax.lax.map(
        lambda zi: (jax.nn.silu(zi @ wg.astype(F32)) * (zi @ wu.astype(F32))) @ wd.astype(F32),
        zb)
    return out.reshape(blocks * tb, -1)[:s]


@functools.partial(jax.jit, static_argnames=("m", "faults"))
def _layer(x, stack, at, *, m, faults: frozenset):
    # the layer's weights are taken out of the stack inside the program, a
    # matrix where it is used: sliced outside, a whole layer (0.4 GB) is copied
    m = dict(m)
    w = {n: jax.lax.dynamic_index_in_dim(v, at, 0, keepdims=False) for n, v in stack.items()}
    offset = m["norm_add_unit_offset"] and "no_unit_offset" not in faults
    with jax.default_matmul_precision("highest"):
        eps = m["rms_norm_eps"]
        x = x + _eva(_norm(x, w["input_norm"], eps, offset), w, m, faults)
        return x + _swiglu(_norm(x, w["post_norm"], eps, offset),
                           w["gate"], w["up"], w["down"])


def hidden_states(params, ids, m: dict, **faults):
    """ids [S] -> final-norm hidden states [S, hidden], float32; `m`: the
    configuration file's keys (`KEYS`). A layer at a time. `faults`: FAULTS
    names set true, for the probe."""
    unknown = set(faults) - set(FAULTS)
    if unknown:
        raise TypeError(f"reference_evabyte: unknown fault {sorted(unknown)}")
    if m["attention_class"] != "eva" or m["attention_bias"] or m["tie_word_embeddings"]:
        raise ValueError("reference_evabyte: EVA attention, no bias, an untied head")
    on = frozenset(k for k, v in faults.items() if v)
    frozen = tuple(sorted((k, m[k]) for k in KEYS))
    x = params["embedding"][ids].astype(F32)
    for i in range(m["num_hidden_layers"]):
        x = _layer(x, params["layers"], jnp.int32(i), m=frozen, faults=on)
    offset = m["norm_add_unit_offset"] and "no_unit_offset" not in on
    with jax.default_matmul_precision("highest"):
        return _norm(x, params["final_norm"], m["rms_norm_eps"], offset)


@jax.jit
def _head_rows(hidden, rows, head):
    with jax.default_matmul_precision("highest"):
        return hidden[rows] @ head.astype(F32)


def logits_at(params, ids, rows, m: dict, head=0, **faults):
    """Logits float32 at the given positions of `ids` [S]: [len(rows), V] of
    prediction head `head` (0: the served byte's), or [len(rows),
    num_pred_heads, V] with `head=None`."""
    v = m["vocab_size"]
    out = _head_rows(hidden_states(params, ids, m, **faults), rows, params["lm_head"])
    out = out.reshape(out.shape[0], m["num_pred_heads"], v)
    return out if head is None else out[:, head]


MATRICES = ("q", "k", "v", "o", "gate", "up", "down")


def rounded_to(params, bits: int, only=None):
    """The control of the cell's `correct`: the same tree with every matrix
    rounded to `bits`-bit integers and back, one scale an output channel
    (symmetric, largest magnitude / (2^(bits-1) - 1)). 8 bits is the nearest
    precision below the bfloat16 the configuration states. Norm weights and
    the pooling vectors stay. `only`: the names to round, of those the tree
    holds (the probe rounds a matrix at a time, so that no second copy of the
    weights is held)."""
    top = 2.0 ** (bits - 1) - 1

    @functools.partial(jax.jit, static_argnums=1)
    def rnd(w, axis):
        w32 = w.astype(F32)
        scale = jnp.max(jnp.abs(w32), axis=axis, keepdims=True) / top
        return (jnp.round(w32 / jnp.where(scale > 0, scale, 1.0)) * scale).astype(w.dtype)

    def wanted(n, tree):
        return tree.get(n) is not None and (only is None or n in only)

    out = dict(params)
    out["layers"] = dict(out["layers"])
    for n in MATRICES:  # [L, in, out]: a scale a layer and column
        if wanted(n, out["layers"]):
            out["layers"][n] = rnd(out["layers"][n], -2)
    if wanted("embedding", out):  # [V, h]: a scale a token
        out["embedding"] = rnd(out["embedding"], -1)
    if wanted("lm_head", out):    # [h, heads x V]: a scale an output column
        out["lm_head"] = rnd(out["lm_head"], -2)
    return out
