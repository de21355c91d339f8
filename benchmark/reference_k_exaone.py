"""The plain reference for K-EXAONE-236B-A23B's decoder (`model_type:
exaone_moe`, https://huggingface.co/LGAI-EXAONE/K-EXAONE-236B-A23B/blob/main/config.json)
as ONE chip's share of it: sliding-window and full-attention layers in the
published pattern, GQA with an RMSNorm over each head of q and k, a leading
dense layer before expert layers, a shared expert, sigmoid routing over all
the model's experts of which `num_experts` are held here from `expert_first`
on. Straightforward jax.numpy, float32, matmuls at `highest` precision. No
kernels, no cache, no batching, no ring, and nothing imported from the
program: it reads the program's parameter tree (`dense_layers` and `layers`,
each stacked on a leading axis; `[in, out]` matrices; `embedding`,
`final_norm`, `lm_head`) and the configuration file's published keys.

One layer `l` of kind `layer_types[l]`, for a sequence of S tokens (pre-norm,
two norms a layer):

    x1 = x + Attn_l(N1(x));  y = x1 + MLP_l(N2(x1))
    N1 input_norm, N2 post_norm, RMSNorm with eps rms_norm_eps

    Attn(u): q = u Wq (heads x head_dim), k = u Wk, v = u Wv (kv heads x head_dim)
        q = RMSNorm(q) * q_norm,  k = RMSNorm(k) * k_norm   over each head's head_dim
        sliding layer: q, k = RoPE(q), RoPE(k), rotate-half over the whole head,
            inv_freq_i = theta^(-2i/head_dim), unscaled; a FULL layer is not rotated
        scores q . k / sqrt(head_dim), causal; a sliding layer's query at p sees
            keys p - (sliding_window - 1) .. p; softmax;  o = concat_heads(P v) Wo

    dense MLP (the first `first_k_dense_replace` layers): (silu(z Wg) * (z Wu)) Wd
    expert MLP: s = sigmoid(z Wr) over ALL the router's experts; the k largest
        (no groups, no selection bias); g_e = routed_scaling_factor * s_e /
        (sum of the k + 1e-20);
        out = Shared(z) + sum over the chosen e that are HELD here of g_e Expert_e(z)

Departures from the published description (config.json has no key for any;
each is under `assumed` in the configuration's file): the per-head QK-norm and
the unrotated full layers are the EXAONE family's modelling code, not keys; the
router has no selection bias; the multi-token-prediction layer
(`num_nextn_predict_layers` 1) is not here, a served token does not pass
through it.

The share: the router has a column for every expert of the model, the banks
hold experts `expert_first .. expert_first + num_experts - 1`. A chosen expert
that is held elsewhere adds nothing here (no stand-in for the absent chips),
and its gate still counts in the sum the gates are normalised by.

So that a 16k-token request fits one chip beside the bfloat16 weights: a layer
is computed at a time from its own slice of the (bfloat16-rounded) weights,
cast to float32 inside; attention runs one KV head's group of query heads at a
time (their `(P v) Wo_heads` summed, which is `concat_heads(P v) Wo`), Q_BLOCK
queries at a time against all the keys, a sliding layer's band a mask on them;
the MLPs run TOKEN_BLOCK tokens at a time; every token goes through EVERY held
expert densely, one expert at a time, and the outputs are summed with the gate
as the weight, 0 for an expert not chosen.

The keyword arguments of `hidden_states` exist for the tolerance probe only
(`tools/tolerance_probe_k_exaone.py`): what an ignored window, a rotated full
layer, a skipped QK-norm, a missing shared expert, a missing held expert (each
layer's busiest) or softmax in the sigmoid's place do to the numbers `correct`
compares. `rounded_to` is its seventh control.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

F32 = jnp.float32
Q_BLOCK = 512      # queries a block: [8 heads, 512, 16384] float32 scores are 0.27 GB
TOKEN_BLOCK = 4096  # tokens a block of the MLPs: [4096, 18432] float32 is 0.3 GB
# the keys this file reads from a configuration file's top level: the published
# ones, and the two that say which share of the experts this chip holds
KEYS = ("vocab_size", "hidden_size", "num_hidden_layers", "num_attention_heads",
        "num_key_value_heads", "head_dim", "rms_norm_eps", "layer_types", "sliding_window",
        "rope_parameters", "first_k_dense_replace", "intermediate_size",
        "moe_intermediate_size", "num_experts", "num_shared_experts", "num_experts_per_tok",
        "norm_topk_prob", "routed_scaling_factor", "scoring_func", "tie_word_embeddings",
        "router_experts", "expert_first")
FAULTS = ("no_window", "full_rotated", "no_qk_norm", "no_shared_expert",
          "drop_held_expert", "softmax_router")


def as_program(pub: dict) -> dict:
    """The same keys under the names and in the forms of the program's
    ModelConfig (a plain mapping: nothing of the program is imported). The
    cell's runner checks the model the program built against it."""
    n = pub["num_hidden_layers"]
    theta = float(pub["rope_parameters"]["rope_theta"])
    return dict(
        vocab_size=pub["vocab_size"], hidden_size=pub["hidden_size"], num_hidden_layers=n,
        num_attention_heads=pub["num_attention_heads"],
        num_key_value_heads=pub["num_key_value_heads"], head_dim=pub["head_dim"],
        rms_norm_eps=pub["rms_norm_eps"], layer_types=tuple(pub["layer_types"][:n]),
        sliding_window=pub["sliding_window"], qk_norm="head",
        rope_parameters=(("full_attention", (("rope_type", "none"),)),
                         ("sliding_attention", (("rope_theta", theta),
                                                ("rope_type", "default")))),
        first_k_dense_replace=pub["first_k_dense_replace"],
        intermediate_size=pub["intermediate_size"],
        moe_intermediate_size=pub["moe_intermediate_size"],
        num_experts=pub["num_experts"], router_experts=pub["router_experts"],
        expert_first=pub["expert_first"], n_shared_experts=pub["num_shared_experts"],
        num_experts_per_token=pub["num_experts_per_tok"],
        norm_topk_prob=pub["norm_topk_prob"], moe_scoring=pub["scoring_func"],
        routed_scaling_factor=pub["routed_scaling_factor"],
        tie_word_embeddings=pub["tie_word_embeddings"])


def _norm(x, w, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * w.astype(F32)


def _rope(x, theta: float):
    # x [S, H, D], position p rotates pair (i, i + D/2) by p * theta^(-2i/D)
    d = x.shape[-1]
    freq = jnp.asarray(theta ** (-np.arange(0, d, 2, dtype=np.float64) / d), F32)
    ang = jnp.arange(x.shape[0], dtype=F32)[:, None] * freq[None, :]
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    x1, x2 = x[..., : d // 2], x[..., d // 2:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)


def _attention(q, k, v, wo, window):
    """q [S, G, D] (one KV head's query heads), k / v [S, D], wo [G, D, hidden]
    -> those heads' share of the attention output [S, hidden]; causal, a
    sliding layer's band `window` wide, Q_BLOCK queries at a time."""
    s, g, d = q.shape
    blocks = -(-s // Q_BLOCK)
    qb = jnp.pad(q, ((0, blocks * Q_BLOCK - s), (0, 0), (0, 0))).reshape(blocks, Q_BLOCK, g, d)
    j = jnp.arange(s)[None, :]

    def block(args):
        qi, b = args
        i = (b * Q_BLOCK + jnp.arange(Q_BLOCK))[:, None]
        seen = j <= i
        if window is not None:
            seen &= i - j < window
        sc = jnp.einsum("qgd,sd->gqs", qi, k) / jnp.sqrt(F32(d))
        p = jax.nn.softmax(jnp.where(seen[None], sc, -jnp.inf), axis=-1)
        return jnp.einsum("qgd,gdh->qh", jnp.einsum("gqs,sd->qgd", p, v), wo)

    return jax.lax.map(block, (qb, jnp.arange(blocks))).reshape(blocks * Q_BLOCK, -1)[:s]


def _gqa(u, w, m: dict, sliding: bool, faults: frozenset):
    """u [S, hidden] (normed) -> Attn(u) [S, hidden]."""
    s = u.shape[0]
    heads, kv, d = m["num_attention_heads"], m["num_key_value_heads"], m["head_dim"]
    eps, theta = m["rms_norm_eps"], float(dict(m["rope_parameters"])["rope_theta"])
    q = (u @ w["q"].astype(F32)).reshape(s, heads, d)
    k = (u @ w["k"].astype(F32)).reshape(s, kv, d)
    v = (u @ w["v"].astype(F32)).reshape(s, kv, d)
    if "no_qk_norm" not in faults:
        q, k = _norm(q, w["q_norm"], eps), _norm(k, w["k_norm"], eps)
    if sliding or "full_rotated" in faults:
        q, k = _rope(q, theta), _rope(k, theta)
    window = m["sliding_window"] if sliding and "no_window" not in faults else None
    g = heads // kv
    qg = q.reshape(s, kv, g, d).transpose(1, 0, 2, 3)            # [kv, S, G, D]
    wo = w["o"].astype(F32).reshape(kv, g, d, -1)

    def group(out, xs):
        q_h, k_h, v_h, wo_h = xs
        return out + _attention(q_h, k_h, v_h, wo_h, window), None

    out, _ = jax.lax.scan(group, jnp.zeros_like(u),
                          (qg, k.transpose(1, 0, 2), v.transpose(1, 0, 2), wo))
    return out


def _by_token_blocks(fn, z):
    s = z.shape[0]
    tb = min(TOKEN_BLOCK, s)
    blocks = -(-s // tb)
    zb = jnp.pad(z, ((0, blocks * tb - s), (0, 0))).reshape(blocks, tb, -1)
    return jax.lax.map(fn, zb).reshape(blocks * tb, -1)[:s]


def _swiglu(z, wg, wu, wd):
    return _by_token_blocks(
        lambda zi: (jax.nn.silu(zi @ wg.astype(F32)) * (zi @ wu.astype(F32))) @ wd.astype(F32), z)


def _experts(z, w, m: dict, faults: frozenset):
    """z [S, hidden] -> Shared(z) + the held experts' gated outputs."""
    k, first, held = m["num_experts_per_tok"], m["expert_first"], m["num_experts"]
    logits = z @ w["router"].astype(F32)                               # [S, R]
    sigmoid = m["scoring_func"] == "sigmoid" and "softmax_router" not in faults
    score = jax.nn.sigmoid(logits) if sigmoid else jax.nn.softmax(logits, axis=-1)
    top_s, top_i = jax.lax.top_k(score, k)
    gate = top_s / (jnp.sum(top_s, axis=-1, keepdims=True) + 1e-20) if m["norm_topk_prob"] \
        else top_s
    gate = gate * m["routed_scaling_factor"]
    dense = jnp.zeros_like(score).at[jnp.arange(z.shape[0])[:, None], top_i].set(gate)
    here = dense[:, first:first + held]                                # [S, held]
    if "drop_held_expert" in faults:
        # the probe's control: one held expert left out of the layer, the one most
        # tokens chose
        here = here.at[:, jnp.argmax(jnp.sum(here > 0, axis=0))].set(0.0)

    def one(out, e):
        wg, wu, wd, g = e
        return out + g[:, None] * _swiglu(z, wg, wu, wd), None

    out, _ = jax.lax.scan(one, jnp.zeros_like(z),
                          (w["w_gate"], w["w_up"], w["w_down"], here.T))
    if m["num_shared_experts"] and "no_shared_expert" not in faults:
        out = out + _swiglu(z, w["shared_gate"], w["shared_up"], w["shared_down"])
    return out


@functools.partial(jax.jit, static_argnames=("m", "dense", "sliding", "faults"))
def _layer(x, stack, at, *, m, dense: bool, sliding: bool, faults: frozenset):
    # the layer's weights are taken out of the stack inside the program, a
    # matrix where it is used: sliced outside, a whole layer (1.5 GB) is copied
    m = dict(m)
    w = {n: jax.lax.dynamic_index_in_dim(v, at, 0, keepdims=False) for n, v in stack.items()}
    with jax.default_matmul_precision("highest"):
        eps = m["rms_norm_eps"]
        x = x + _gqa(_norm(x, w["input_norm"], eps), w, m, sliding, faults)
        z = _norm(x, w["post_norm"], eps)
        return x + (_swiglu(z, w["gate"], w["up"], w["down"]) if dense
                    else _experts(z, w, m, faults))


def _frozen(v):
    if isinstance(v, dict):
        return tuple(sorted((k, _frozen(x)) for k, x in v.items()))
    return tuple(_frozen(x) for x in v) if isinstance(v, (list, tuple)) else v


def hidden_states(params, ids, m: dict, **faults):
    """ids [S] -> final-norm hidden states [S, hidden], float32; `m`: the
    configuration file's keys (`KEYS`). A layer at a time. `faults`: FAULTS
    names set true, for the probe."""
    unknown = set(faults) - set(FAULTS)
    if unknown:
        raise TypeError(f"reference_k_exaone: unknown fault {sorted(unknown)}")
    on = frozenset(k for k, v in faults.items() if v)
    frozen = tuple(sorted((k, _frozen(m[k])) for k in KEYS if k != "layer_types"))
    x = params["embedding"][ids].astype(F32)
    n_dense = m["first_k_dense_replace"]
    for i, kind in enumerate(m["layer_types"][: m["num_hidden_layers"]]):
        stack, at = (("dense_layers", i) if i < n_dense else ("layers", i - n_dense))
        x = _layer(x, params[stack], jnp.int32(at), m=frozen, dense=i < n_dense,
                   sliding=kind == "sliding_attention", faults=on)
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


MATRICES = ("q", "k", "v", "o", "gate", "up", "down", "router",
            "w_gate", "w_up", "w_down", "shared_gate", "shared_up", "shared_down")


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

    out = dict(params)
    for stack in ("dense_layers", "layers"):
        if stack in out:
            out[stack] = dict(out[stack])
            for n in MATRICES:  # [L, (E,) in, out]: a scale a layer (an expert) and column
                if wanted(n, out[stack]):
                    out[stack][n] = rnd(out[stack][n], -2)
    if wanted("embedding", out):  # [V, h]: a scale a token
        out["embedding"] = rnd(out["embedding"], -1)
    if wanted("lm_head", out):    # [h, V]: a scale an output column
        out["lm_head"] = rnd(out["lm_head"], -2)
    return out
