"""The plain reference for the language model of LongCat-Flash-Omni (560B-A27B,
https://huggingface.co/meituan-longcat/LongCat-Flash-Omni/blob/main/config.json)
as ONE chip's share of it: a layer of two (latent attention, dense SwiGLU)
pairs with a shortcut-connected expert branch, a softmax router over the routed
experts AND the zero-compute experts, chosen by score + a selection bias.
Straightforward jax.numpy, float32, matmuls at `highest` precision. No kernels,
no cache, no batching, no absorbed attention, and nothing imported from the
program: it reads the program's parameter tree (`layers`, stacked on a leading
layer axis, every leaf of a pair with a sublayer axis of 2 behind it; `[in,
out]` matrices; `embedding`, `final_norm`, `lm_head`) and the configuration
file's published keys (`KEYS`), not the program's config objects.

One layer, for a sequence of S tokens, N an RMSNorm (eps rms_norm_eps), x the
residual stream:

    a1 = x  + MLA_0(N_in0(x))
    h1 = N_post0(a1)
    s  = MoE(h1)                      the shortcut branch starts here ...
    m1 = a1 + FFN_0(h1)               dense SwiGLU, ffn_hidden_size wide
    a2 = m1 + MLA_1(N_in1(m1))
    y  = a2 + FFN_1(N_post1(a2)) + s  ... and lands here

    MLA_i(u), each with its own weights: c_q = N(u Wqa) * sqrt(hidden / q_lora_rank)
        q = c_q Wqb, heads of nope (q_n) + rope (q_r);  [c | k_r] = u Wkva
        c = N(c) * sqrt(hidden / kv_lora_rank);  k_r = RoPE(k_r), one for all heads
        q_r = RoPE(q_r);  [k_n | v] = c Wkvb, heads of nope + v
        scores (q_n . k_n + q_r . k_r) / sqrt(nope + rope), causal, softmax
        o = concat_heads(P v) Wo
    RoPE: rotate-half over the rope dimensions, inv_freq_i = theta^(-2i/rope), unscaled

    MoE(z): p = softmax(z Wr) over ALL the router's columns, router_experts routed
        experts then zero_expert_num zero-compute experts; chosen = the moe_topk
        largest of p + b (b: `router_bias`, e_score_correction_bias);
        g_e = routed_scaling_factor * p_e for the chosen, not renormalised;
        out = sum over the chosen routed e HELD here of g_e SwiGLU_e(z)
              + (sum over the chosen zero-compute e of g_e) z

The share: the router has a column for every routed expert of the model, the
banks hold experts `expert_first .. expert_first + n_routed_experts - 1`. A
chosen routed expert that is held elsewhere adds nothing here (no stand-in for
the absent chips). A zero-compute expert is on the token's own chip whichever
chip that is: its term is added here in full.

Departures from the published description, each also under `assumed` in the
configuration's file: (1) config.json gives mla_scale_q_lora / mla_scale_kv_lora
as booleans; the factors sqrt(hidden / rank) and their place after the latents'
norms are the released modelling code's. (2) norm_topk_prob is absent from
config.json: false, the released default. (3) rotate-half RoPE: an interleaved
layout is a fixed permutation of q_b's and kv_a's columns, invisible under
random weights. (4) the audio and vision encoders and the codec decoder are not
here. (5) weights are random from a seed.

So that a 32k-token request fits one chip beside the bfloat16 weights: a layer
is computed at a time from its own slice of the (bfloat16-rounded) weights,
cast to float32 inside; attention runs HEAD_GROUP heads at a time, Q_BLOCK
queries at a time against all the keys; the MLPs run TOKEN_BLOCK tokens at a
time; every token goes through EVERY held expert densely, one expert at a
time, the outputs summed with the gate as the weight, 0 for an expert not
chosen.

The keyword arguments of `hidden_states` exist for the tolerance probe only
(`tools/tolerance_probe_longcat.py`): what a dropped zero-compute term, a
missing key/value latent scale, a missing query latent scale, a layer's second
attention reading the first one's cached rows, or a selection bias left out do
to the numbers `correct` compares. `rounded_to` is its precision control.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

F32 = jnp.float32
Q_BLOCK = 512      # queries a block: [8 heads, 512, 32768] float32 scores are 0.5 GiB
HEAD_GROUP = 8     # heads attended at a time
TOKEN_BLOCK = 4096  # tokens a block of the MLPs: [4096, 12288] float32 is 0.2 GB
# the keys this file reads from a configuration file's top level: the published
# ones, and the two that say which share of the routed experts this chip holds
KEYS = ("vocab_size", "hidden_size", "num_layers", "num_attention_heads",
        "q_lora_rank", "kv_lora_rank", "qk_nope_head_dim", "qk_rope_head_dim", "v_head_dim",
        "rope_theta", "rms_norm_eps", "ffn_hidden_size", "expert_ffn_hidden_size",
        "n_routed_experts", "moe_topk", "zero_expert_num", "zero_expert_type",
        "routed_scaling_factor", "mla_scale_q_lora", "mla_scale_kv_lora",
        "router_experts", "expert_first")
FAULTS = ("no_zero_term", "no_kv_scale", "no_q_scale", "shared_cache_row",
          "no_selection_bias")


def as_program(pub: dict) -> dict:
    """The same keys under the names and in the forms of the program's
    ModelConfig (a plain mapping: nothing of the program is imported). The
    cell's runner checks the model the program built against it."""
    if pub["zero_expert_type"] != "identity":
        raise ValueError("reference_longcat: zero_expert_type must be 'identity'")
    return dict(
        vocab_size=pub["vocab_size"], hidden_size=pub["hidden_size"],
        num_hidden_layers=pub["num_layers"], num_attention_heads=pub["num_attention_heads"],
        q_lora_rank=pub["q_lora_rank"], kv_lora_rank=pub["kv_lora_rank"],
        qk_nope_head_dim=pub["qk_nope_head_dim"], qk_rope_head_dim=pub["qk_rope_head_dim"],
        v_head_dim=pub["v_head_dim"], rope_theta=float(pub["rope_theta"]),
        rms_norm_eps=pub["rms_norm_eps"], intermediate_size=pub["ffn_hidden_size"],
        moe_intermediate_size=pub["expert_ffn_hidden_size"],
        num_experts=pub["n_routed_experts"], router_experts=pub["router_experts"],
        expert_first=pub["expert_first"], num_experts_per_token=pub["moe_topk"],
        zero_experts=pub["zero_expert_num"],
        routed_scaling_factor=float(pub["routed_scaling_factor"]),
        mla_scale_q_lora=pub["mla_scale_q_lora"], mla_scale_kv_lora=pub["mla_scale_kv_lora"],
        shortcut_moe=True, moe_selection_bias=True, norm_topk_prob=False,
        moe_scoring="softmax", attention_sublayers=2 * pub["num_layers"])


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


def _attention(q, k, v, wo):
    """q / k [S, G, dqk], v [S, G, dv], wo [G, dv, hidden] -> the heads' share of
    the attention output [S, hidden]; causal, Q_BLOCK queries at a time."""
    s, g, d = q.shape
    blocks = -(-s // Q_BLOCK)
    qb = jnp.pad(q, ((0, blocks * Q_BLOCK - s), (0, 0), (0, 0))).reshape(blocks, Q_BLOCK, g, d)
    j = jnp.arange(s)[None, :]

    def block(args):
        qi, b = args
        i = (b * Q_BLOCK + jnp.arange(Q_BLOCK))[:, None]
        sc = jnp.einsum("qgd,sgd->gqs", qi, k) / jnp.sqrt(F32(d))
        p = jax.nn.softmax(jnp.where((j <= i)[None], sc, -jnp.inf), axis=-1)
        return jnp.einsum("qgd,gdh->qh", jnp.einsum("gqs,sgd->qgd", p, v), wo)

    return jax.lax.map(block, (qb, jnp.arange(blocks))).reshape(blocks * Q_BLOCK, -1)[:s]


def _latents(u, w, m: dict, faults: frozenset):
    """u [S, hidden] (normed) -> (c [S, rank] normed and scaled, k_r [S, rope]
    rotated): what a cache would hold of one attention sublayer."""
    rank, h = m["kv_lora_rank"], m["hidden_size"]
    ckr = u @ w["kv_a"].astype(F32)
    c = _norm(ckr[:, :rank], w["kv_a_norm"], m["rms_norm_eps"])
    if m["mla_scale_kv_lora"] and "no_kv_scale" not in faults:
        c = c * F32((h / rank) ** 0.5)
    return c, _rope(ckr[:, None, rank:], float(m["rope_theta"]))[:, 0]


def _mla(u, w, m: dict, faults: frozenset, latents=None):
    """u [S, hidden] (normed) -> (MLA(u) [S, hidden], its latents), un-absorbed.
    `latents`: another sublayer's, attended in this one's place (a control)."""
    s, h = u.shape[0], m["hidden_size"]
    heads, rank, ql = m["num_attention_heads"], m["kv_lora_rank"], m["q_lora_rank"]
    dn, dr, dv = m["qk_nope_head_dim"], m["qk_rope_head_dim"], m["v_head_dim"]
    theta = float(m["rope_theta"])
    c_q = _norm(u @ w["q_a"].astype(F32), w["q_a_norm"], m["rms_norm_eps"])
    if m["mla_scale_q_lora"] and "no_q_scale" not in faults:
        c_q = c_q * F32((h / ql) ** 0.5)
    own = _latents(u, w, m, faults)
    c, k_r = own if latents is None else latents
    groups = heads // min(HEAD_GROUP, heads)
    per = heads // groups
    wqb = w["q_b"].reshape(-1, groups, per * (dn + dr)).transpose(1, 0, 2)
    wkvb = w["kv_b"].reshape(rank, groups, per * (dn + dv)).transpose(1, 0, 2)
    wo = w["o"].reshape(groups, per, dv, -1)

    def group(out, ws):
        wq_g, wkv_g, wo_g = ws
        q = (c_q @ wq_g.astype(F32)).reshape(s, per, dn + dr)
        q = jnp.concatenate([q[..., :dn], _rope(q[..., dn:], theta)], axis=-1)
        kv = (c @ wkv_g.astype(F32)).reshape(s, per, dn + dv)
        k = jnp.concatenate([kv[..., :dn],
                             jnp.broadcast_to(k_r[:, None, :], (s, per, dr))], axis=-1)
        return out + _attention(q, k, kv[..., dn:], wo_g.astype(F32)), None

    out, _ = jax.lax.scan(group, jnp.zeros_like(u), (wqb, wkvb, wo))
    return out, own


def _by_token_blocks(fn, z):
    s = z.shape[0]
    tb = min(TOKEN_BLOCK, s)
    blocks = -(-s // tb)
    zb = jnp.pad(z, ((0, blocks * tb - s), (0, 0))).reshape(blocks, tb, -1)
    return jax.lax.map(fn, zb).reshape(blocks * tb, -1)[:s]


def _swiglu(z, wg, wu, wd):
    return _by_token_blocks(
        lambda zi: (jax.nn.silu(zi @ wg.astype(F32)) * (zi @ wu.astype(F32))) @ wd.astype(F32), z)


def gates(z, w, m: dict, faults: frozenset = frozenset()):
    """z [S, hidden] -> the gate of every router column [S, R + zero], 0 where
    the column was not chosen: softmax over all of them, the moe_topk largest
    of score + bias, gate = routed_scaling_factor * score."""
    p = jax.nn.softmax(z @ w["router"].astype(F32), axis=-1)
    by = p if "no_selection_bias" in faults else p + w["router_bias"].astype(F32)
    _, top_i = jax.lax.top_k(by, m["moe_topk"])
    rows = jnp.arange(z.shape[0])[:, None]
    return jnp.zeros_like(p).at[rows, top_i].set(p[rows, top_i] * m["routed_scaling_factor"])


def _moe(z, w, m: dict, faults: frozenset):
    """z [S, hidden] -> the held routed experts' gated outputs + the
    zero-compute experts' term."""
    first, held, routed = m["expert_first"], m["n_routed_experts"], m["router_experts"]
    g = gates(z, w, m, faults)
    here = g[:, first:first + held]                                    # [S, held]

    def one(out, e):
        wg, wu, wd, ge = e
        return out + ge[:, None] * _swiglu(z, wg, wu, wd), None

    out, _ = jax.lax.scan(one, jnp.zeros_like(z),
                          (w["w_gate"], w["w_up"], w["w_down"], here.T))
    if "no_zero_term" not in faults:
        out = out + jnp.sum(g[:, routed:], axis=-1, keepdims=True) * z
    return out


BRANCH = ("router", "router_bias", "w_gate", "w_up", "w_down")  # a leaf a layer, not a pair


def layer(x, w, m: dict, faults: frozenset = frozenset()):
    """One layer over x [S, hidden]; `w`: the layer's leaves, those of a pair
    [2, ...]."""
    eps = m["rms_norm_eps"]
    w0, w1 = ({n: (v if n in BRANCH else v[j]) for n, v in w.items()} for j in (0, 1))
    out, lat0 = _mla(_norm(x, w0["input_norm"], eps), w0, m, faults)
    a1 = x + out
    h1 = _norm(a1, w0["post_norm"], eps)
    s = _moe(h1, w0, m, faults)
    m1 = a1 + _swiglu(h1, w0["gate"], w0["up"], w0["down"])
    out, _ = _mla(_norm(m1, w1["input_norm"], eps), w1, m, faults,
                  lat0 if "shared_cache_row" in faults else None)
    a2 = m1 + out
    return a2 + _swiglu(_norm(a2, w1["post_norm"], eps), w1["gate"], w1["up"], w1["down"]) + s


@functools.partial(jax.jit, static_argnames=("m", "faults"))
def _layer(x, stack, at, *, m, faults: frozenset):
    # the layer's weights are taken out of the stack inside the program, a
    # matrix where it is used: sliced outside, a whole layer (2.5 GB) is copied
    w = {n: jax.lax.dynamic_index_in_dim(v, at, 0, keepdims=False) for n, v in stack.items()}
    with jax.default_matmul_precision("highest"):
        return layer(x, w, dict(m), faults)


def hidden_states(params, ids, m: dict, **faults):
    """ids [S] -> final-norm hidden states [S, hidden], float32; `m`: the
    configuration file's keys (`KEYS`). A layer at a time. `faults`: FAULTS
    names set true, for the probe."""
    unknown = set(faults) - set(FAULTS)
    if unknown:
        raise TypeError(f"reference_longcat: unknown fault {sorted(unknown)}")
    on = frozenset(k for k, v in faults.items() if v)
    frozen = tuple(sorted((k, m[k]) for k in KEYS))
    x = params["embedding"][ids].astype(F32)
    for i in range(m["num_layers"]):
        x = _layer(x, params["layers"], jnp.int32(i), m=frozen, faults=on)
    with jax.default_matmul_precision("highest"):
        return _norm(x, params["final_norm"], m["rms_norm_eps"])


@jax.jit
def _head_rows(hidden, rows, head):
    with jax.default_matmul_precision("highest"):
        return hidden[rows] @ head.astype(F32)


def logits_at(params, ids, rows, m: dict, **faults):
    """Logits [len(rows), V] float32 at the given positions of `ids` [S]."""
    return _head_rows(hidden_states(params, ids, m, **faults), rows, params["lm_head"])


MATRICES = ("q_a", "q_b", "kv_a", "kv_b", "o", "gate", "up", "down", "router",
            "w_gate", "w_up", "w_down")


def rounded_to(params, bits: int, only=None):
    """The control of the cell's `correct`: the same tree with every matrix
    rounded to `bits`-bit integers and back, one scale an output channel
    (symmetric, largest magnitude / (2^(bits-1) - 1)). 8 bits is the nearest
    precision below the bfloat16 the configuration states. Norm weights and the
    selection bias stay. `only`: the names to round, of those the tree holds
    (the probe rounds a matrix at a time, so that no second copy of the weights
    is held)."""
    top = 2.0 ** (bits - 1) - 1

    @functools.partial(jax.jit, static_argnums=1)
    def rnd(w, axis):
        w32 = w.astype(F32)
        scale = jnp.max(jnp.abs(w32), axis=axis, keepdims=True) / top
        return (jnp.round(w32 / jnp.where(scale > 0, scale, 1.0)) * scale).astype(w.dtype)

    def wanted(n, tree):
        return tree.get(n) is not None and (only is None or n in only)

    out = dict(params, layers=dict(params["layers"]))
    for n in MATRICES:  # [L, (2 | E,) in, out]: a scale a layer (a pair, an expert) and column
        if wanted(n, out["layers"]):
            out["layers"][n] = rnd(out["layers"][n], -2)
    if wanted("embedding", out):  # [V, h]: a scale a token
        out["embedding"] = rnd(out["embedding"], -1)
    if wanted("lm_head", out):    # [h, V]: a scale an output column
        out["lm_head"] = rnd(out["lm_head"], -2)
    return out
