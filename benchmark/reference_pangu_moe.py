"""The plain reference for openPangu-Ultra-MoE-718B's decoder (`model_type:
pangu_ultra_moe`, https://huggingface.co/FreedomIntelligence/openPangu-Ultra-MoE-718B/blob/main/config.json)
as ONE chip's share of it: latent attention (MLA), leading dense layers before
expert layers, a shared expert, sigmoid routing over all the model's experts
of which `n_routed_experts` are held here from `expert_first` on, four RMSNorms
a layer. Straightforward jax.numpy, float32, matmuls at `highest` precision.
No kernels, no cache, no batching, no absorbed attention, and nothing imported
from the program: it reads the program's parameter tree (`dense_layers` and
`layers`, each stacked on a leading axis; `[in, out]` matrices; `embedding`,
`final_norm`, `lm_head`) and the configuration file's published keys.

One layer, for a sequence of S tokens (`sandwich_norm`):

    a  = N2(Attn(N1(x)));  x1 = x + a;  m = N4(MLP(N3(x1)));  y = x1 + m
    N1 input_norm, N2 attn_out_norm, N3 post_norm (the MLP's input), N4 mlp_out_norm

    Attn(u): c_q = RMSNorm(u Wqa);  q = c_q Wqb, heads of nope (q_n) + rope (q_r)
        [c | k_r] = u Wkva;  c = RMSNorm(c);  k_r = RoPE(k_r), one for all heads
        q_r = RoPE(q_r);  [k_n | v] = c Wkvb, heads of nope + v
        scores (q_n . k_n + q_r . k_r) / sqrt(nope + rope), causal, softmax
        o = concat_heads(P v) Wo
    RoPE: rotate-half over the rope dimensions, inv_freq_i = theta^(-2i/rope), unscaled

    dense MLP (the first `first_k_dense_replace` layers): (silu(z Wg) * (z Wu)) Wd
    expert MLP: s = sigmoid(z Wr) over ALL the router's experts; the k largest;
        gates g_e = routed_scaling_factor * s_e / (sum of the k + 1e-20);
        out = Shared(z) + sum over the chosen e that are HELD here of g_e Expert_e(z)

The share: the router has a column for every expert of the model, the banks
hold experts `expert_first .. expert_first + n_routed_experts - 1`. A chosen
expert that is held elsewhere adds nothing here (no stand-in for the absent
chips), and its gate still counts in the sum the gates are normalised by.

So that a 30k-token request fits one chip beside the bfloat16 weights: a layer
is computed at a time from its own slice of the (bfloat16-rounded) weights,
cast to float32 inside; attention runs HEAD_GROUP heads at a time (their
`(P v) Wo_heads` summed, which is `concat_heads(P v) Wo`), Q_BLOCK queries at a
time against all the keys; the MLPs run TOKEN_BLOCK tokens at a time; every
token goes through EVERY held expert densely, one expert at a time, and the
outputs are summed with the gate as the weight, 0 for an expert not chosen.

The keyword arguments of `hidden_states` exist for the tolerance probe only
(`tools/tolerance_probe_pangu_moe.py`): what an unrotated `k_r`, a skipped norm
of `c`, a skipped output norm, a missing shared expert, a missing held expert
(each layer's busiest)
or softmax in the sigmoid's place do to the numbers `correct` compares.
`rounded_to` is its seventh control.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

F32 = jnp.float32
Q_BLOCK = 512      # queries a block: [8 heads, 512, 32768] float32 scores are 0.5 GiB
HEAD_GROUP = 8     # heads attended at a time
TOKEN_BLOCK = 4096  # tokens a block of the MLPs: [4096, 18432] float32 is 0.3 GB
# the keys this file reads from a configuration file's top level: the published
# ones, and the two that say which share of the experts this chip holds
KEYS = ("vocab_size", "hidden_size", "num_hidden_layers", "num_attention_heads",
        "q_lora_rank", "kv_lora_rank", "qk_nope_head_dim", "qk_rope_head_dim", "v_head_dim",
        "rope_theta", "rms_norm_eps", "first_k_dense_replace", "intermediate_size",
        "moe_intermediate_size", "n_routed_experts", "n_shared_experts",
        "num_experts_per_tok", "norm_topk_prob", "routed_scaling_factor", "sandwich_norm",
        "tie_word_embeddings", "router_experts", "expert_first")
FAULTS = ("k_rope_unrotated", "no_latent_norm", "no_attn_out_norm", "no_shared_expert",
          "drop_held_expert", "softmax_router")


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


def _mla(u, w, m: dict, faults: frozenset):
    """u [S, hidden] (normed) -> Attn(u) [S, hidden], un-absorbed."""
    s = u.shape[0]
    heads, rank = m["num_attention_heads"], m["kv_lora_rank"]
    dn, dr, dv = m["qk_nope_head_dim"], m["qk_rope_head_dim"], m["v_head_dim"]
    eps, theta = m["rms_norm_eps"], float(m["rope_theta"])
    c_q = _norm(u @ w["q_a"].astype(F32), w["q_a_norm"], eps)
    ckr = u @ w["kv_a"].astype(F32)
    c, k_r = ckr[:, :rank], ckr[:, rank:]
    if "no_latent_norm" not in faults:
        c = _norm(c, w["kv_a_norm"], eps)
    if "k_rope_unrotated" not in faults:
        k_r = _rope(k_r[:, None, :], theta)[:, 0]
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
    k, first, held = m["num_experts_per_tok"], m["expert_first"], m["n_routed_experts"]
    logits = z @ w["router"].astype(F32)                               # [S, R]
    score = (jax.nn.softmax(logits, axis=-1) if "softmax_router" in faults
             else jax.nn.sigmoid(logits))
    top_s, top_i = jax.lax.top_k(score, k)
    gate = top_s / (jnp.sum(top_s, axis=-1, keepdims=True) + 1e-20) if m["norm_topk_prob"] \
        else top_s
    gate = gate * m["routed_scaling_factor"]
    dense = jnp.zeros_like(score).at[jnp.arange(z.shape[0])[:, None], top_i].set(gate)
    here = dense[:, first:first + held]                                # [S, held]
    if "drop_held_expert" in faults:
        # the probe's control: one held expert left out of the layer, the one most
        # tokens chose (a held expert receives 3% of the tokens on average, and one
        # that random weights leave nearly unchosen would show nothing)
        here = here.at[:, jnp.argmax(jnp.sum(here > 0, axis=0))].set(0.0)

    def one(out, e):
        wg, wu, wd, g = e
        return out + g[:, None] * _swiglu(z, wg, wu, wd), None

    out, _ = jax.lax.scan(one, jnp.zeros_like(z),
                          (w["w_gate"], w["w_up"], w["w_down"], here.T))
    if m["n_shared_experts"] and "no_shared_expert" not in faults:
        out = out + _swiglu(z, w["shared_gate"], w["shared_up"], w["shared_down"])
    return out


@functools.partial(jax.jit, static_argnames=("m", "dense", "faults"))
def _layer(x, stack, at, *, m, dense: bool, faults: frozenset):
    # the layer's weights are taken out of the stack inside the program, a
    # matrix where it is used: sliced outside, a whole layer (2 GB) is copied
    m = dict(m)
    w = {n: jax.lax.dynamic_index_in_dim(v, at, 0, keepdims=False) for n, v in stack.items()}
    with jax.default_matmul_precision("highest"):
        eps, sandwich = m["rms_norm_eps"], m["sandwich_norm"]
        a = _mla(_norm(x, w["input_norm"], eps), w, m, faults)
        if sandwich and "no_attn_out_norm" not in faults:
            a = _norm(a, w["attn_out_norm"], eps)
        x = x + a
        z = _norm(x, w["post_norm"], eps)
        y = (_swiglu(z, w["gate"], w["up"], w["down"]) if dense
             else _experts(z, w, m, faults))
        if sandwich:
            y = _norm(y, w["mlp_out_norm"], eps)
        return x + y


def hidden_states(params, ids, m: dict, **faults):
    """ids [S] -> final-norm hidden states [S, hidden], float32; `m`: the
    configuration file's keys (`KEYS`). A layer at a time. `faults`: FAULTS
    names set true, for the probe."""
    unknown = set(faults) - set(FAULTS)
    if unknown:
        raise TypeError(f"reference_pangu_moe: unknown fault {sorted(unknown)}")
    on = frozenset(k for k, v in faults.items() if v)
    frozen = tuple(sorted((k, m[k]) for k in KEYS))
    x = params["embedding"][ids].astype(F32)
    n_dense = m["first_k_dense_replace"]
    for i in range(m["num_hidden_layers"]):
        stack, at = (("dense_layers", i) if i < n_dense else ("layers", i - n_dense))
        x = _layer(x, params[stack], jnp.int32(at), m=frozen, dense=i < n_dense, faults=on)
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


MATRICES = ("q_a", "q_b", "kv_a", "kv_b", "o", "gate", "up", "down", "router",
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
