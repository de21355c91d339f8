"""The plain reference for a decoder with sparse experts and QK-norm: OLMoE's
layer (`transformers`' `modeling_olmoe.py`) in straightforward jax.numpy,
float32, matmuls at `highest` precision. No kernels, no cache, no remat, no
scan, no capacity, no sharding rules, and nothing imported from the program:
it reads the program's parameter tree (layer weights stacked on a leading
axis, `[in, out]` matrices; per layer `q_norm` `[n_q * d]`, `k_norm`
`[n_kv * d]`, `router` `[h, E]`, expert banks `w_gate`, `w_up` `[E, h, f]`,
`w_down` `[E, f, h]`) and the configuration's sizes.

One layer, for a sequence of S tokens:

    h = RMSNorm(x)
    q = RMSNorm_q(h Wq), k = RMSNorm_k(h Wk)    over the WHOLE projected vector,
    v = h Wv                                     before the split into heads
    rotate-half RoPE on q and k; causal softmax attention at 1/sqrt(d)
    x = x + o Wo
    h = RMSNorm(x)
    p = softmax(h Wr) over all E experts                         [S, E]
    the k largest p_j and their experts; gates = p_j, NOT renormalised
        (`norm_topk_prob` false; true divides them by their sum, Mixtral's rule)
    x = x + sum_j p_j * down_j(silu(gate_j h) * up_j h)

Every token is computed by all k of its experts: each token goes through
EVERY expert densely (EXPERT_CHUNK experts at a time, to bound memory) and the
outputs are summed with the gate as the weight, 0 for an expert not chosen.
Dropless, as the model is trained and run.

The two auxiliary terms, per layer, over the sequence's S tokens (the
program's microbatch of one sequence):

    balance = E * sum_e f_e * P_e     f_e = the share of the S * k assignments
                                      that went to expert e (sums to 1 over e),
                                      P_e = mean over tokens of p[:, e]
    z       = mean over tokens of logsumexp(h Wr)^2

    loss = cross-entropy mean + sum over layers of
           (router_aux_coef * balance + router_z_coef * z)

Scale against `transformers`' `load_balancing_loss_func`: there the expert
fractions are a mean over tokens for each of the k choices, summed over the
choices, so they sum to k and its term is k times `balance`; and the layers'
routers are pooled into one mean where this sums the layers' terms. At one
layer `transformers`' term is exactly k * balance, so its coefficient 0.01
there weighs what 0.01 * k would here. The program's definition (Switch eq. 4,
`picotron_tpu/ops/moe.py`) is the one followed, with the published coefficient
on it; `transformers` has no z-loss (the OLMoE paper, arXiv:2409.02060, trains
with 0.001 of it).

Run under plain `jax.jit`.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

F32 = jnp.float32
EXPERT_CHUNK = 16  # experts computed at a time: [16, S, hidden] float32 is 0.5 GiB at S 4096
# the sizes this file reads from a configuration's `model` block
SIZES = ("vocab_size", "hidden_size", "intermediate_size", "num_hidden_layers",
         "num_attention_heads", "num_key_value_heads", "rope_theta", "rms_norm_eps",
         "attention_bias", "tie_word_embeddings", "num_experts",
         "num_experts_per_token", "moe_intermediate_size", "norm_topk_prob", "qk_norm")


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


def _mm(a, b, round_to=None):
    """a @ b; with `round_to` (probe only) both operands are first rounded to
    that dtype, as a program computing in it would hold them."""
    if round_to is not None:
        a, b = a.astype(round_to).astype(F32), b.astype(round_to).astype(F32)
    return a @ b


def _experts(h, w, m: dict, *, renorm_gates: bool, drop_last_expert: bool,
             round_to=None):
    """h [S, hidden] -> (the expert block's output [S, hidden], balance [],
    z [], p [S, E])."""
    e, k = m["num_experts"], m["num_experts_per_token"]
    logits = _mm(h, w["router"], round_to)
    p = jax.nn.softmax(logits, axis=-1)
    top_p, top_i = jax.lax.top_k(p, k)
    chosen = jax.nn.one_hot(top_i, e, dtype=F32)             # [S, k, E]
    balance = e * jnp.sum(jnp.mean(chosen, axis=(0, 1)) * jnp.mean(p, axis=0))
    z = jnp.mean(jax.nn.logsumexp(logits, axis=-1) ** 2)
    gates = top_p / jnp.sum(top_p, axis=-1, keepdims=True) if renorm_gates else top_p
    if drop_last_expert:  # probe: the k-th expert's term left out
        gates = gates.at[:, -1].set(0.0)
    weight = jnp.einsum("sk,ske->se", gates, chosen)          # 0 where not chosen
    out = jnp.zeros_like(h)
    for j in range(0, e, EXPERT_CHUNK):  # every token through every expert, weighted
        bank = slice(j, j + EXPERT_CHUNK)
        g = _mm(h, w["w_gate"][bank], round_to)                # [chunk, S, f]
        u = _mm(h, w["w_up"][bank], round_to)
        y = _mm(jax.nn.silu(g) * u, w["w_down"][bank], round_to)  # [chunk, S, hidden]
        out = out + jnp.einsum("se,esh->sh", weight[:, bank], y)
    return out, balance, z, p


def forward(params, ids, m: dict, *, causal: bool = True, skip_layers=(),
            renorm_gates=None, drop_last_expert: bool = False,
            skip_qk_norm: bool = False, round_to=None) -> dict:
    """ids [S] -> `hidden` [S, h] after the final norm, `balance` and `z`
    (each summed over the layers) and `probs` [L, S, E], the routers'
    probabilities, all float32. The keywords exist for the tolerance probe and
    the tests only (what a wrong mask, a dropped layer, renormalised gates, a
    missing expert, a skipped QK-norm or projections, experts and head
    computed on operands rounded to the dtype `round_to` would do to the
    numbers `correct` compares); `renorm_gates=None` follows
    `norm_topk_prob`."""
    n_q, n_kv = m["num_attention_heads"], m["num_key_value_heads"]
    d = m.get("head_dim") or m["hidden_size"] // n_q
    eps, theta = m["rms_norm_eps"], m["rope_theta"]
    if m.get("rope_scaling"):
        raise NotImplementedError("reference_moe.py has no scaled RoPE")
    if renorm_gates is None:
        renorm_gates = bool(m["norm_topk_prob"])
    L = params["layers"]
    s = ids.shape[0]
    x = params["embedding"][ids].astype(F32)
    mask = jnp.tril(jnp.ones((s, s), bool)) if causal else jnp.ones((s, s), bool)
    balance = z = F32(0.0)
    probs = []
    for i in range(m["num_hidden_layers"]):
        if i in skip_layers:
            continue
        w = {k: v[i].astype(F32) for k, v in L.items()}
        h = _norm(x, w["input_norm"], eps)
        q, k, v = (_mm(h, w[n], round_to) for n in ("q", "k", "v"))
        if "b_q" in w:
            q, k, v = q + w["b_q"], k + w["b_k"], v + w["b_v"]
        if m["qk_norm"] and not skip_qk_norm:
            q, k = _norm(q, w["q_norm"], eps), _norm(k, w["k_norm"], eps)
        q = _rope(q.reshape(s, n_q, d), theta)
        k = _rope(k.reshape(s, n_kv, d), theta)
        v = v.reshape(s, n_kv, d)
        g = n_q // n_kv
        qg = q.reshape(s, n_kv, g, d)
        sc = jnp.einsum("qkgd,skd->kgqs", qg, k) / jnp.sqrt(F32(d))
        sc = jnp.where(mask[None, None], sc, -jnp.inf)
        p = jax.nn.softmax(sc, axis=-1)
        o = jnp.einsum("kgqs,skd->qkgd", p, v).reshape(s, n_q * d)
        x = x + _mm(o, w["o"], round_to)
        h = _norm(x, w["post_norm"], eps)
        y, b_i, z_i, p_i = _experts(h, w, m, renorm_gates=renorm_gates,
                                    drop_last_expert=drop_last_expert,
                                    round_to=round_to)
        x = x + y
        balance, z = balance + b_i, z + z_i
        probs.append(p_i)
    return dict(hidden=_norm(x, params["final_norm"], eps), balance=balance, z=z,
                probs=jnp.stack(probs))


def _head(params):
    w = params.get("lm_head")
    return (w if w is not None else params["embedding"].T).astype(F32)


def evaluate(params, ids, targets, rows, m: dict, precision: str = "highest", **kw) -> dict:
    """One forward of one sequence, everything `correct` compares: `nll_sum`
    (the sum over the sequence of -log p(target)), `count`, the two auxiliary
    terms `balance` and `z` (unweighted, summed over the layers), `loss` (the
    training loss: cross-entropy mean + `router_aux_coef` * balance +
    `router_z_coef` * z, the coefficients of the `model` block), and at the
    positions `rows` the `logits` [len(rows), V] and the routers' `probs`
    [L, len(rows), E]. (`precision` other than `highest` is the tolerance
    probe's.)"""
    with jax.default_matmul_precision(precision):
        f = forward(params, ids, m, **kw)
        logits = _mm(f["hidden"], _head(params), kw.get("round_to"))
        lse = jax.nn.logsumexp(logits, axis=-1)
        picked = jnp.take_along_axis(logits, targets[:, None], axis=-1)[:, 0]
        nll_sum, count = jnp.sum(lse - picked), targets.shape[0]
        return dict(nll_sum=nll_sum, count=count, balance=f["balance"], z=f["z"],
                    loss=(nll_sum / count + m["router_aux_coef"] * f["balance"]
                          + m["router_z_coef"] * f["z"]),
                    logits=logits[rows], probs=f["probs"][:, rows])


def loss_terms(params, ids, targets, m: dict, precision: str = "highest", **kw) -> dict:
    """`evaluate`'s `nll_sum`, `count`, `balance` and `z`."""
    r = evaluate(params, ids, targets, jnp.arange(1), m, precision, **kw)
    return {k: r[k] for k in ("nll_sum", "count", "balance", "z")}


def loss(params, ids, targets, m: dict, precision: str = "highest", **kw):
    """The training loss of one sequence (`evaluate`'s `loss`)."""
    return evaluate(params, ids, targets, jnp.arange(1), m, precision, **kw)["loss"]


def logits_at(params, ids, rows, m: dict, precision: str = "highest", **kw):
    """(logits [len(rows), V] at the given positions of `ids` [S], the routers'
    probabilities there [L, len(rows), E])."""
    r = evaluate(params, ids, jnp.zeros_like(ids), rows, m, precision, **kw)
    return r["logits"], r["probs"]
