"""The plain reference for Qwen3-Next-80B-A3B-Instruct
(https://huggingface.co/Qwen/Qwen3-Next-80B-A3B-Instruct/blob/main/config.json)
as ONE chip's share of it: Gated DeltaNet mixers and gated softmax attentions
3 : 1, top-k of a softmax router's experts beside a gated shared expert in
every layer. Straightforward jax.numpy, float32, matmuls at `highest`
precision. No kernels, no cache, no chunking of the recurrence, no batching,
and nothing imported from the program: it reads the program's parameter tree
(`layers`, stacked on a leading axis: the leaves every layer has over all the
layers, the softmax attention's `q k v o q_norm k_norm` over the full layers
alone, the mixer's `gdn_...` over the mixers alone, each in the layers' order;
`[in, out]` matrices; `embedding`, `final_norm`, `lm_head`) and the
configuration file's published keys (`KEYS`), not the program's config objects.

N(x) = x / rms(x) * (1 + w), eps rms_norm_eps: every norm but the mixer's
output norm. x the residual stream. Layer i (0-based) is a full attention when
(i + 1) % full_attention_interval == 0, a mixer otherwise. Every layer:

    h = x + Mixer(N_in(x));   y = h + MoE(N_post(h))

    GDN(u), H_k key heads, H_v value heads, d_k, d_v:
        [q | k | v | z] = u Wqkvz;  [b | a] = u Wba
        [q | k | v] through a causal depthwise convolution over the sequence
            (kernel linear_conv_kernel_dim, no bias; zeros before position 0),
            then SiLU
        beta = sigmoid(b);  g = -exp(A_log) softplus(a + dt_bias)
        q, k L2-normalised over d_k (eps 1e-6), q scaled by d_k^-0.5; key head
            j serves value heads j H_v/H_k .. (j + 1) H_v/H_k - 1
        a value head, S [d_k, d_v], S_0 = 0, TOKEN BY TOKEN under lax.scan:
            S' = exp(g_t) S_{t-1};  r = S'^T k_t
            S_t = S' + k_t (beta_t (v_t - r))^T;  o_t = S_t^T q_t
        out = concat_heads(rmsnorm(o_t) * w_norm * silu(z)) Wout   (plain w_norm)

    Attn(u): [q | gate] = u Wq, each head's 2 D outputs its query, then its
        gate; k = u Wk, v = u Wv; q and k normed a head with N; RoPE
        (rotate-half, inv_freq_i = theta^(-2i/R)) on the first R = D x
        partial_rotary_factor dimensions of a head; causal softmax, scale
        D^-0.5; out = concat_heads(P v * sigmoid(gate)) Wo

    MoE(z): p = softmax(z Wr) over ALL router_experts columns; the
        num_experts_per_tok largest, renormalised to sum 1 (norm_topk_prob);
        out = sum over the chosen e HELD here of g_e SwiGLU_e(z)
              + sigmoid(z . w_sg) SwiGLU_shared(z)

The share: the router has a column for every expert of the model, the banks
hold experts `expert_first .. expert_first + num_experts - 1`. A chosen expert
held elsewhere adds nothing here (no stand-in for the absent chips). The shared
expert is every chip's and is added here in full.

Departures from config.json, each also under `assumed` in the configuration's
file (the released modelling code's): (1) the attention's output gate and its
place in q's projection; (2) the order of [q | k | v | z] and [b | a] inside
their projections (a permutation of columns, invisible under seeded weights);
(3) the plain weight of the mixer's output norm, 1 + w everywhere else,
per-head QK-norm; (4) float32 state and convolution tail; (5) as seeded, A_log
= log U(0, 16) and dt_bias the inverse softplus of dt log-uniform in [0.001,
0.1] (the rule's authors' start; the released code's placeholder dt_bias = 1
keeps less than half of a state a step in 31 heads of 32: no memory); (6) the multi-token-prediction module is not here; (7) weights are
random from a seed.

So that a 64k-token request fits one chip beside the bfloat16 weights: a layer
is computed at a time from its own rows of the (bfloat16-rounded) weights, cast
to float32 inside; attention runs one KV head's queries at a time, Q_BLOCK
queries against all the keys; the mixer runs GDN_GROUPS groups of heads one
after the other; every token goes through EVERY held expert densely, one expert
at a time, the outputs summed with the gate as the weight, 0 for an expert not
chosen.

The keyword arguments of `hidden_states` (`FAULTS`) exist for the tolerance
probe only (`tools/tolerance_probe_qwen3_next.py`); `rounded_to` is its
precision control.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

F32 = jnp.float32
Q_BLOCK = 256       # queries a block: [8 heads, 256, 65536] float32 scores are 0.5 GiB
GDN_GROUPS = 2      # the mixer's heads run in this many groups, one after the other
TOKEN_BLOCK = 8192  # tokens a block of an expert: [8192, 512] float32
CHUNK = 256         # the prefill chunk the probe's `tail_dropped` control cuts at
KEYS = ("vocab_size", "hidden_size", "num_hidden_layers", "num_attention_heads",
        "num_key_value_heads", "head_dim", "full_attention_interval",
        "linear_conv_kernel_dim", "linear_key_head_dim", "linear_num_key_heads",
        "linear_num_value_heads", "linear_value_head_dim", "partial_rotary_factor",
        "rope_theta", "rms_norm_eps", "moe_intermediate_size",
        "shared_expert_intermediate_size", "num_experts", "num_experts_per_tok",
        "norm_topk_prob", "router_experts", "expert_first")
FAULTS = ("bf16_state", "no_decay", "no_qk_norm", "tail_dropped", "state_kept",
          "gdn_layer_skipped", "no_attn_gate", "no_shared_gate", "bf16_acts")


def kinds_of(m: dict) -> tuple:
    every = m["full_attention_interval"]
    return tuple("full_attention" if (i + 1) % every == 0 else "linear_attention"
                 for i in range(m["num_hidden_layers"]))


def as_program(pub: dict) -> dict:
    """The same keys under the names and in the forms of the program's
    ModelConfig (a plain mapping: nothing of the program is imported). The
    cell's runner checks the model the program built against it."""
    kinds = kinds_of(pub)
    return dict(
        vocab_size=pub["vocab_size"], hidden_size=pub["hidden_size"],
        num_hidden_layers=pub["num_hidden_layers"],
        num_attention_heads=pub["num_attention_heads"],
        num_key_value_heads=pub["num_key_value_heads"], head_dim=pub["head_dim"],
        layer_types=kinds, partial_rotary_factor=float(pub["partial_rotary_factor"]),
        rope_theta=float(pub["rope_theta"]), rms_norm_eps=pub["rms_norm_eps"],
        linear_conv_kernel_dim=pub["linear_conv_kernel_dim"],
        linear_key_head_dim=pub["linear_key_head_dim"],
        linear_num_key_heads=pub["linear_num_key_heads"],
        linear_num_value_heads=pub["linear_num_value_heads"],
        linear_value_head_dim=pub["linear_value_head_dim"],
        moe_intermediate_size=pub["moe_intermediate_size"],
        n_shared_experts=pub["shared_expert_intermediate_size"] // pub["moe_intermediate_size"],
        num_experts=pub["num_experts"], router_experts=pub["router_experts"],
        expert_first=pub["expert_first"], num_experts_per_token=pub["num_experts_per_tok"],
        norm_topk_prob=pub["norm_topk_prob"], moe_scoring="softmax",
        qk_norm="head", attn_output_gate=True, shared_expert_gate=True,
        norm_add_unit_offset=True, gdn=True)


def _r(x, faults: frozenset):
    """The probe's witness (`bf16_acts`): x rounded to bfloat16 where a
    bfloat16 program holds an activation (the residual stream, a norm's
    output, a projection's output, a matmul's input); x itself otherwise."""
    if "bf16_acts" not in faults:
        return x
    return jax.lax.reduce_precision(x, exponent_bits=8, mantissa_bits=7)


def _norm(x, w, eps):
    """N: 1 + w."""
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * (1.0 + w.astype(F32))


def _rope(x, theta: float, rot: int):
    # x [S, H, D]: position p rotates pair (i, i + rot/2) of the first `rot`
    # dimensions by p * theta^(-2i/rot); the rest pass through
    freq = jnp.asarray(theta ** (-np.arange(0, rot, 2, dtype=np.float64) / rot), F32)
    ang = jnp.arange(x.shape[0], dtype=F32)[:, None] * freq[None, :]
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    x1, x2 = x[..., : rot // 2], x[..., rot // 2: rot]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin, x[..., rot:]], axis=-1)


def _attention(q, k, v):
    """q [S, G, D] (one KV head's queries), k / v [S, D] -> [S, G, D]; causal,
    Q_BLOCK queries at a time."""
    s, g, d = q.shape
    qb_n = min(Q_BLOCK, s)
    blocks = -(-s // qb_n)
    qb = jnp.pad(q, ((0, blocks * qb_n - s), (0, 0), (0, 0))).reshape(blocks, qb_n, g, d)
    j = jnp.arange(s)[None, :]

    def block(args):
        qi, b = args
        i = (b * qb_n + jnp.arange(qb_n))[:, None]
        sc = jnp.einsum("qgd,sd->gqs", qi, k) / jnp.sqrt(F32(d))
        p = jax.nn.softmax(jnp.where((j <= i)[None], sc, -jnp.inf), axis=-1)
        return jnp.einsum("gqs,sd->qgd", p, v)

    return jax.lax.map(block, (qb, jnp.arange(blocks))).reshape(blocks * qb_n, g, d)[:s]


def _gated_attention(u, w, m: dict, faults: frozenset):
    """u [S, hidden] (normed) -> Attn(u) [S, hidden]."""
    s = u.shape[0]
    heads, kvh, d = m["num_attention_heads"], m["num_key_value_heads"], m["head_dim"]
    eps, theta = m["rms_norm_eps"], float(m["rope_theta"])
    rot = int(d * m["partial_rotary_factor"])
    qg = _r(u @ w["q"].astype(F32), faults).reshape(s, heads, 2, d)
    q, gate = qg[:, :, 0], qg[:, :, 1]
    k = _r(u @ w["k"].astype(F32), faults).reshape(s, kvh, d)
    v = _r(u @ w["v"].astype(F32), faults).reshape(s, kvh, d)
    q = _r(_rope(_r(_norm(q, w["q_norm"], eps), faults), theta, rot), faults)
    k = _r(_rope(_r(_norm(k, w["k_norm"], eps), faults), theta, rot), faults)
    per = heads // kvh
    out = jnp.concatenate([_attention(q[:, j * per:(j + 1) * per], k[:, j], v[:, j])
                           for j in range(kvh)], axis=1)
    if "no_attn_gate" not in faults:
        out = _r(out, faults) * jax.nn.sigmoid(gate)
    return _r(out, faults).reshape(s, heads * d) @ w["o"].astype(F32)


def _conv(x, wc, faults: frozenset):
    """x [S, C], wc [C, K] -> the causal depthwise convolution, zeros before
    position 0, then SiLU."""
    s, kern = x.shape[0], wc.shape[1]
    padded = jnp.pad(x, ((kern - 1, 0), (0, 0)))
    t = jnp.arange(s)[:, None]
    out = 0.0
    for j in range(kern):
        tap = padded[j:j + s] * wc[:, j].astype(F32)
        if "tail_dropped" in faults:
            # control: at every chunk boundary the earlier positions are lost
            tap = jnp.where(t % CHUNK >= kern - 1 - j, tap, 0.0)
        out = out + tap
    return jax.nn.silu(out)


def _delta_rule(q, k, v, g, beta, faults: frozenset):
    """q / k [S, Hk, dk], v [S, Hv, dv], g / beta [S, Hv] -> (the state after
    the last token [Hv, dk, dv], o [S, Hv, dv]): the gated delta rule token by
    token from a zero state."""
    hk, hv = q.shape[1], v.shape[1]
    rep = hv // hk

    def step(st, xs):
        q_t, k_t, v_t, g_t, b_t = xs
        q_t, k_t = jnp.repeat(q_t, rep, axis=0), jnp.repeat(k_t, rep, axis=0)
        st = st * jnp.exp(g_t)[:, None, None]
        r = jnp.einsum("hkv,hk->hv", st, k_t)
        st = st + k_t[:, :, None] * (b_t[:, None] * (v_t - r))[:, None, :]
        if "bf16_state" in faults:
            # (not a cast there and back, which the chip's compiler drops)
            st = jax.lax.reduce_precision(st, exponent_bits=8, mantissa_bits=7)
        return st, jnp.einsum("hkv,hk->hv", st, q_t)

    start = jnp.zeros((hv, q.shape[2], v.shape[2]), F32)
    if "state_kept" in faults:
        # control: the slot's last request (this one's first chunk) left its state
        start, _ = jax.lax.scan(step, start, tuple(x[:CHUNK] for x in (q, k, v, g, beta)))
    return jax.lax.scan(step, start, (q, k, v, g, beta))


def _gdn(u, w, m: dict, faults: frozenset):
    """u [S, hidden] (normed) -> (GDN(u) [S, hidden], the state every value
    head carries out of the last token [Hv, dk, dv]), GDN_GROUPS groups of
    heads one after the other."""
    s = u.shape[0]
    hk, hv = m["linear_num_key_heads"], m["linear_num_value_heads"]
    dk, dv = m["linear_key_head_dim"], m["linear_value_head_dim"]
    groups = GDN_GROUPS if hk % GDN_GROUPS == 0 else 1
    gk, gv = hk // groups, hv // groups
    nq, nv = hk * dk, hv * dv
    wqkvz, wba, wc = w["gdn_qkvz"], w["gdn_ba"], w["gdn_conv"]
    a_log, dt_bias = w["gdn_A_log"].astype(F32), w["gdn_dt_bias"].astype(F32)
    out, states = jnp.zeros_like(u), []
    for j in range(groups):
        cols = dict(q=slice(j * gk * dk, (j + 1) * gk * dk),
                    k=slice(nq + j * gk * dk, nq + (j + 1) * gk * dk),
                    v=slice(2 * nq + j * gv * dv, 2 * nq + (j + 1) * gv * dv),
                    z=slice(2 * nq + nv + j * gv * dv, 2 * nq + nv + (j + 1) * gv * dv))
        q, k, v = (_conv(u @ wqkvz[:, cols[n]].astype(F32), wc[cols[n]], faults)
                   for n in "qkv")
        z = u @ wqkvz[:, cols["z"]].astype(F32)
        heads = slice(j * gv, (j + 1) * gv)
        beta = jax.nn.sigmoid(u @ wba[:, :hv][:, heads].astype(F32))
        g = -jnp.exp(a_log[heads]) * jax.nn.softplus(
            u @ wba[:, hv:][:, heads].astype(F32) + dt_bias[heads])
        if "no_decay" in faults:
            g = jnp.zeros_like(g)
        q, k = q.reshape(s, gk, dk), k.reshape(s, gk, dk)
        if "no_qk_norm" not in faults:
            q, k = (x * jax.lax.rsqrt(jnp.sum(x * x, axis=-1, keepdims=True) + 1e-6)
                    for x in (q, k))
        st, o = _delta_rule(q * F32(dk ** -0.5), k, v.reshape(s, gv, dv), g, beta, faults)
        states.append(st)
        o = o * jax.lax.rsqrt(jnp.mean(o * o, axis=-1, keepdims=True) + m["rms_norm_eps"])
        o = o * w["gdn_norm"].astype(F32) * jax.nn.silu(z.reshape(s, gv, dv))
        out = out + _r(o, faults).reshape(s, gv * dv) @ w["gdn_out"][j * gv * dv:(j + 1) * gv * dv].astype(F32)
    return out, jnp.concatenate(states)


def _swiglu(z, wg, wu, wd, faults: frozenset = frozenset()):
    s = z.shape[0]
    tb = min(TOKEN_BLOCK, s)
    blocks = -(-s // tb)
    zb = jnp.pad(z, ((0, blocks * tb - s), (0, 0))).reshape(blocks, tb, -1)
    out = jax.lax.map(lambda zi: _r(_r(jax.nn.silu(_r(zi @ wg.astype(F32), faults)), faults)
                                     * _r(zi @ wu.astype(F32), faults), faults)
                      @ wd.astype(F32), zb)
    return out.reshape(blocks * tb, -1)[:s]


def gates(z, w, m: dict):
    """z [S, hidden] -> the gate of every router column [S, router_experts], 0
    where the column was not chosen."""
    p = jax.nn.softmax(z @ w["router"].astype(F32), axis=-1)
    top_p, top_i = jax.lax.top_k(p, m["num_experts_per_tok"])
    if m["norm_topk_prob"]:
        top_p = top_p / jnp.sum(top_p, axis=-1, keepdims=True)
    return jnp.zeros_like(p).at[jnp.arange(z.shape[0])[:, None], top_i].set(top_p)


def routed(z, w, m: dict, faults: frozenset = frozenset()):
    """The held experts' gated outputs: this share's routed part."""
    first, held = m["expert_first"], m["num_experts"]
    here = gates(z, w, m)[:, first:first + held]                          # [S, held]

    def one(out, e):
        wg, wu, wd, ge = e
        return out + ge[:, None] * _r(_swiglu(z, wg, wu, wd, faults), faults), None

    return jax.lax.scan(one, jnp.zeros_like(z),
                        (w["w_gate"], w["w_up"], w["w_down"], here.T))[0]


def shared(z, w, m: dict, faults: frozenset = frozenset()):
    """The shared expert's output times its gate: every chip's, in full."""
    out = _r(_swiglu(z, w["shared_gate"], w["shared_up"], w["shared_down"], faults), faults)
    if "no_shared_gate" in faults:
        return out
    return out * jax.nn.sigmoid(z @ w["shared_out_gate"].astype(F32))[:, None]


def layer(x, w, kind: str, m: dict, faults: frozenset = frozenset(), skip_mixer: bool = False):
    """One layer over x [S, hidden]; `w`: the layer's own leaves."""
    eps = m["rms_norm_eps"]
    u = _r(_norm(x, w["input_norm"], eps), faults)
    if skip_mixer:
        h = x
    elif kind == "linear_attention":
        h = _r(x + _r(_gdn(u, w, m, faults)[0], faults), faults)
    else:
        h = _r(x + _r(_gated_attention(u, w, m, faults), faults), faults)
    z = _r(_norm(h, w["post_norm"], eps), faults)
    return _r(h + _r(_r(routed(z, w, m, faults), faults) + _r(shared(z, w, m, faults), faults),
                     faults), faults)


def _is_own(name: str, kind: str) -> bool:
    if name.startswith("gdn_"):
        return kind == "linear_attention"
    return kind != "linear_attention" or name not in ("q", "k", "v", "o", "q_norm", "k_norm")


@functools.partial(jax.jit, static_argnames=("m", "faults", "kind", "skip_mixer"))
def _layer(x, stack, at, own, *, kind, m, faults: frozenset, skip_mixer: bool = False):
    # the layer's weights are taken out of the stack inside the program, a
    # matrix where it is used: `at` its index among all the layers, `own`
    # among the layers of its kind
    w = {n: jax.lax.dynamic_index_in_dim(v, at if _is_own(n, "linear_attention")
                                         and _is_own(n, "full_attention") else own,
                                         0, keepdims=False)
         for n, v in stack.items() if _is_own(n, kind)}
    with jax.default_matmul_precision("highest"):
        return layer(x, w, kind, dict(m), faults, skip_mixer)


def hidden_states(params, ids, m: dict, **faults):
    """ids [S] -> final-norm hidden states [S, hidden], float32; `m`: the
    configuration file's keys (`KEYS`). A layer at a time. `faults`: FAULTS
    names set true, for the probe."""
    unknown = set(faults) - set(FAULTS)
    if unknown:
        raise TypeError(f"reference_qwen3_next: unknown fault {sorted(unknown)}")
    on = frozenset(k for k, v in faults.items() if v)
    frozen = tuple(sorted((k, m[k]) for k in KEYS))
    kinds = kinds_of(m)
    # the control's layer: the middle mixer
    skipped = [i for i, k in enumerate(kinds) if k == "linear_attention"]
    skipped = skipped[len(skipped) // 2] if "gdn_layer_skipped" in on else -1
    x = params["embedding"][ids].astype(F32)
    for i, kind in enumerate(kinds):
        x = _layer(x, params["layers"], jnp.int32(i), jnp.int32(kinds[:i].count(kind)),
                   kind=kind, m=frozen, faults=on - {"gdn_layer_skipped"},
                   skip_mixer=i == skipped)
    with jax.default_matmul_precision("highest"):
        return _norm(x, params["final_norm"], m["rms_norm_eps"])


@functools.partial(jax.jit, static_argnames=("m", "faults"))
def _first_state(x, stack, *, m, faults: frozenset):
    w = {n: v[0] for n, v in stack.items() if _is_own(n, "linear_attention")}
    with jax.default_matmul_precision("highest"):
        m = dict(m)
        return _gdn(_r(_norm(x, w["input_norm"], m["rms_norm_eps"]), faults), w, m, faults)[1]


def first_state(params, ids, m: dict, **faults):
    """The state [Hv, dk, dv] float32 that the FIRST layer's mixer carries out
    of the last of `ids` [S] (no padding behind them): what a serving cache
    holds for the sequence there. The first layer alone reads the embedding,
    so nothing of the layers above it is in the comparison."""
    on = frozenset(k for k, v in faults.items() if v)
    assert kinds_of(m)[0] == "linear_attention"
    return _first_state(params["embedding"][ids].astype(F32), params["layers"],
                        m=tuple(sorted((k, m[k]) for k in KEYS)), faults=on)


@jax.jit
def _head_rows(hidden, rows, head):
    with jax.default_matmul_precision("highest"):
        return hidden[rows] @ head.astype(F32)


def logits_at(params, ids, rows, m: dict, **faults):
    """Logits [len(rows), V] float32 at the given positions of `ids` [S]."""
    return _head_rows(hidden_states(params, ids, m, **faults), rows, params["lm_head"])


MATRICES = ("q", "k", "v", "o", "gdn_qkvz", "gdn_ba", "gdn_out", "router", "w_gate", "w_up",
            "w_down", "shared_gate", "shared_up", "shared_down")


def rounded_to(params, bits: int, only=None):
    """The control of the cell's `correct`: the same tree with every matrix
    rounded to `bits`-bit integers and back, one scale an output channel
    (symmetric, largest magnitude / (2^(bits-1) - 1)). 8 bits is the nearest
    precision below the bfloat16 the configuration states. Norm weights, the
    convolution, A_log, dt_bias and the shared expert's gate vector stay.
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
    for n in MATRICES:  # [L, (E,) in, out]: a scale a layer (an expert) and column
        if wanted(n, out["layers"]):
            out["layers"][n] = rnd(out["layers"][n], -2)
    if wanted("embedding", out):  # [V, h]: a scale a token
        out["embedding"] = rnd(out["embedding"], -1)
    if wanted("lm_head", out):    # [h, V]: a scale an output column
        out["lm_head"] = rnd(out["lm_head"], -2)
    return out
