"""The plain reference for Kimi-Linear-48B-A3B-Instruct (`model_type:
kimi_linear`, https://huggingface.co/moonshotai/Kimi-Linear-48B-A3B-Instruct/blob/main/config.json)
as ONE chip's share of it: Kimi Delta Attention (KDA) mixers and latent
attentions (MLA) without positions 3 : 1, a leading dense layer, then top-k of
a sigmoid router's experts beside a shared expert. Straightforward jax.numpy,
float32, matmuls at `highest` precision. No kernels, no cache, no chunking of
the recurrence, no absorbed attention, no batching, and nothing imported from
the program: it reads the program's parameter tree (`dense_layers`, then
`layers`, each stacked on a leading axis: the leaves every layer of a stack
has over all its layers, the latent attention's `q_b kv_a kv_a_norm kv_b o`
over its full layers alone, the mixer's `kda_...` over its mixers alone, each
in the layers' order; `[in, out]` matrices; `embedding`, `final_norm`,
`lm_head`) and the configuration file's published keys (`KEYS`), not the
program's config objects.

N(x) = x / rms(x) * w, eps rms_norm_eps, a plain weight: every norm. x the
residual stream. Layer i (1-based, as `linear_attn_config` counts) is a KDA
mixer where i is in `kda_layers`, a latent attention where it is in
`full_attn_layers` (entries beyond num_hidden_layers name no layer of a model
cut in depth). The first `first_k_dense_replace` layers have a dense MLP, the
others the experts. Every layer:

    h = x + Mixer(N_in(x));   y = h + MLP(N_post(h))

    KDA(u), H heads, d_k = d_v = `linear_attn_config.head_dim`:
        [q | k | v] = u Wqkv, each through a causal depthwise convolution over
            the sequence (kernel `short_conv_kernel_size`, no bias; zeros
            before position 0; one convolution over all 3 H d channels is the
            three side by side), then SiLU
        q, k L2-normalised over d_k (eps 1e-6), q scaled by d_k^-0.5
        g = -exp(A_log_h) softplus(Wf2 (Wf1 u) + dt_bias)  in R^{H x d_k}: a
            decay a CHANNEL of the key, through a bottleneck of d_v numbers
        beta = sigmoid(u Wb), a head
        a head, S [d_k, d_v], S_0 = 0, TOKEN BY TOKEN under lax.scan:
            S' = Diag(exp(g_t)) S_{t-1};  r = S'^T k_t
            S_t = S' + k_t (beta_t (v_t - r))^T;  o_t = S_t^T q_t
        out = concat_heads(rmsnorm(o_t) * w_norm * sigmoid(Wg2 (Wg1 u))) Wout

    MLA(u), no query bottleneck (q_lora_rank null), NO rotation (mla_use_nope):
        [q_n | q_r]_h = u Wq, heads of nope + rope
        [c | k_r] = u Wkva;  c = N(c);  k_r one for all heads, as it is
        [k_n | v]_h = c Wkvb, heads of nope + v
        scores (q_n . k_n + q_r . k_r) / sqrt(nope + rope), causal, softmax
        out = concat_heads(P v) Wo

    dense MLP: (silu(z Wg) * (z Wu)) Wd
    MoE(z): s = sigmoid(z Wr) over ALL router_experts columns; the
        num_experts_per_token largest of s + bias (e_score_correction_bias);
        gates s_e / sum of the chosen s (moe_renormalize) x
        routed_scaling_factor;
        out = sum over the chosen e HELD here of g_e SwiGLU_e(z) + SwiGLU_shared(z)

The share: the router has a column for every expert of the model, the banks
hold experts `expert_first .. expert_first + num_experts - 1`. A chosen expert
held elsewhere adds nothing here (no stand-in for the absent chips), and its
score still counts in the sum the gates are normalised by. The shared expert
is every chip's and is added here in full.

Assumed, with no key in config.json (each also under `assumed` in the
configuration's file; the released modelling code's unless said): (1) no bias
in any projection or convolution; (2) SiLU behind the convolutions; (3) q and
k L2-normalised with eps 1e-6 and q scaled by d_k^-0.5; (4) the two low-rank
projections pass through d_v = 128 numbers, and the gate's order of
operations: the head's RMSNorm with a plain weight first, then times
sigmoid(gate); (5) float32 state and convolution tail; (6) as seeded, A_log =
log U(1, 16) a head and dt_bias the inverse softplus of a step log-uniform in
[0.001, 0.1] a channel (a placeholder dt_bias leaves a mixer without a
memory); (7) the selection bias is zeros, as a checkpoint's buffer starts;
(8) `head_dim: 72` is used by neither mixer; (9) `mla_use_nope: true` read as
"neither q_r nor k_r is rotated and no RoPE table is built" (described_as:
"MLA NoPE global"; rope_theta 10000 is published and unused); (10) weights
are random from a seed.

So that a 64k-position request fits one chip beside the bfloat16 weights: a
layer is computed at a time from its own rows of the (bfloat16-rounded)
weights, cast to float32 inside; attention runs HEAD_GROUP heads at a time,
Q_BLOCK queries against all the keys; the mixer runs KDA_GROUPS groups of
heads one after the other; every token goes through EVERY held expert densely,
one expert at a time, the outputs summed with the gate as the weight, 0 for an
expert not chosen.

The keyword arguments of `hidden_states` (`FAULTS`) exist for the tolerance
probe only (`tools/tolerance_probe_kimi_linear.py`); `rounded_to` is its
precision control.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

F32 = jnp.float32
Q_BLOCK = 256       # queries a block: [8 heads, 256, 65536] float32 scores are 0.5 GiB
HEAD_GROUP = 8      # heads attended at a time
KDA_GROUPS = 4      # the mixer's heads run in this many groups, one after the other
TOKEN_BLOCK = 8192  # tokens a block of an MLP: [8192, 9216] float32 is 0.3 GB
CHUNK = 256         # the prefill chunk the probe's `tail_dropped` control cuts at
KDA, FULL = "kda", "full_attention"
KEYS = ("vocab_size", "hidden_size", "num_hidden_layers", "num_attention_heads",
        "q_lora_rank", "kv_lora_rank", "qk_nope_head_dim", "qk_rope_head_dim",
        "v_head_dim", "mla_use_nope", "rope_theta", "rms_norm_eps",
        "linear_attn_config", "first_k_dense_replace", "intermediate_size",
        "moe_intermediate_size", "num_experts", "num_experts_per_token",
        "num_shared_experts", "moe_renormalize", "moe_router_activation_func",
        "routed_scaling_factor", "router_experts", "expert_first")
FAULTS = ("decay_head_mean", "no_decay", "beta_one", "no_qk_norm", "tail_dropped",
          "state_kept", "bf16_state", "pe_rotated", "no_out_gate", "silu_out_gate",
          "no_renorm", "no_route_scale", "kda_layer_skipped", "bf16_acts")


def kinds_of(m: dict) -> tuple:
    lin = m["linear_attn_config"]
    kda_at, full_at = set(lin["kda_layers"]), set(lin["full_attn_layers"])
    assert not kda_at & full_at
    return tuple(KDA if i in kda_at else FULL if i in full_at else None
                 for i in range(1, m["num_hidden_layers"] + 1))


def as_program(pub: dict) -> dict:
    """The same keys under the names and in the forms of the program's
    ModelConfig (a plain mapping: nothing of the program is imported). The
    cell's runner checks the model the program built against it."""
    lin = pub["linear_attn_config"]
    return dict(
        vocab_size=pub["vocab_size"], hidden_size=pub["hidden_size"],
        num_hidden_layers=pub["num_hidden_layers"],
        num_attention_heads=pub["num_attention_heads"],
        layer_types=kinds_of(pub), q_lora_rank=pub["q_lora_rank"] or 0,
        kv_lora_rank=pub["kv_lora_rank"], qk_nope_head_dim=pub["qk_nope_head_dim"],
        qk_rope_head_dim=pub["qk_rope_head_dim"], v_head_dim=pub["v_head_dim"],
        mla_use_nope=pub["mla_use_nope"], rms_norm_eps=pub["rms_norm_eps"],
        linear_conv_kernel_dim=lin["short_conv_kernel_size"],
        linear_key_head_dim=lin["head_dim"], linear_value_head_dim=lin["head_dim"],
        linear_num_key_heads=lin["num_heads"], linear_num_value_heads=lin["num_heads"],
        first_k_dense_replace=pub["first_k_dense_replace"],
        intermediate_size=pub["intermediate_size"],
        moe_intermediate_size=pub["moe_intermediate_size"],
        n_shared_experts=pub["num_shared_experts"],
        num_experts=pub["num_experts"], router_experts=pub["router_experts"],
        expert_first=pub["expert_first"],
        num_experts_per_token=pub["num_experts_per_token"],
        norm_topk_prob=pub["moe_renormalize"],
        moe_scoring=pub["moe_router_activation_func"],
        routed_scaling_factor=pub["routed_scaling_factor"],
        moe_selection_bias=True, kda=True)


def _flat(m: dict) -> tuple:
    """The keys as one hashable tuple of pairs (a jitted layer's static
    argument): the nested group's sizes under names of their own, its two
    lists as the layers' kinds."""
    lin = m["linear_attn_config"]
    flat = {k: m[k] for k in KEYS if k != "linear_attn_config"}
    flat.update(kinds=kinds_of(m), kda_heads=lin["num_heads"], kda_dim=lin["head_dim"],
                kda_kernel=lin["short_conv_kernel_size"])
    return tuple(sorted(flat.items()))


def _r(x, faults: frozenset):
    """The probe's witness (`bf16_acts`): x rounded to bfloat16 where a
    bfloat16 program holds an activation (the residual stream, a norm's
    output, a matmul's input, a bfloat16 matmul's output); x itself
    otherwise."""
    if "bf16_acts" not in faults:
        return x
    return jax.lax.reduce_precision(x, exponent_bits=8, mantissa_bits=7)


def _norm(x, w, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * w.astype(F32)


def _rope(x, theta: float):
    # x [S, H, D], position p rotates pair (i, i + D/2) by p * theta^(-2i/D)
    # (the probe's `pe_rotated` control alone)
    d = x.shape[-1]
    freq = jnp.asarray(theta ** (-np.arange(0, d, 2, dtype=np.float64) / d), F32)
    ang = jnp.arange(x.shape[0], dtype=F32)[:, None] * freq[None, :]
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    x1, x2 = x[..., : d // 2], x[..., d // 2:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)


def _attention(q, k, v):
    """q / k [S, G, dqk], v [S, G, dv] -> P v [S, G, dv]; causal, Q_BLOCK
    queries at a time."""
    s, g, d = q.shape
    qb_n = min(Q_BLOCK, s)
    blocks = -(-s // qb_n)
    qb = jnp.pad(q, ((0, blocks * qb_n - s), (0, 0), (0, 0))).reshape(blocks, qb_n, g, d)
    j = jnp.arange(s)[None, :]

    def block(args):
        qi, b = args
        i = (b * qb_n + jnp.arange(qb_n))[:, None]
        sc = jnp.einsum("qgd,sgd->gqs", qi, k) / jnp.sqrt(F32(d))
        p = jax.nn.softmax(jnp.where((j <= i)[None], sc, -jnp.inf), axis=-1)
        return jnp.einsum("gqs,sgd->qgd", p, v)

    return jax.lax.map(block, (qb, jnp.arange(blocks))).reshape(blocks * qb_n, g, -1)[:s]


def _mla(u, w, m: dict, faults: frozenset):
    """u [S, hidden] (normed) -> MLA(u) [S, hidden], un-absorbed, unrotated;
    HEAD_GROUP heads at a time, their `(P v) Wo_heads` summed."""
    s = u.shape[0]
    heads, rank = m["num_attention_heads"], m["kv_lora_rank"]
    dn, dr, dv = m["qk_nope_head_dim"], m["qk_rope_head_dim"], m["v_head_dim"]
    theta = float(m["rope_theta"])
    ckr = _r(u @ w["kv_a"].astype(F32), faults)
    c = _r(_norm(ckr[:, :rank], w["kv_a_norm"], m["rms_norm_eps"]), faults)
    k_r = ckr[:, rank:]
    # (the probe's control rotates what the model leaves alone)
    rotated = "pe_rotated" in faults or not m["mla_use_nope"]
    if rotated:
        k_r = _rope(k_r[:, None, :], theta)[:, 0]
    groups = heads // min(HEAD_GROUP, heads)
    per = heads // groups
    wq = w["q_b"].reshape(-1, groups, per * (dn + dr)).transpose(1, 0, 2)
    wkvb = w["kv_b"].reshape(rank, groups, per * (dn + dv)).transpose(1, 0, 2)
    wo = w["o"].reshape(groups, per * dv, -1)

    def group(out, ws):
        wq_g, wkv_g, wo_g = ws
        q = _r(u @ wq_g.astype(F32), faults).reshape(s, per, dn + dr)
        if rotated:
            q = jnp.concatenate([q[..., :dn], _rope(q[..., dn:], theta)], axis=-1)
        kv = _r(c @ wkv_g.astype(F32), faults).reshape(s, per, dn + dv)
        k = jnp.concatenate([kv[..., :dn],
                             jnp.broadcast_to(k_r[:, None, :], (s, per, dr))], axis=-1)
        pv = _r(_attention(q, k, kv[..., dn:]), faults)
        return out + pv.reshape(s, per * dv) @ wo_g.astype(F32), None

    out, _ = jax.lax.scan(group, jnp.zeros_like(u), (wq, wkvb, wo))
    return out


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
    """q / k / g [S, H, dk], v [S, H, dv], beta [S, H] -> (the state after the
    last token [H, dk, dv], o [S, H, dv]): the delta rule with a decay a
    channel, token by token from a zero state."""
    def step(st, xs):
        q_t, k_t, v_t, g_t, b_t = xs
        st = st * jnp.exp(g_t)[:, :, None]
        r = jnp.einsum("hkv,hk->hv", st, k_t)
        st = st + k_t[:, :, None] * (b_t[:, None] * (v_t - r))[:, None, :]
        if "bf16_state" in faults:
            # (not a cast there and back, which the chip's compiler drops)
            st = jax.lax.reduce_precision(st, exponent_bits=8, mantissa_bits=7)
        return st, jnp.einsum("hkv,hk->hv", st, q_t)

    start = jnp.zeros((q.shape[1], q.shape[2], v.shape[2]), F32)
    if "state_kept" in faults:
        # control: the slot's last request (this one's first chunk) left its state
        start, _ = jax.lax.scan(step, start, tuple(x[:CHUNK] for x in (q, k, v, g, beta)))
    return jax.lax.scan(step, start, (q, k, v, g, beta))


def _kda(u, w, m: dict, faults: frozenset):
    """u [S, hidden] (normed) -> (KDA(u) [S, hidden], the state every head
    carries out of the last token [H, dk, dv]), KDA_GROUPS groups of heads one
    after the other."""
    s = u.shape[0]
    h, d, eps = m["kda_heads"], m["kda_dim"], m["rms_norm_eps"]
    groups = KDA_GROUPS if h % KDA_GROUPS == 0 else 1
    gh = h // groups
    n = h * d
    a_log, dt_bias = w["kda_A_log"].astype(F32), w["kda_dt_bias"].astype(F32)
    f_mid = _r(u @ w["kda_f_a"].astype(F32), faults)
    g_mid = _r(u @ w["kda_g_a"].astype(F32), faults)
    out, states = jnp.zeros_like(u), []
    for j in range(groups):
        cols = slice(j * gh * d, (j + 1) * gh * d)
        q, k, v = (_conv(u @ w["kda_qkv"][:, at * n:(at + 1) * n][:, cols].astype(F32),
                         w["kda_conv"][at * n:(at + 1) * n][cols], faults).reshape(s, gh, d)
                   for at in range(3))
        heads = slice(j * gh, (j + 1) * gh)
        beta = jax.nn.sigmoid(u @ w["kda_beta"][:, heads].astype(F32))
        g = -jnp.exp(a_log[heads])[:, None] * jax.nn.softplus(
            (f_mid @ w["kda_f_b"][:, cols].astype(F32) + dt_bias[cols]).reshape(s, gh, d))
        if "decay_head_mean" in faults:
            # control: the scalar-gated rule in KDA's place, a head's decay the mean
            # of its channels'
            g = jnp.broadcast_to(jnp.mean(g, axis=-1, keepdims=True), g.shape)
        if "no_decay" in faults:
            g = jnp.zeros_like(g)
        if "beta_one" in faults:
            beta = jnp.ones_like(beta)
        if "no_qk_norm" not in faults:
            q, k = (x * jax.lax.rsqrt(jnp.sum(x * x, axis=-1, keepdims=True) + 1e-6)
                    for x in (q, k))
        st, o = _delta_rule(q * F32(d ** -0.5), k, v, g, beta, faults)
        states.append(st)
        o = o * jax.lax.rsqrt(jnp.mean(o * o, axis=-1, keepdims=True) + eps)
        o = o * w["kda_norm"].astype(F32)
        gate = (g_mid @ w["kda_g_b"][:, cols].astype(F32)).reshape(s, gh, d)
        if "silu_out_gate" in faults:
            o = o * jax.nn.silu(gate)
        elif "no_out_gate" not in faults:
            o = o * jax.nn.sigmoid(gate)
        out = out + _r(o, faults).reshape(s, gh * d) @ w["kda_out"][cols].astype(F32)
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


def gates(z, w, m: dict, faults: frozenset = frozenset()):
    """z [S, hidden] -> the gate of every router column [S, router_experts], 0
    where the column was not chosen."""
    logits = z @ w["router"].astype(F32)
    score = (jax.nn.sigmoid(logits) if m["moe_router_activation_func"] == "sigmoid"
             else jax.nn.softmax(logits, axis=-1))
    _, top_i = jax.lax.top_k(score + w["router_bias"].astype(F32), m["num_experts_per_token"])
    top_s = jnp.take_along_axis(score, top_i, axis=-1)
    if m["moe_renormalize"] and "no_renorm" not in faults:
        top_s = top_s / (jnp.sum(top_s, axis=-1, keepdims=True) + 1e-20)
    if "no_route_scale" not in faults:
        top_s = top_s * m["routed_scaling_factor"]
    return jnp.zeros_like(score).at[jnp.arange(z.shape[0])[:, None], top_i].set(top_s)


def routed(z, w, m: dict, faults: frozenset = frozenset()):
    """The held experts' gated outputs: this share's routed part."""
    first, held = m["expert_first"], m["num_experts"]
    here = gates(z, w, m, faults)[:, first:first + held]                  # [S, held]

    def one(out, e):
        wg, wu, wd, ge = e
        return out + ge[:, None] * _r(_swiglu(z, wg, wu, wd, faults), faults), None

    return jax.lax.scan(one, jnp.zeros_like(z),
                        (w["w_gate"], w["w_up"], w["w_down"], here.T))[0]


def shared(z, w, m: dict, faults: frozenset = frozenset()):
    """The shared expert's output, unweighted: every chip's, in full."""
    return _r(_swiglu(z, w["shared_gate"], w["shared_up"], w["shared_down"], faults), faults)


def layer(x, w, kind: str, dense: bool, m: dict, faults: frozenset = frozenset(),
          skip_mixer: bool = False):
    """One layer over x [S, hidden]; `w`: the layer's own leaves."""
    eps = m["rms_norm_eps"]
    u = _r(_norm(x, w["input_norm"], eps), faults)
    if skip_mixer:
        h = x
    elif kind == KDA:
        h = _r(x + _r(_kda(u, w, m, faults)[0], faults), faults)
    else:
        h = _r(x + _r(_mla(u, w, m, faults), faults), faults)
    z = _r(_norm(h, w["post_norm"], eps), faults)
    if dense:
        y = _r(_swiglu(z, w["gate"], w["up"], w["down"], faults), faults)
    else:
        y = _r(routed(z, w, m, faults), faults) + _r(shared(z, w, m, faults), faults)
        y = _r(y, faults)
    return _r(h + y, faults)


ATTENTION = ("q_b", "kv_a", "kv_a_norm", "kv_b", "o")


def _holds(name: str, kind: str) -> bool:
    if name.startswith("kda_"):
        return kind == KDA
    return kind != KDA or name not in ATTENTION


@functools.partial(jax.jit, static_argnames=("m", "faults", "kind", "dense", "skip_mixer"))
def _layer(x, stack, at, own, *, kind, dense, m, faults: frozenset, skip_mixer: bool = False):
    # the layer's weights are taken out of the stack inside the program, a
    # matrix where it is used: `at` its index among the stack's layers, `own`
    # among the stack's layers of its kind
    w = {n: jax.lax.dynamic_index_in_dim(
        v, own if n.startswith("kda_") or n in ATTENTION else at, 0, keepdims=False)
         for n, v in stack.items() if _holds(n, kind)}
    with jax.default_matmul_precision("highest"):
        return layer(x, w, kind, dense, dict(m), faults, skip_mixer)


def _places(m: dict):
    """(stack, index in it, index among the stack's layers of its kind, kind,
    dense) of every layer."""
    kinds, k = kinds_of(m), m["first_k_dense_replace"]
    for i, kind in enumerate(kinds):
        first = 0 if i < k else k
        yield (("dense_layers" if i < k else "layers"), i - first,
               kinds[first:i].count(kind), kind, i < k)


def hidden_states(params, ids, m: dict, **faults):
    """ids [S] -> final-norm hidden states [S, hidden], float32; `m`: the
    configuration file's keys (`KEYS`). A layer at a time. `faults`: FAULTS
    names set true, for the probe."""
    unknown = set(faults) - set(FAULTS)
    if unknown:
        raise TypeError(f"reference_kimi_linear: unknown fault {sorted(unknown)}")
    on = frozenset(k for k, v in faults.items() if v)
    frozen = _flat(m)
    kinds = kinds_of(m)
    # the control's layer: the middle mixer
    skipped = [i for i, k in enumerate(kinds) if k == KDA]
    skipped = skipped[len(skipped) // 2] if "kda_layer_skipped" in on else -1
    x = params["embedding"][ids].astype(F32)
    for i, (stack, at, own, kind, dense) in enumerate(_places(m)):
        x = _layer(x, params[stack], jnp.int32(at), jnp.int32(own), kind=kind, dense=dense,
                   m=frozen, faults=on - {"kda_layer_skipped"}, skip_mixer=i == skipped)
    with jax.default_matmul_precision("highest"):
        return _norm(x, params["final_norm"], m["rms_norm_eps"])


@functools.partial(jax.jit, static_argnames=("m", "faults"))
def _first_state(x, stack, *, m, faults: frozenset):
    w = {n: v[0] for n, v in stack.items() if _holds(n, KDA)}
    with jax.default_matmul_precision("highest"):
        m = dict(m)
        return _kda(_r(_norm(x, w["input_norm"], m["rms_norm_eps"]), faults), w, m, faults)[1]


def first_state(params, ids, m: dict, **faults):
    """The state [H, dk, dv] float32 that the FIRST layer's mixer carries out
    of the last of `ids` [S] (no padding behind them): what a serving cache
    holds for the sequence there. The first layer alone reads the embedding,
    so nothing of the layers above it is in the comparison."""
    on = frozenset(k for k, v in faults.items() if v)
    stack, _, _, kind, _ = next(_places(m))
    assert kind == KDA
    return _first_state(params["embedding"][ids].astype(F32), params[stack], m=_flat(m),
                        faults=on)


@jax.jit
def _head_rows(hidden, rows, head):
    with jax.default_matmul_precision("highest"):
        return hidden[rows] @ head.astype(F32)


def logits_at(params, ids, rows, m: dict, **faults):
    """Logits [len(rows), V] float32 at the given positions of `ids` [S]."""
    return _head_rows(hidden_states(params, ids, m, **faults), rows, params["lm_head"])


MATRICES = ("q_b", "kv_a", "kv_b", "o", "kda_qkv", "kda_f_a", "kda_f_b", "kda_beta",
            "kda_g_a", "kda_g_b", "kda_out", "gate", "up", "down", "router", "w_gate",
            "w_up", "w_down", "shared_gate", "shared_up", "shared_down")


def rounded_to(params, bits: int, only=None):
    """The control of the cell's `correct`: the same tree with every matrix
    rounded to `bits`-bit integers and back, one scale an output channel
    (symmetric, largest magnitude / (2^(bits-1) - 1)). 8 bits is the nearest
    precision below the bfloat16 the configuration states. Norm weights, the
    convolutions, A_log, dt_bias and the selection bias stay. `only`: the
    names to round, of those the tree holds (the probe rounds a matrix at a
    time, so that no second copy of the weights is held)."""
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
