"""The plain reference for NVIDIA-Nemotron-3-Super-120B-A12B
(https://huggingface.co/nvidia/NVIDIA-Nemotron-3-Super-120B-A12B-BF16/blob/main/config.json,
model_type nemotron_h) as one chip of an 8-chip expert group serves it: layers
that are ONE sublayer each (a Mamba-2 mixer, a position-free attention or a
LatentMoE expert block alone), a held share of the routed experts. Straightforward
jax.numpy, float32, matmuls at `highest` precision. No kernels, no cache, the
recurrence TOKEN BY TOKEN (not chunked), no batching, every held expert computed
densely and selected, and nothing imported from the program: it reads the
program's parameter tree (`layers`, stacked on a leading axis: `input_norm` over
all the layers; the attention's `q k v o` over the `*` layers alone, the mixer's
`ssd_...` over the `M` layers alone, the experts' `router router_bias latent_down
latent_up w_up w_down shared_up shared_down` over the `E` layers alone, each in
the layers' order; `[in, out]` matrices; `embedding`, `final_norm`, `lm_head`)
and the configuration file's published keys (`KEYS`), not the program's config
objects.

N(x) = x / rms(x) * w, eps layer_norm_epsilon: every norm, a plain weight. x the
residual stream, h = hidden_size. ONE norm a layer: x <- x + f(N(x)), f by the
layer's letter in hybrid_override_pattern:

    `M`, Mamba-2 (H = mamba_num_heads heads of P = mamba_head_dim, d_inner = H P;
    N = ssm_state_size; G = n_groups, H / G heads a group; K = conv_kernel):
        [z | xBC | dt] = u W_in          widths d_inner | d_inner + 2 G N | H, no bias
        xBC_t = silu(b_c + sum_{j=0..K-1} w_c[:, j] xBC_{t-(K-1)+j})   a channel,
            causal, zeros before position 0; split x [H, P], B [G, N], C [G, N]
        d_t = softplus(dt_t + dt_bias)  a head, NOT clamped;  A = -exp(A_log) a head
        a head h of group g = h // (H / G), S_0 = 0 [P, N] float32, TOKEN BY TOKEN:
            S_t = exp(d_t A) S_{t-1} + (d_t x_t) B_t,g^T;   y_t = S_t C_t,g + D x_t
        out = W_out [ groupnorm_G(y * silu(z)) * w ]   the gate BEFORE the norm, the
            RMS over each group's d_inner / G numbers

    `*`, attention: q = u W_q in num_attention_heads heads of head_dim; k = u W_k,
        v = u W_v in num_key_value_heads heads; NO rotation and no position term of
        any kind; causal softmax, scale head_dim^-0.5; out = concat_heads(P v) W_o;
        no bias, no gate, no QK-norm

    `E`, LatentMoE: s = sigmoid(u W_r) over router_experts columns, float32; the
        num_experts_per_tok largest of s + router_bias (n_group 1: no groups);
        gates g_i = s_i / (sum of the chosen + 1e-20) x routed_scaling_factor
        (norm_topk_prob); l = u W_dn (h -> moe_latent_size); a routed expert is NOT
        gated: E_i(l) = relu(l W1_i)^2 W2_i (mlp_hidden_act relu2); held here are
        experts expert_first .. expert_first + n_routed_experts - 1 of the
        router's, a pick elsewhere adds nothing and nothing stands in for it;
        out = (sum_i g_i E_i(l)) W_up + relu(u W1_s)^2 W2_s, the shared expert on
        the full width

    logits = N_final(x) W_head, untied

Departures from config.json, each also under `assumed` in the configuration's file:
(1) no rotation in attention (the nemotron_h modelling code applies none;
rope_theta and partial_rotary_factor are published and read by nothing); (2) the
split order [z | xBC | dt] and the gate before the grouped norm; (3) no clamp on
the step d (time_step_min / max / floor shape the seeded dt_bias only); (4) as
seeded, A_log = log U(1, 16) a head, dt_bias the inverse softplus of a step
log-uniform in [0.001, 0.1], D = 1; (5) router_bias (e_score_correction_bias) is a
buffer of the checkpoint, seeded by the cell's runner or zero; (6) float32 state
and convolution tail; (7) the shared expert on the full width and the routed ones
on the latent (the published parameter count decides it); (8) no drafting module
(num_nextn_predict_layers 1, mtp_hybrid_override_pattern: ROADMAP M8); (9) the
embedding scaled to initializer_range; (10) weights are random from a seed.

So that a 65,536-position sequence fits one chip beside the bfloat16 weights: a
layer is computed at a time from its own rows of the (bfloat16-rounded) weights,
cast to float32 inside; what is a function of one token alone (the projections,
the experts) runs TOKEN_BLOCK tokens at a time, a mixer's blocks one after the
other with the state and the last K - 1 inputs handed on (the recurrence itself a
token at a time inside); attention runs Q_BLOCK queries of every head against all
the keys at a time.

The keyword arguments of `hidden_states` (`FAULTS`) exist for the tolerance probe
only (`tools/tolerance_probe_nemotron_h.py`); `rounded_to` is its precision control.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

F32 = jnp.float32
Q_BLOCK = 128        # queries a block: [32 heads, 128, 65536] float32 scores are 1 GiB
TOKEN_BLOCK = 4096   # tokens a block of what is a token's own: [4096, 18560] float32
CHUNK = 256          # the prefill chunk the probe's `tail_dropped` control cuts at
KEYS = ("vocab_size", "hidden_size", "num_hidden_layers", "hybrid_override_pattern",
        "num_attention_heads", "num_key_value_heads", "head_dim", "mamba_num_heads",
        "mamba_head_dim", "n_groups", "ssm_state_size", "conv_kernel", "use_conv_bias",
        "n_routed_experts", "router_experts", "expert_first", "num_experts_per_tok",
        "moe_intermediate_size", "moe_latent_size", "moe_shared_expert_intermediate_size",
        "n_shared_experts", "norm_topk_prob", "routed_scaling_factor", "mlp_hidden_act",
        "layer_norm_epsilon", "tie_word_embeddings")
FAULTS = ("bf16_state", "tail_dropped", "state_kept", "no_d_skip", "wrong_group",
          "norm_ungrouped", "gate_after_norm", "relu_not_squared", "no_scale", "no_latent_up",
          "bias_ignored", "mixer_skipped", "bf16_acts")
ATTENTION, MAMBA2, EXPERTS = "full_attention", "mamba2", "experts"
LETTERS = {"M": MAMBA2, "*": ATTENTION, "E": EXPERTS}


def kinds_of(m: dict) -> tuple:
    return tuple(LETTERS[c] for c in m["hybrid_override_pattern"])


def as_program(pub: dict) -> dict:
    """The same keys under the names and in the forms of the program's
    ModelConfig (a plain mapping: nothing of the program is imported). The
    cell's runner checks the model the program built against it."""
    if (pub["mlp_hidden_act"] != "relu2" or pub["tie_word_embeddings"]
            or pub["n_shared_experts"] != 1
            or len(pub["hybrid_override_pattern"]) != pub["num_hidden_layers"]):
        raise ValueError("reference_nemotron_h: relu2 experts beside one shared expert, an "
                         "untied head, a letter of the pattern a layer")
    return dict(
        vocab_size=pub["vocab_size"], hidden_size=pub["hidden_size"],
        num_hidden_layers=pub["num_hidden_layers"],
        num_attention_heads=pub["num_attention_heads"],
        num_key_value_heads=pub["num_key_value_heads"], head_dim=pub["head_dim"],
        layer_types=kinds_of(pub), rms_norm_eps=pub["layer_norm_epsilon"],
        mamba_num_heads=pub["mamba_num_heads"], mamba_head_dim=pub["mamba_head_dim"],
        n_groups=pub["n_groups"], ssm_state_size=pub["ssm_state_size"],
        mamba_d_conv=pub["conv_kernel"], mamba_conv_bias=pub["use_conv_bias"],
        num_experts=pub["n_routed_experts"], router_experts=pub["router_experts"],
        expert_first=pub["expert_first"], num_experts_per_token=pub["num_experts_per_tok"],
        moe_intermediate_size=pub["moe_intermediate_size"],
        moe_latent_size=pub["moe_latent_size"],
        moe_shared_expert_intermediate_size=pub["moe_shared_expert_intermediate_size"],
        n_shared_experts=1, norm_topk_prob=pub["norm_topk_prob"],
        routed_scaling_factor=float(pub["routed_scaling_factor"]), moe_scoring="sigmoid",
        moe_selection_bias=True, hidden_act="relu2", tie_word_embeddings=False,
        attention_bias=False, qk_norm=False,
        # no rotation: the one law of the attention layers, as the program holds it
        rope_parameters=((ATTENTION, (("rope_type", "none"),)),), ssd=True,
        single_sublayer=True)


def _r(x, faults: frozenset):
    """The probe's witness (`bf16_acts`): x rounded to bfloat16 where a
    bfloat16 program holds an activation (the residual stream, a block norm's
    output, a matmul's input); x itself otherwise."""
    if "bf16_acts" not in faults:
        return x
    return jax.lax.reduce_precision(x, exponent_bits=8, mantissa_bits=7)


def _norm(x, w, eps):
    x = x.astype(F32)
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * w.astype(F32)


def _blocks(fn, *xs):
    """fn over TOKEN_BLOCK rows of every x [S, ...] at a time -> [S, ...]."""
    s = xs[0].shape[0]
    tb = TOKEN_BLOCK if s % TOKEN_BLOCK == 0 else s  # the runner pads to a power of two
    out = jax.lax.map(lambda a: fn(*a), tuple(x.reshape(s // tb, tb, *x.shape[1:]) for x in xs))
    return out.reshape(s, *out.shape[2:])


def _attention(u, w, m: dict, faults: frozenset):
    """u [S, hidden] (normed) -> Attn(u) [S, hidden]: grouped-query, causal, no
    position term; Q_BLOCK queries at a time."""
    s = u.shape[0]
    heads, kvh, d = m["num_attention_heads"], m["num_key_value_heads"], m["head_dim"]
    q = _r(_blocks(lambda x: x @ w["q"].astype(F32), u), faults).reshape(s, heads, d)
    k = _r(u @ w["k"].astype(F32), faults).reshape(s, kvh, d)
    v = _r(u @ w["v"].astype(F32), faults).reshape(s, kvh, d)
    group = heads // kvh
    qb = Q_BLOCK if s % Q_BLOCK == 0 else s
    key_at = jnp.arange(s)

    def block(start):
        qs = jax.lax.dynamic_slice_in_dim(q, start, qb, 0).reshape(qb, kvh, group, d)
        scores = jnp.einsum("qhgd,khd->hgqk", qs, k) * F32(d ** -0.5)
        seen = key_at[None, :] <= (start + jnp.arange(qb))[:, None]
        p = jax.nn.softmax(jnp.where(seen, scores, -jnp.inf), axis=-1)
        return jnp.einsum("hgqk,khd->qhgd", p, v).reshape(qb, heads * d)

    out = jax.lax.map(block, jnp.arange(0, s, qb)).reshape(s, heads * d)
    return _blocks(lambda x: _r(x, faults) @ w["o"].astype(F32), out)


def _conv(x, before, at, wc, bias, faults: frozenset):
    """x [T, C] at positions `at` [T], `before` [K - 1, C] the inputs of the
    K - 1 positions before them (zeros before position 0), wc [C, K], bias [C]
    or None -> the causal depthwise convolution, the bias, then SiLU."""
    t_n, kern = x.shape[0], wc.shape[1]
    padded = jnp.concatenate([before, x], axis=0)
    out = 0.0
    for j in range(kern):
        tap = padded[j:j + t_n] * wc[:, j].astype(F32)
        if "tail_dropped" in faults:
            # control: at every chunk boundary the earlier positions are lost
            tap = jnp.where((at % CHUNK)[:, None] >= kern - 1 - j, tap, 0.0)
        out = out + tap
    if bias is not None:
        out = out + bias.astype(F32)
    return jax.nn.silu(out)


def _mamba2(u, w, m: dict, faults: frozenset, start=None):
    """u [S, hidden] (normed) -> (Mamba2(u) [S, hidden], the state the mixer
    carries out of the last token, [H, P, N]). The blocks of TOKEN_BLOCK tokens
    one after the other, the state and the last K - 1 inputs handed on; inside a
    block the recurrence token by token."""
    s = u.shape[0]
    hm, p, grp, n = m["mamba_num_heads"], m["mamba_head_dim"], m["n_groups"], m["ssm_state_size"]
    di, kern, eps = hm * p, m["conv_kernel"], m["layer_norm_epsilon"]
    c = di + 2 * grp * n
    per = hm // grp
    a = -jnp.exp(w["ssd_A_log"].astype(F32))                       # [H]
    tb = TOKEN_BLOCK if s % TOKEN_BLOCK == 0 else s

    def step(st, xs):
        x_t, d_t, b_t, c_t = xs                                    # [H,P] [H] [G,N] [G,N]
        if "wrong_group" in faults:  # control: a head reads the NEXT group's B and C
            b_t, c_t = jnp.roll(b_t, -1, axis=0), jnp.roll(c_t, -1, axis=0)
        bh, ch = jnp.repeat(b_t, per, axis=0), jnp.repeat(c_t, per, axis=0)   # [H, N]
        st = (jnp.exp(d_t * a)[:, None, None] * st
              + (d_t[:, None] * x_t)[:, :, None] * bh[:, None, :])
        if "bf16_state" in faults:
            # (not a cast there and back, which the chip's compiler drops)
            st = jax.lax.reduce_precision(st, exponent_bits=8, mantissa_bits=7)
        return st, jnp.sum(st * ch[:, None, :], axis=-1)

    def block(carry, xs):
        st, before = carry
        ub, at = xs
        zxd = ub @ w["ssd_in"].astype(F32)
        raw = zxd[:, di:di + c]
        xbc = _conv(raw, before, at, w["ssd_conv"], w.get("ssd_conv_bias"), faults)
        x = xbc[:, :di].reshape(tb, hm, p)
        bb = xbc[:, di:di + grp * n].reshape(tb, grp, n)
        cc = xbc[:, di + grp * n:].reshape(tb, grp, n)
        d = jax.nn.softplus(zxd[:, di + c:] + w["ssd_dt_bias"].astype(F32))
        st, y = jax.lax.scan(step, st, (x, d, bb, cc))
        if "no_d_skip" not in faults:
            y = y + w["ssd_D"].astype(F32)[:, None] * x
        y, gate = y.reshape(tb, di), jax.nn.silu(zxd[:, :di])
        groups = 1 if "norm_ungrouped" in faults else grp

        def gnorm(v):
            v = v.reshape(tb, groups, di // groups)
            v = v * jax.lax.rsqrt(jnp.mean(v * v, axis=-1, keepdims=True) + eps)
            return v.reshape(tb, di) * w["ssd_norm"].astype(F32)

        y = gnorm(y) * gate if "gate_after_norm" in faults else gnorm(y * gate)
        return (st, raw[tb - (kern - 1):]), _r(y, faults) @ w["ssd_out"].astype(F32)

    st = jnp.zeros((hm, p, n), F32) if start is None else start
    at = jnp.arange(s).reshape(s // tb, tb)
    (st, _), out = jax.lax.scan(block, (st, jnp.zeros((kern - 1, c), F32)),
                                (u.reshape(s // tb, tb, -1), at))
    return out.reshape(s, -1), st


def gates(z, w, m: dict, faults: frozenset = frozenset()):
    """z [T, hidden] -> [T, router_experts] float32: a token's weight on every
    expert of the router, zeros but for its chosen num_experts_per_tok."""
    s = jax.nn.sigmoid(z @ w["router"].astype(F32))
    chosen_by = s if "bias_ignored" in faults else s + w["router_bias"].astype(F32)
    _, idx = jax.lax.top_k(chosen_by, m["num_experts_per_tok"])
    top = jnp.take_along_axis(s, idx, axis=-1)
    if m["norm_topk_prob"]:
        top = top / (jnp.sum(top, axis=-1, keepdims=True) + 1e-20)
    if "no_scale" not in faults:
        top = top * F32(m["routed_scaling_factor"])
    return jnp.zeros_like(s).at[jnp.arange(z.shape[0])[:, None], idx].set(top)


def _act(x, faults: frozenset):
    x = jax.nn.relu(x)
    return x if "relu_not_squared" in faults else x * x


def _experts(z, w, m: dict, faults: frozenset):
    """z [T, hidden] (normed) -> LatentMoE(z) [T, hidden]: every held expert
    over every token, weighted by the token's gate on it (zero where it did not
    choose it), then the shared expert."""
    first, held = m["expert_first"], m["n_routed_experts"]
    g = jax.lax.dynamic_slice_in_dim(gates(z, w, m, faults), first, held, axis=1)   # [T, E]
    lat = _r(_r(z, faults) @ w["latent_down"].astype(F32), faults)

    def one(out, e):
        mid = _r(_act(lat @ w["w_up"][e].astype(F32), faults), faults)
        y = _r(mid @ w["w_down"][e].astype(F32), faults)
        return out + g[:, e][:, None] * y, None

    routed, _ = jax.lax.scan(one, jnp.zeros_like(lat), jnp.arange(held))
    routed = _r(routed, faults)
    if "no_latent_up" in faults:
        # control: the sum left in the latent's columns of the stream
        out = jnp.pad(routed, ((0, 0), (0, m["hidden_size"] - routed.shape[1])))
    else:
        out = _r(routed @ w["latent_up"].astype(F32), faults)
    mid = _r(_act(z @ w["shared_up"].astype(F32), faults), faults)
    return out + _r(mid @ w["shared_down"].astype(F32), faults)


def layer(x, w, kind: str, m: dict, faults: frozenset = frozenset(), skip: bool = False,
          start=None):
    """One layer over x [S, hidden]; `w`: the layer's own leaves. Returns (x',
    the state a mixer carries out, None for another layer)."""
    if skip:
        return x, None
    u = _blocks(lambda xb: _r(_norm(xb, w["input_norm"], m["layer_norm_epsilon"]), faults), x)
    st = None
    if kind == MAMBA2:
        out, st = _mamba2(u, w, m, faults, start)
    elif kind == ATTENTION:
        out = _attention(u, w, m, faults)
    else:
        out = _blocks(lambda ub: _experts(ub, w, m, faults), u)
    return _r(x + _r(out, faults), faults), st


_OWN = {ATTENTION: ("q", "k", "v", "o"), MAMBA2: ("ssd_",),
        EXPERTS: ("router", "latent_", "w_", "shared_")}


def _kind_of_leaf(name: str):
    """The kind of layer a leaf belongs to, None for the one every layer has."""
    if name == "input_norm":
        return None
    if name.startswith("ssd_"):
        return MAMBA2
    return ATTENTION if name in _OWN[ATTENTION] else EXPERTS


@functools.partial(jax.jit, static_argnames=("m", "faults", "kind", "skip", "kept"))
def _layer(x, stack, at, own, *, kind, m, faults: frozenset, skip: bool = False,
           kept: bool = False):
    # the layer's weights are taken out of the stack inside the program, a
    # matrix where it is used: `at` its index among all the layers, `own`
    # among the layers of its kind
    w = {n: jax.lax.dynamic_index_in_dim(v, at if _kind_of_leaf(n) is None else own, 0,
                                         keepdims=False)
         for n, v in stack.items() if _kind_of_leaf(n) in (None, kind)}
    with jax.default_matmul_precision("highest"):
        m = dict(m)
        start = None
        if kept:
            # control: the slot's last request (this one's first chunk) left its state
            u = _norm(x[:CHUNK], w["input_norm"], m["layer_norm_epsilon"])
            start = _mamba2(u, w, m, faults)[1]
        return layer(x, w, kind, m, faults, skip, start)


def _frozen(m: dict) -> tuple:
    return tuple(sorted((k, m[k]) for k in KEYS))


def _run(params, ids, m: dict, on: frozenset, upto=None):
    """The layers one after the other over ids [S] -> (the stream, the state of
    the last mixer run). `upto`: stop behind that layer."""
    kinds = kinds_of(m)
    mixers = [i for i, k in enumerate(kinds) if k == MAMBA2]
    # the control's layer: the middle mixer
    skipped = mixers[len(mixers) // 2] if "mixer_skipped" in on else -1
    x, st = params["embedding"][ids].astype(F32), None
    for i, kind in enumerate(kinds[:None if upto is None else upto + 1]):
        x, s_i = _layer(x, params["layers"], jnp.int32(i), jnp.int32(kinds[:i].count(kind)),
                        kind=kind, m=_frozen(m), faults=on - {"mixer_skipped", "state_kept"},
                        skip=i == skipped, kept=kind == MAMBA2 and "state_kept" in on)
        st = s_i if s_i is not None else st
    return x, st


def hidden_states(params, ids, m: dict, **faults):
    """ids [S] -> final-norm hidden states [S, hidden], float32; `m`: the
    configuration file's keys (`KEYS`). A layer at a time. `faults`: FAULTS
    names set true, for the probe."""
    unknown = set(faults) - set(FAULTS)
    if unknown:
        raise TypeError(f"reference_nemotron_h: unknown fault {sorted(unknown)}")
    x, _ = _run(params, ids, m, frozenset(k for k, v in faults.items() if v))
    with jax.default_matmul_precision("highest"):
        return _norm(x, params["final_norm"], m["layer_norm_epsilon"])


def first_state(params, ids, m: dict, **faults):
    """The state float32 that the FIRST mixer carries out of the last of `ids`
    [S] (no padding behind them), as a serving cache holds it: [H, P, N]. The
    layers in front of it (an attention and an expert layer in the benchmark's
    cut) are in the comparison, the twenty behind it are not."""
    on = frozenset(k for k, v in faults.items() if v)
    return _run(params, ids, m, on, upto=kinds_of(m).index(MAMBA2))[1]


@jax.jit
def _head_rows(hidden, rows, head):
    with jax.default_matmul_precision("highest"):
        return hidden[rows] @ head.astype(F32)


def logits_at(params, ids, rows, m: dict, **faults):
    """Logits [len(rows), V] float32 at the given positions of `ids` [S]."""
    return _head_rows(hidden_states(params, ids, m, **faults), rows, params["lm_head"])


MATRICES = ("q", "k", "v", "o", "ssd_in", "ssd_out", "router", "latent_down", "latent_up",
            "w_up", "w_down", "shared_up", "shared_down")


def rounded_to(params, bits: int, only=None, donate: bool = False):
    """The control of the cell's `correct`: the same tree with every matrix
    rounded to `bits`-bit integers and back, one scale an output channel
    (symmetric, largest magnitude / (2^(bits-1) - 1)). 8 bits is the nearest
    precision below the bfloat16 the configuration states. Norm weights, the
    convolution and its bias, A_log, D, dt_bias and the router's bias stay.
    `only`: the names to round, of those the tree holds (the probe rounds a
    matrix at a time, so that no second copy of the weights is held, and with
    `donate` into the matrix's own buffer: the banks are 3.5 GB each beside 10.7
    GB of weights; `params` then no longer holds that matrix)."""
    top = 2.0 ** (bits - 1) - 1

    def one(w, axis):
        w32 = w.astype(F32)
        scale = jnp.max(jnp.abs(w32), axis=axis, keepdims=True) / top
        return (jnp.round(w32 / jnp.where(scale > 0, scale, 1.0)) * scale).astype(w.dtype)

    @functools.partial(jax.jit, static_argnums=1, donate_argnums=(0,) if donate else ())
    def rnd(w, axis):
        # a stack a layer at a time: a bank's float32 copy is 0.7 GB a layer
        return jax.lax.map(lambda x: one(x, axis), w) if w.ndim > 2 else one(w, axis)

    def wanted(n, tree):
        return tree.get(n) is not None and (only is None or n in only)

    out = dict(params, layers=dict(params["layers"]))
    for n in MATRICES:  # [L, (E,) in, out]: a scale a layer (and expert) and column
        if wanted(n, out["layers"]):
            out["layers"][n] = rnd(out["layers"][n], -2)
    if wanted("embedding", out):  # [V, h]: a scale a token
        out["embedding"] = rnd(out["embedding"], -1)
    if wanted("lm_head", out):    # [h, V]: a scale an output column
        out["lm_head"] = rnd(out["lm_head"], 0)
    return out
