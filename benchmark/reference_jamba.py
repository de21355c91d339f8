"""The plain reference for AI21-Jamba2-3B
(https://huggingface.co/ai21labs/AI21-Jamba2-3B/blob/main/config.json), the
whole published model: Mamba-1 selective-scan mixers and position-free
multi-query attentions 13 : 1, a dense gated MLP in every layer, a tied head.
Straightforward jax.numpy, float32, matmuls at `highest` precision. No kernels,
no cache, the recurrence TOKEN BY TOKEN, no batching, and nothing imported from
the program: it reads the program's parameter tree (`layers`, stacked on a
leading axis: the leaves every layer has over all the layers, the softmax
attention's `q k v o` over the attention layers alone, the mixer's `ssm_...`
over the mixers alone, each in the layers' order; `[in, out]` matrices;
`embedding`, `final_norm`) and the configuration file's published keys (`KEYS`),
not the program's config objects.

N(x) = x / rms(x) * w, eps rms_norm_eps: every norm. x the residual stream.
Layer i (0-based) is an attention where i % attn_layer_period ==
attn_layer_offset, a Mamba mixer otherwise. Every layer:

    h = x + Mixer(N_in(x));   y = h + W_down (silu(W_gate z) * (W_up z)),  z = N_post(h)

    Mamba(h), d_inner = mamba_expand x hidden, N = mamba_d_state, R = mamba_dt_rank:
        [u | z] = h W_in                                  (no bias)
        u_t = silu(b_c + sum_{j=0..K-1} w_c[:, j] u_{t-(K-1)+j})   a channel, causal,
            zeros before position 0 (K = mamba_d_conv)
        [r | B | C] = u_t W_x (R, N, N; no bias);  r, B, C each through N of its own
        dt_t = softplus(r W_dt + b_dt);   A = -exp(A_log)  [d_inner, N]
        S_0 = 0 [d_inner, N], TOKEN BY TOKEN under lax.scan:
            S_t = exp(dt_t[:, None] A) S_{t-1} + (dt_t u_t)[:, None] B_t[None, :]
            y_t = S_t C_t + D u_t
        out = (y_t * silu(z_t)) W_out                     (no bias)

    Attn(h): q = h W_q in num_attention_heads heads of D = hidden / heads; k = h W_k,
        v = h W_v, num_key_value_heads (1) heads of D, shared by all the query
        heads; NO rotation and no position term of any kind; causal softmax,
        scale D^-0.5; out = concat_heads(P v) W_o; no bias, no gate, no QK-norm

    logits = N_final(x) E^T, E the embedding (tied)

The state is HELD transposed here, [N, d_inner] (S^T: a channel's N states down
the rows), as the serving cache lays it, and `first_state` returns it so; the
arithmetic is the lines above, element for element.

Departures from config.json, each also under `assumed` in the configuration's
file (the released Jamba modelling code's): (1) the layer order from
attn_layer_period and attn_layer_offset; (2) head_dim = hidden_size /
num_attention_heads (no key); (3) the three inner norms of r, B and C; (4)
float32 state and convolution tail, dt, exp and the recurrence in float32; (5)
as seeded, A_log = log(1..N) a channel, D = 1, b_dt the inverse softplus of a
step log-uniform in [0.001, 0.1] (Gu and Dao's Mamba initialiser); (6)
num_experts 1: every feed-forward the dense gated MLP; (7) weights are random
from a seed.

So that an 8k-token sequence fits one chip beside the bfloat16 weights: a layer
is computed at a time from its own rows of the (bfloat16-rounded) weights, cast
to float32 inside; attention runs Q_BLOCK queries of every head against all the
keys at a time.

The keyword arguments of `hidden_states` (`FAULTS`) exist for the tolerance
probe only (`tools/tolerance_probe_jamba.py`); `rounded_to` is its precision
control.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

F32 = jnp.float32
Q_BLOCK = 512   # queries a block: [20 heads, 512, 8192] float32 scores are 0.3 GiB
CHUNK = 256     # the prefill chunk the probe's `tail_dropped` control cuts at
KEYS = ("vocab_size", "hidden_size", "intermediate_size", "num_hidden_layers",
        "num_attention_heads", "num_key_value_heads", "attn_layer_period",
        "attn_layer_offset", "mamba_d_state", "mamba_d_conv", "mamba_expand",
        "mamba_dt_rank", "mamba_conv_bias", "mamba_proj_bias", "rms_norm_eps",
        "tie_word_embeddings", "num_experts")
FAULTS = ("bf16_state", "tail_dropped", "state_kept", "no_dt_norm", "no_d_skip", "no_gate",
          "attn_rope", "mixer_skipped", "bf16_acts")
ATTENTION, MAMBA = "full_attention", "mamba"


def kinds_of(m: dict) -> tuple:
    every, at = m["attn_layer_period"], m["attn_layer_offset"]
    return tuple(ATTENTION if i % every == at else MAMBA
                 for i in range(m["num_hidden_layers"]))


def as_program(pub: dict) -> dict:
    """The same keys under the names and in the forms of the program's
    ModelConfig (a plain mapping: nothing of the program is imported). The
    cell's runner checks the model the program built against it."""
    if pub["num_experts"] != 1 or pub["mamba_proj_bias"] or not pub["tie_word_embeddings"]:
        raise ValueError("reference_jamba: the dense Jamba with a tied head and no projection bias")
    return dict(
        vocab_size=pub["vocab_size"], hidden_size=pub["hidden_size"],
        intermediate_size=pub["intermediate_size"],
        num_hidden_layers=pub["num_hidden_layers"],
        num_attention_heads=pub["num_attention_heads"],
        num_key_value_heads=pub["num_key_value_heads"],
        head_dim=pub["hidden_size"] // pub["num_attention_heads"],
        layer_types=kinds_of(pub), rms_norm_eps=pub["rms_norm_eps"],
        mamba_d_state=pub["mamba_d_state"], mamba_d_conv=pub["mamba_d_conv"],
        mamba_expand=pub["mamba_expand"], mamba_dt_rank=pub["mamba_dt_rank"],
        mamba_conv_bias=pub["mamba_conv_bias"], mamba_proj_bias=False,
        tie_word_embeddings=True, num_experts=0, attention_bias=False, qk_norm=False,
        # no rotation: the one law of the attention layers, as the program holds it
        rope_parameters=((ATTENTION, (("rope_type", "none"),)),), ssm=True)


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


def _rope(x, theta: float = 10000.0):
    """The probe's `attn_rope` control: rotate-half RoPE over the whole head."""
    s, d = x.shape[0], x.shape[-1]
    inv = 1.0 / theta ** (jnp.arange(0, d, 2, dtype=F32) / d)
    ang = jnp.arange(s, dtype=F32)[:, None] * inv[None, :]
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    x1, x2 = x[..., :d // 2], x[..., d // 2:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)


def _attention(u, w, m: dict, faults: frozenset):
    """u [S, hidden] (normed) -> Attn(u) [S, hidden]: multi-query, causal, no
    position term; Q_BLOCK queries at a time."""
    s = u.shape[0]
    heads, kvh = m["num_attention_heads"], m["num_key_value_heads"]
    d = m["hidden_size"] // heads
    q = (u @ w["q"].astype(F32)).reshape(s, heads, d)
    k = (u @ w["k"].astype(F32)).reshape(s, kvh, d)
    v = (u @ w["v"].astype(F32)).reshape(s, kvh, d)
    if "attn_rope" in faults:
        q, k = _rope(q), _rope(k)
    q, k, v = _r(q, faults), _r(k, faults), _r(v, faults)
    group = heads // kvh
    qb = Q_BLOCK if s % Q_BLOCK == 0 else s  # the runner pads to a power of two
    key_at = jnp.arange(s)

    def block(start):
        qs = jax.lax.dynamic_slice_in_dim(q, start, qb, 0).reshape(qb, kvh, group, d)
        scores = jnp.einsum("qhgd,khd->hgqk", qs, k) * F32(d ** -0.5)
        seen = key_at[None, :] <= (start + jnp.arange(qb))[:, None]
        p = jax.nn.softmax(jnp.where(seen, scores, -jnp.inf), axis=-1)
        return jnp.einsum("hgqk,khd->qhgd", p, v).reshape(qb, heads * d)

    out = jax.lax.map(block, jnp.arange(0, s, qb)).reshape(s, heads * d)
    return _r(out, faults) @ w["o"].astype(F32)


def _conv(x, wc, bias, faults: frozenset):
    """x [S, C], wc [C, K], bias [C] or None -> the causal depthwise
    convolution, zeros before position 0, the bias, then SiLU."""
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
    if bias is not None:
        out = out + bias.astype(F32)
    return jax.nn.silu(out)


def _scan(u, dt, b, c, a_t, faults: frozenset):
    """u, dt [S, Di], b, c [S, N], a_t [N, Di] (A transposed) -> (S^T after the
    last token [N, Di], y [S, Di] without D u): the selective scan token by
    token from a zero state."""
    def step(st, xs):
        u_t, dt_t, b_t, c_t = xs
        st = jnp.exp(dt_t[None, :] * a_t) * st + b_t[:, None] * (dt_t * u_t)[None, :]
        if "bf16_state" in faults:
            # (not a cast there and back, which the chip's compiler drops)
            st = jax.lax.reduce_precision(st, exponent_bits=8, mantissa_bits=7)
        return st, c_t @ st

    start = jnp.zeros(a_t.shape, F32)
    if "state_kept" in faults:
        # control: the slot's last request (this one's first chunk) left its state
        start, _ = jax.lax.scan(step, start, tuple(x[:CHUNK] for x in (u, dt, b, c)))
    return jax.lax.scan(step, start, (u, dt, b, c))


def _mamba(h, w, m: dict, faults: frozenset):
    """h [S, hidden] (normed) -> (Mamba(h) [S, hidden], the state the mixer
    carries out of the last token, S^T [N, Di])."""
    di = m["mamba_expand"] * m["hidden_size"]
    n, r, eps = m["mamba_d_state"], m["mamba_dt_rank"], m["rms_norm_eps"]
    uz = h @ w["ssm_in"].astype(F32)
    u = _conv(uz[:, :di], w["ssm_conv"], w.get("ssm_conv_bias"), faults)
    x = _r(u, faults) @ w["ssm_x"].astype(F32)
    rr = x[:, :r] if "no_dt_norm" in faults else _norm(x[:, :r], w["ssm_dt_norm"], eps)
    b = _norm(x[:, r:r + n], w["ssm_b_norm"], eps)
    c = _norm(x[:, r + n:], w["ssm_c_norm"], eps)
    dt = jax.nn.softplus(_r(rr, faults) @ w["ssm_dt"].astype(F32) + w["ssm_dt_bias"].astype(F32))
    a_t = -jnp.exp(w["ssm_A_log"].astype(F32)).T
    st, y = _scan(u, dt, b, c, a_t, faults)
    if "no_d_skip" not in faults:
        y = y + w["ssm_D"].astype(F32) * u
    if "no_gate" not in faults:
        y = y * jax.nn.silu(uz[:, di:])
    return _r(y, faults) @ w["ssm_out"].astype(F32), st


def _mlp(z, w):
    return (jax.nn.silu(z @ w["gate"].astype(F32)) * (z @ w["up"].astype(F32))) @ w[
        "down"].astype(F32)


def layer(x, w, kind: str, m: dict, faults: frozenset = frozenset(), skip_mixer: bool = False):
    """One layer over x [S, hidden]; `w`: the layer's own leaves."""
    eps = m["rms_norm_eps"]
    u = _r(_norm(x, w["input_norm"], eps), faults)
    if skip_mixer:
        h = x
    elif kind == MAMBA:
        h = _r(x + _r(_mamba(u, w, m, faults)[0], faults), faults)
    else:
        h = _r(x + _r(_attention(u, w, m, faults), faults), faults)
    z = _r(_norm(h, w["post_norm"], eps), faults)
    return _r(h + _r(_mlp(z, w), faults), faults)


_OWN = {MAMBA: ("ssm_",), ATTENTION: ("q", "k", "v", "o")}


def _kind_of_leaf(name: str):
    """The kind of mixer a leaf belongs to, None for one every layer has."""
    if name.startswith("ssm_"):
        return MAMBA
    return ATTENTION if name in _OWN[ATTENTION] else None


@functools.partial(jax.jit, static_argnames=("m", "faults", "kind", "skip_mixer"))
def _layer(x, stack, at, own, *, kind, m, faults: frozenset, skip_mixer: bool = False):
    # the layer's weights are taken out of the stack inside the program, a
    # matrix where it is used: `at` its index among all the layers, `own`
    # among the layers of its kind
    w = {n: jax.lax.dynamic_index_in_dim(v, at if _kind_of_leaf(n) is None else own, 0,
                                         keepdims=False)
         for n, v in stack.items() if _kind_of_leaf(n) in (None, kind)}
    with jax.default_matmul_precision("highest"):
        return layer(x, w, kind, dict(m), faults, skip_mixer)


def hidden_states(params, ids, m: dict, **faults):
    """ids [S] -> final-norm hidden states [S, hidden], float32; `m`: the
    configuration file's keys (`KEYS`). A layer at a time. `faults`: FAULTS
    names set true, for the probe."""
    unknown = set(faults) - set(FAULTS)
    if unknown:
        raise TypeError(f"reference_jamba: unknown fault {sorted(unknown)}")
    on = frozenset(k for k, v in faults.items() if v)
    frozen = tuple(sorted((k, m[k]) for k in KEYS))
    kinds = kinds_of(m)
    # the control's layer: the middle mixer
    skipped = [i for i, k in enumerate(kinds) if k == MAMBA]
    skipped = skipped[len(skipped) // 2] if "mixer_skipped" in on else -1
    x = params["embedding"][ids].astype(F32)
    for i, kind in enumerate(kinds):
        x = _layer(x, params["layers"], jnp.int32(i), jnp.int32(kinds[:i].count(kind)),
                   kind=kind, m=frozen, faults=on - {"mixer_skipped"}, skip_mixer=i == skipped)
    with jax.default_matmul_precision("highest"):
        return _norm(x, params["final_norm"], m["rms_norm_eps"])


@functools.partial(jax.jit, static_argnames=("m", "faults"))
def _first_state(x, stack, *, m, faults: frozenset):
    w = {n: v[0] for n, v in stack.items() if _kind_of_leaf(n) in (None, MAMBA)}
    with jax.default_matmul_precision("highest"):
        m = dict(m)
        return _mamba(_r(_norm(x, w["input_norm"], m["rms_norm_eps"]), faults), w, m, faults)[1]


def first_state(params, ids, m: dict, **faults):
    """The state float32 that the FIRST layer's mixer carries out of the last
    of `ids` [S] (no padding behind them), transposed as a serving cache holds
    it: S^T [mamba_d_state, d_inner]. The first layer alone reads the embedding,
    so nothing of the layers above it is in the comparison."""
    on = frozenset(k for k, v in faults.items() if v)
    assert kinds_of(m)[0] == MAMBA
    return _first_state(params["embedding"][ids].astype(F32), params["layers"],
                        m=tuple(sorted((k, m[k]) for k in KEYS)), faults=on)


@jax.jit
def _head_rows(hidden, rows, embedding):
    with jax.default_matmul_precision("highest"):
        return hidden[rows] @ embedding.astype(F32).T


def logits_at(params, ids, rows, m: dict, **faults):
    """Logits [len(rows), V] float32 at the given positions of `ids` [S]."""
    return _head_rows(hidden_states(params, ids, m, **faults), rows, params["embedding"])


MATRICES = ("q", "k", "v", "o", "ssm_in", "ssm_x", "ssm_dt", "ssm_out", "gate", "up", "down")


def rounded_to(params, bits: int, only=None):
    """The control of the cell's `correct`: the same tree with every matrix
    rounded to `bits`-bit integers and back, one scale an output channel
    (symmetric, largest magnitude / (2^(bits-1) - 1)). 8 bits is the nearest
    precision below the bfloat16 the configuration states. Norm weights, the
    convolution and its bias, A_log, D and the step's bias stay. `only`: the
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

    out = dict(params, layers=dict(params["layers"]))
    for n in MATRICES:  # [L, in, out]: a scale a layer and column
        if wanted(n, out["layers"]):
            out["layers"][n] = rnd(out["layers"][n], -2)
    if wanted("embedding", out):  # [V, h]: a scale a token (the tied head's output column)
        out["embedding"] = rnd(out["embedding"], -1)
    return out
