"""Gated DeltaNet: the recurrence of a `linear_attention` layer
(Qwen3-Next), whose state is not a row a position but one matrix a value
head, S [d_k, d_v] float32, carried from token to token by the gated delta
rule. For a token with key k and query q (L2-normalised, q scaled by
d_k^-0.5), value v, decay g <= 0 and write strength beta in (0, 1):

    S' = exp(g) S;   r = S'^T k;   S = S' + k (beta (v - r))^T;   o = S^T q

Three things live here, each a function of arrays alone:

- `causal_conv`: the depthwise convolution over the sequence that q, k and v
  pass through before the recurrence, with the `kernel - 1` positions before
  the segment carried in (a TAIL; zeros before position 0) and the tail
  after the segment's last real position handed back.
- `gated_delta_step`: one token a row, the rule as written above. The decode
  step's form, and under `lax.scan` the token-by-token form of any segment
  (`gated_delta_scan`, what the chunked form is tested against).
- `gated_delta_chunked`: a segment in sub-chunks of `sub` positions (64).
  Inside a sub-chunk the rule is solved for all its positions at once: with
  G the running sum of g, A_ij = beta_i (k_i . k_j) exp(G_i - G_j) for j < i,
  the rows u = (I + A)^-1 (beta v) and w = (I + A)^-1 (beta exp(G) k) are
  what each position writes given the state at the sub-chunk's start (one
  triangular solve of 64 x 64 a head, the rest matrix products), and only
  the sub-chunks run one after the other. Same mathematics as the step:
  tests/test_qwen3_next.py holds them together from a non-zero start state.

A position that carries no token (chunk padding, an idle slot) is made inert
by its caller: g = 0 and beta = 0 leave the state as it was. Everything is
float32; the matrix products are asked for at the highest precision, because
a float32 product on a TPU is otherwise rounded to bfloat16 on the way in,
and the state lives for tens of thousands of tokens.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
from jax import lax

F32 = jnp.float32


def causal_conv(x, tail, w, n_valid):
    """Depthwise causal convolution over the sequence, then SiLU.

    x [B, s, C]: the segment's channels; tail [B, K - 1, C]: the channels of
    the K - 1 positions before it; w [C, K], w[:, K - 1] the current
    position's tap; n_valid [B]: the segment's real positions, a prefix of
    it. Returns (y [B, s, C] in x's dtype, the tail after the last real
    position [B, K - 1, C] in the tail's dtype: the old tail where
    n_valid is 0)."""
    k = w.shape[-1]
    s = x.shape[1]
    full = jnp.concatenate([tail.astype(x.dtype), x], axis=1)
    wf = w.astype(F32)
    y = sum(full[:, j:j + s].astype(F32) * wf[:, j] for j in range(k))
    new_tail = jax.vmap(
        lambda f, n: lax.dynamic_slice_in_dim(f, n, k - 1, axis=0))(
            full, n_valid)
    return jax.nn.silu(y).astype(x.dtype), new_tail.astype(tail.dtype)


def l2_normalise(x, eps: float = 1e-6):
    """x / sqrt(sum x^2 + eps) over the last axis, float32."""
    x = x.astype(F32)
    return x * lax.rsqrt(jnp.sum(x * x, axis=-1, keepdims=True) + eps)


def gated_delta_step(q, k, v, g, beta, state):
    """One token a row. q, k [B, H, d_k]; v [B, H, d_v]; g, beta [B, H];
    state [B, H, d_k, d_v], all float32 -> (o [B, H, d_v], state')."""
    s = state * jnp.exp(g)[..., None, None]
    r = jnp.sum(s * k[..., :, None], axis=-2)
    s = s + k[..., :, None] * (beta[..., None] * (v - r))[..., None, :]
    return jnp.sum(s * q[..., :, None], axis=-2), s


def gated_delta_scan(q, k, v, g, beta, state):
    """A segment token by token. q, k [B, s, H, d_k]; v [B, s, H, d_v];
    g, beta [B, s, H]; state [B, H, d_k, d_v] -> (o [B, s, H, d_v],
    state')."""
    def one(s, xs):
        o, s = gated_delta_step(*xs, s)
        return s, o

    state, o = lax.scan(one, state, tuple(
        jnp.moveaxis(x, 1, 0) for x in (q, k, v, g, beta)))
    return jnp.moveaxis(o, 0, 1), state


def gated_delta_chunked(q, k, v, g, beta, state, sub: int = 64):
    """A segment in sub-chunks of `sub` positions (the module docstring).
    Shapes as `gated_delta_scan`'s; s need not be a multiple of `sub` (the
    segment is padded with inert positions)."""
    b, s, h, dk = q.shape
    dv = v.shape[-1]
    c = min(sub, s)
    pad = -s % c
    n = (s + pad) // c

    def split(x):  # [B, s, H, ...] -> [N, B, H, c, ...]
        x = jnp.pad(x.astype(F32), ((0, 0), (0, pad)) + ((0, 0),) * (x.ndim - 2))
        x = x.reshape(b, n, c, *x.shape[2:])
        return jnp.moveaxis(jnp.moveaxis(x, 3, 2), 1, 0)

    q, k, v, g, beta = (split(x) for x in (q, k, v, g, beta))
    with jax.default_matmul_precision("highest"):
        gc = jnp.cumsum(g, axis=-1)                            # [N, B, H, c]
        i, j = jnp.arange(c)[:, None], jnp.arange(c)[None, :]
        # exp(G_i - G_j) where j <= i; the other half would overflow
        decay = jnp.where(j <= i, jnp.exp(jnp.where(
            j <= i, gc[..., :, None] - gc[..., None, :], 0.0)), 0.0)
        kb = k * beta[..., None]
        a = jnp.where(j < i, jnp.einsum("...id,...jd->...ij", kb, k) * decay,
                      0.0)
        rhs = jnp.concatenate(
            [v * beta[..., None], kb * jnp.exp(gc)[..., None]], axis=-1)
        # (the finite series (I - A)(I + A^2)(I + A^4) ... is the same inverse
        # in matrix products alone and a quarter faster on the chip, but its
        # powers overflow float32 where a sub-chunk's keys are alike)
        sol = jax.scipy.linalg.solve_triangular(
            a + jnp.eye(c, dtype=F32), rhs, lower=True, unit_diagonal=True)
        u, w = sol[..., :dv], sol[..., dv:]
        qk = jnp.einsum("...id,...jd->...ij", q, k) * decay

        def one(st, xs):
            q_i, k_i, u_i, w_i, qk_i, gc_i = xs
            v_new = u_i - jnp.einsum("bhcd,bhde->bhce", w_i, st)
            o = jnp.einsum("bhcd,bhde->bhce", q_i * jnp.exp(gc_i)[..., None],
                           st) + jnp.einsum("bhij,bhje->bhie", qk_i, v_new)
            last = gc_i[..., -1:]
            st = st * jnp.exp(last)[..., None] + jnp.einsum(
                "bhcd,bhce->bhde", k_i * jnp.exp(last - gc_i)[..., None],
                v_new)
            return st, o

        state, o = lax.scan(one, state.astype(F32), (q, k, u, w, qk, gc))
    o = jnp.moveaxis(jnp.moveaxis(o, 0, 1), 3, 2)       # [B, N, c, H, d_v]
    return o.reshape(b, n * c, h, dv)[:, :s], state
