"""EVA attention ("efficient attention via control variates", ICLR 2023, in
the form EvaByte's release describes): exact attention on a local set plus
one term a chunk of the rest, under one normaliser.

Positions fall into windows `w(i) = i // window` and chunks `c(i) = i //
chunk`. A query at `i` sees the keys of its own window up to itself one by
one, and every chunk of every CLOSED window (`C < (window // chunk) w(i)`)
through one summary row `(k~_C, v~_C)`, all under one softmax with the one
scale `s = head_dim^-1/2`:

    k~_C = sum_{j in C} softmax_{j in C}(s k_j . mu)  k_j
    v~_C = sum_{j in C} softmax_{j in C}(s k_j . phi) v_j

with one learned vector `mu` and one `phi` a KV head (the layer's `eva_mu`,
`eva_phi`); keys are pooled after their rotation. A summary row has the
shape of a K/V row, which is what lets a cache hold both kinds side by side.

This file is the one description of the law. `forward()`
(models/llama.py) and the contiguous cache of `generate()`
(generate.EvaCache) attend densely through `eva_attention`, a mask over
`[k | k~]`; the serving cache (serve/paged_cache.py EvaPagedCache) lays a
slot's visible rows out so that they are the rows below one length, and
reads them with the programs every other model's cache uses.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from picotron_tpu.telemetry.scopes import scope


@scope("eva_summarise")
def eva_summarise(k, v, mu, phi):
    """The summary row of each chunk: k, v [..., C, Hkv, D] (a chunk's
    rotated keys and its values on axis -3), mu, phi [Hkv, D] ->
    (k~, v~) [..., Hkv, D] in the inputs' dtypes. Two softmaxes over the
    chunk's C positions a head, in float32."""
    s = k.shape[-1] ** -0.5
    k32, v32 = k.astype(jnp.float32), v.astype(jnp.float32)

    def pooled(x32, vec):
        score = jnp.einsum("...chd,hd->...ch", k32,
                           vec.astype(jnp.float32)) * s
        return jnp.einsum("...ch,...chd->...hd",
                          jax.nn.softmax(score, axis=-2), x32)

    return pooled(k32, mu).astype(k.dtype), pooled(v32, phi).astype(v.dtype)


def chunk_summaries(k, v, mu, phi, chunk: int):
    """The summaries of the whole chunks of a segment that starts on a
    chunk boundary: k, v [B, S, Hkv, D] -> (k~, v~) [B, S // chunk, Hkv,
    D]; what is left over after them is in no summary yet."""
    b, s = k.shape[:2]
    n = s // chunk

    def by_chunk(x):
        return x[:, :n * chunk].reshape(b, n, chunk, *x.shape[2:])

    return eva_summarise(by_chunk(k), by_chunk(v), mu, phi)


def eva_attention(q, k, v, ks, vs, q_pos, window: int, chunk: int):
    """Dense EVA attention. q [B, s, Hq, D] at positions q_pos ([s]
    batch-shared or [B, s]); k, v [B, T, Hkv, D] with row j the position
    j; ks, vs [B, Tc, Hkv, D] with row C the summary of chunk C. A row
    that no query may see (a position past the query, a chunk not yet
    closed or not yet written) is masked, whatever it holds. Scores and
    softmax in float32, P in the value dtype. -> [B, s, Hq, D]."""
    b, s, hq, d = q.shape
    hkv = k.shape[2]
    qp = jnp.maximum(q_pos, 0)[..., None]                    # [(B,) s, 1]
    at = jnp.arange(k.shape[1])
    seen = (at // window == qp // window) & (at <= qp)        # [(B,) s, T]
    closed = jnp.arange(ks.shape[1]) < (qp // window) * (window // chunk)
    mask = jnp.concatenate([seen, closed], axis=-1)
    if mask.ndim == 2:
        mask = mask[None]
    qg = q.reshape(b, s, hkv, hq // hkv, d)
    keys = jnp.concatenate([k, ks], axis=1)
    vals = jnp.concatenate([v, vs], axis=1)
    sc = jnp.einsum("bshgd,bthd->bhgst", qg, keys).astype(jnp.float32)
    sc = jnp.where(mask[:, None, None], sc / (d ** 0.5), -jnp.inf)
    p = jax.nn.softmax(sc, axis=-1).astype(q.dtype)
    return jnp.einsum("bhgst,bthd->bshgd", p, vals).reshape(b, s, hq, d)
