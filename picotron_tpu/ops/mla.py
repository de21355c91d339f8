"""Latent attention (MLA, the DeepSeek-V2/V3 lineage; openPangu-Ultra-MoE).

A token's attention state is ONE normed latent `c` of `kv_lora_rank` numbers
and `qk_rope_head_dim` rotated numbers `k_r` shared by every head; a head's
key is `[c Wuk_h | k_r]` and its value `c Wuv_h`, where `Wuk_h`, `Wuv_h` are
head h's columns of `kv_b` `[rank, heads * (nope + v)]`. Two forms of the same
scores and outputs:

- **expanded**: keys and values of every head built from the latents
  (`c @ kv_b`), attention per head as usual. What `forward()` runs, and a
  prefill chunk with enough queries a row to pay for expanding each key tile
  once (on the chip, over the serving pool, in one kernel that keeps the
  expanded tile and its scores in VMEM: `ops/paged_attention.py
  latent_prefill_attention`).
- **absorbed**: `q_n . (c Wuk_h) = (q_n Wuk_h^T) . c` and
  `P (c Wuv_h) = (P c) Wuv_h`: the queries are carried into the latent space
  and every head attends the SAME `[T, rank + rope]` rows, the value being the
  first `rank` numbers of the key. What a decode step runs (on the chip
  through `ops/paged_attention.py latent_decode_attention`), and what makes
  the cache latent: nothing per head is ever stored.

Which form a cached step of `s` queries a row takes is arithmetic on the
shapes (`absorbed_suits`): per cached key the expanded form costs the
expansion, `2 rank heads (nope + v)`, plus `2 s heads (nope + rope + v)`; the
absorbed form `2 s heads (2 rank + rope)`.

`latent_attention` below is the plain form of both, in `jax.numpy`: what
every CPU run, `generate()`'s dense cache, the tiny test models and any
shape the two kernels do not take run (`serve/paged_cache.py
LatentPagedCache.attend` decides), and what the kernels are tested against.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from picotron_tpu.ops.rmsnorm import rms_norm
from picotron_tpu.telemetry.scopes import scope

# Keys a tile of `latent_attention`: [heads, s, 512] float32 scores a row and
# tile (268 MB at 128 heads and 1,024 queries).
TILE_KEYS = 512
_NEG = -1e30


def mla_project(h, lp, cfg, keep_flat: bool = False):
    """The normed block input h [B, s, H] -> (q_n [B, s, heads, nope],
    q_r [B, s, heads, rope] unrotated, c [B, s, rank] normed, k_r
    [B, s, rope] unrotated; whether the two are rotated at all is the
    caller's, `cfg.mla_use_nope`). One implementation for `forward()` and the
    cached decode paths, which keep the flat q a value of its own
    (`keep_flat`) so that `q_b` is read where it lies, as
    `models.llama.qkv_proj` says. Where the model scales its latents
    (`cfg.mla_scales`, static; 1.0 and nothing computed otherwise), the scale
    rides the norm's weight in float32, so a latent is rounded once: `c`
    is what a cache holds, after its scale."""
    dt = h.dtype
    b, s, _ = h.shape
    dn, dr, rank = cfg.qk_nope_head_dim, cfg.qk_rope_head_dim, cfg.kv_lora_rank
    q_scale, kv_scale = cfg.mla_scales

    def weight(w, scale):
        return w if scale == 1.0 else w.astype(jnp.float32) * scale

    with scope("mla_q"):
        # through the bottleneck and its norm, or (q_lora_rank 0) straight
        cq = rms_norm(h @ lp["q_a"].astype(dt), weight(lp["q_a_norm"], q_scale),
                      cfg.rms_norm_eps) if cfg.q_lora_rank else h
        q = cq @ lp["q_b"].astype(dt)
        if keep_flat:
            q = jax.lax.optimization_barrier(q)
        q = q.reshape(b, s, -1, dn + dr)
    with scope("mla_kv_latent"):
        ckr = h @ lp["kv_a"].astype(dt)                    # [B, s, rank + rope]
        c = rms_norm(ckr[..., :rank], weight(lp["kv_a_norm"], kv_scale),
                     cfg.rms_norm_eps)
    return q[..., :dn], q[..., dn:], c, ckr[..., rank:]


def up_weights(kv_b, cfg, dt):
    """`kv_b` [rank, heads * (nope + v)] by head: (Wuk [rank, heads, nope],
    Wuv [rank, heads, v])."""
    dn = cfg.qk_nope_head_dim
    w = kv_b.astype(dt).reshape(cfg.kv_lora_rank, -1, dn + cfg.v_head_dim)
    return w[..., :dn], w[..., dn:]


def absorbed_suits(s: int, cfg) -> bool:
    """Whether a cached step of `s` queries a row costs fewer operations a
    key absorbed than expanded (module docstring): below 171 queries at
    openPangu-Ultra's widths, so a decode step is absorbed and a prefill
    chunk of 256 or more expanded."""
    dn, dr, dv, rank = (cfg.qk_nope_head_dim, cfg.qk_rope_head_dim,
                        cfg.v_head_dim, cfg.kv_lora_rank)
    return s * (2 * rank + dr) < rank * (dn + dv) + s * (dn + dr + dv)


def absorb_queries(q_n, kv_b, cfg):
    """q_n [..., heads, nope] -> [..., heads, rank]: the queries in the
    latent space, rounded to the compute dtype as the cache's keys are."""
    with scope("mla_absorb"):
        w_uk, _ = up_weights(kv_b, cfg, q_n.dtype)
        return jnp.einsum("...hd,rhd->...hr", q_n, w_uk)


def values_from_latent(o_lat, kv_b, cfg):
    """o_lat [..., heads, rank] (P c) -> [..., heads, v] (P c Wuv_h)."""
    with scope("mla_absorb"):
        _, w_uv = up_weights(kv_b, cfg, o_lat.dtype)
        return jnp.einsum("...hr,rhd->...hd", o_lat, w_uv)


def latent_attention(q_n, q_r, q_pos, fetch, n_tiles_max: int, tile: int,
                     kv_b, cfg, absorbed: bool | None = None):
    """Causal attention of q_n [B, s, heads, nope] / q_r [B, s, heads, rope]
    (rotated) at positions q_pos [B, s] over a latent cache walked a row
    and a tile at a time under an online softmax: scores and statistics in
    float32, P in the compute dtype for PV, float32 accumulation.

    `fetch(b, t)` -> (ckr [tile, >= rank + rope], kv_pos [tile]): row b's
    tile t, `[c | k_r]` a cached position (what lies beyond rank + rope is
    ignored) and the position each holds; a key is seen where
    0 <= kv_pos <= q_pos. A row walks the tiles up to its own last position
    and no further (at most `n_tiles_max`): a short prompt beside a long one
    does not pay for the long one's keys. Rows at q_pos < 0 (padding) attend
    as position 0 and are discarded by the caller; a row whose positions are
    all negative reads nothing and returns zeros.

    `absorbed`: the form (module docstring); None = by `absorbed_suits`.
    Returns [B, s, heads, v]."""
    b, s, heads, dn = q_n.shape
    dt = q_n.dtype
    rank, dr, dv = cfg.kv_lora_rank, cfg.qk_rope_head_dim, cfg.v_head_dim
    if absorbed is None:
        absorbed = absorbed_suits(s, cfg)
    scale = 1.0 / (dn + dr) ** 0.5
    if absorbed:
        q_lat = absorb_queries(q_n, kv_b, cfg)
    else:
        q_lat, (w_uk, w_uv) = q_n, up_weights(kv_b, cfg, dt)

    def row(args):
        bi, q1, qr, qp = args            # [s, heads, .], [s, heads, rope], [s]
        qp0 = jnp.maximum(qp, 0)

        def body(t, carry):
            m, l, acc = carry
            ckr, kp = fetch(bi, t)
            c, kr = ckr[:, :rank], ckr[:, rank:rank + dr]
            if absorbed:
                keys, vals = c, c
                sc = jnp.einsum("shr,tr->hst", q1, c,
                                preferred_element_type=jnp.float32)
            else:
                with scope("mla_absorb"):
                    keys = jnp.einsum("tr,rhd->thd", c, w_uk)
                    vals = jnp.einsum("tr,rhd->thd", c, w_uv)
                sc = jnp.einsum("shd,thd->hst", q1, keys,
                                preferred_element_type=jnp.float32)
            sc = (sc + jnp.einsum("shd,td->hst", qr, kr,
                                  preferred_element_type=jnp.float32)) * scale
            seen = ((kp[None, :] >= 0) & (kp[None, :] <= qp0[:, None]))[None]
            sc = jnp.where(seen, sc, _NEG)
            m_new = jnp.maximum(m, jnp.max(sc, axis=-1))
            alpha = jnp.exp(m - m_new)
            p = jnp.where(seen, jnp.exp(sc - m_new[..., None]), 0.0)
            l = l * alpha + jnp.sum(p, axis=-1)
            pv = (jnp.einsum("hst,tr->hsr", p.astype(dt), vals,
                             preferred_element_type=jnp.float32) if absorbed
                  else jnp.einsum("hst,thd->hsd", p.astype(dt), vals,
                                  preferred_element_type=jnp.float32))
            return m_new, l, acc * alpha[..., None] + pv

        init = (jnp.full((heads, s), _NEG, jnp.float32),
                jnp.zeros((heads, s), jnp.float32),
                jnp.zeros((heads, s, rank if absorbed else dv), jnp.float32))
        n_tiles = jnp.clip(-(-(jnp.max(qp) + 1) // tile), 0, n_tiles_max)
        _, l, acc = jax.lax.fori_loop(0, n_tiles, body, init)
        out = (acc / jnp.where(l == 0.0, 1.0, l)[..., None]).astype(dt)
        return out.transpose(1, 0, 2)                       # [s, heads, .]

    out = jax.lax.map(row, (jnp.arange(b), q_lat, q_r, q_pos))
    return values_from_latent(out, kv_b, cfg) if absorbed else out
