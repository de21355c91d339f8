"""Reference attention path: plain jnp scaled-dot-product attention with GQA.

This is the TPU analogue of the reference's SDPA fallback
(ref: picotron/model.py:155-158) and doubles as the ground truth that the
Pallas flash kernel and the context-parallel ring are tested against
(the reference tests TP the same way, against an unsharded nn.Linear).

Softmax statistics are computed in fp32 regardless of input dtype. The
log-sum-exp can be returned so the context-parallel ring can merge partial
results across K/V blocks (ref: context_parallel.py:112-128 keeps LSE for the
same reason).
"""

from __future__ import annotations

import jax.numpy as jnp
from jax.ad_checkpoint import checkpoint_name


def repeat_kv(x: jnp.ndarray, n_rep: int) -> jnp.ndarray:
    """GQA: expand kv heads to match query heads.

    x: [batch, seq, kv_heads, head_dim] -> [batch, seq, kv_heads*n_rep, head_dim]
    (ref: model.py:142-143 uses repeat_interleave on the head axis).
    """
    if n_rep == 1:
        return x
    b, s, kv, d = x.shape
    return jnp.broadcast_to(x[:, :, :, None, :], (b, s, kv, n_rep, d)).reshape(b, s, kv * n_rep, d)


def sdpa_attention_bwd_from_saved(
    q: jnp.ndarray,
    k: jnp.ndarray,
    v: jnp.ndarray,
    out: jnp.ndarray,
    lse: jnp.ndarray,
    dout: jnp.ndarray,
    *,
    causal: bool = True,
    q_positions: jnp.ndarray | None = None,
    kv_positions: jnp.ndarray | None = None,
    sm_scale: float | None = None,
):
    """(dq, dk, dv) from the forward's saved (out, lse) — the flash-attn-2
    backward identity in plain jnp, the reference twin of the Pallas
    backward kernels (ops/flash_attention.py `_bwd`):

        p  = exp(s - lse)            (GLOBALLY normalized probabilities)
        dv = pᵀ @ dout
        ds = p * (dout @ vᵀ - delta),  delta = rowsum(dout * out)
        dq = ds @ k * scale,  dk = dsᵀ @ q * scale

    Because `p` is normalized by the *saved* lse (not a recomputed local
    one), calling this on one K/V block of a larger attention — with the
    block's positions and the GLOBAL (out, lse, dout) — yields exactly that
    block's additive contribution to the global gradients. That property is
    what the context-parallel ring backward sums over visiting blocks
    (ops/ring_attention.py ring_attention_bwd_from_saved); it does NOT hold
    for AD of a per-block forward, which normalizes by the block-local lse.

    Shapes follow sdpa_attention: q/out/dout [B, Sq, Hq, D]; k/v
    [B, Sk, Hkv, D] (GQA unexpanded — the group's query-head grads sum into
    the kv head); lse [B, Hq, Sq] fp32. Rows with no visible keys
    (lse = -inf) contribute zero everywhere.
    """
    b, sq, h, d = q.shape
    sk = k.shape[1]
    n_rep = h // k.shape[2]
    if sm_scale is None:
        sm_scale = 1.0 / (d ** 0.5)
    kx = repeat_kv(k, n_rep)
    vx = repeat_kv(v, n_rep)

    scores = jnp.einsum("bqhd,bkhd->bhqk", q, kx,
                        preferred_element_type=jnp.float32) * sm_scale
    if causal:
        qp = q_positions if q_positions is not None else jnp.arange(sq)
        kp = kv_positions if kv_positions is not None else jnp.arange(sk)
        mask = qp[:, None] >= kp[None, :]
        scores = jnp.where(mask[None, None, :, :], scores, -1e30)
    lse_f = lse.astype(jnp.float32)[..., None]        # [B, H, Sq, 1]
    # exp(-1e30 - lse) underflows to exactly 0 for masked entries; a row
    # with lse = -inf (no visible keys anywhere) must also contribute 0.
    p = jnp.exp(scores - jnp.maximum(lse_f, -1e30))
    p = jnp.where(jnp.isinf(lse_f) & (lse_f < 0), 0.0, p)

    do32 = dout.astype(jnp.float32)
    delta = jnp.sum(do32 * out.astype(jnp.float32), axis=-1)  # [B, Sq, Hq]
    delta = jnp.transpose(delta, (0, 2, 1))[..., None]        # [B, H, Sq, 1]
    dv_x = jnp.einsum("bhqk,bqhd->bkhd", p, do32)
    dp = jnp.einsum("bqhd,bkhd->bhqk", do32, vx.astype(jnp.float32))
    ds = p * (dp - delta)
    dq = jnp.einsum("bhqk,bkhd->bqhd", ds,
                    kx.astype(jnp.float32)) * sm_scale
    dk_x = jnp.einsum("bhqk,bqhd->bkhd", ds,
                      q.astype(jnp.float32)) * sm_scale
    if n_rep > 1:
        dk_x = dk_x.reshape(b, sk, h // n_rep, n_rep, d).sum(axis=3)
        dv_x = dv_x.reshape(b, sk, h // n_rep, n_rep, d).sum(axis=3)
    return (dq.astype(q.dtype), dk_x.astype(k.dtype), dv_x.astype(v.dtype))


def sdpa_attention(
    q: jnp.ndarray,
    k: jnp.ndarray,
    v: jnp.ndarray,
    *,
    causal: bool = True,
    q_positions: jnp.ndarray | None = None,
    kv_positions: jnp.ndarray | None = None,
    return_lse: bool = False,
    sm_scale: float | None = None,
    window: int | None = None,
):
    """Scaled dot-product attention.

    q: [batch, q_len, q_heads, head_dim]
    k, v: [batch, kv_len, kv_heads, head_dim] — kv_heads may be smaller than
        q_heads (GQA); the expansion happens here, NOT in the caller, so
        parallel implementations (CP ring, flash kernel) can move/stream the
        small unexpanded K/V.
    q_positions/kv_positions: optional global position vectors; the causal
        mask is `q_pos >= kv_pos`, which generalizes to context-parallel
        shards where local index != global position.
    window: a sliding-window layer's band: position i sees j only where
        `0 <= i - j < window` (itself and the window - 1 before it).
        None = every j <= i.

    Returns out [batch, q_len, q_heads, head_dim] (and lse
    [batch, q_heads, q_len] fp32 if return_lse).
    """
    b, sq, h, d = q.shape
    sk = k.shape[1]
    if k.shape[2] != h:
        k = repeat_kv(k, h // k.shape[2])
        v = repeat_kv(v, h // v.shape[2])
    if sm_scale is None:
        sm_scale = 1.0 / (d ** 0.5)

    # [B, H, Sq, Sk] in fp32 for stable softmax
    scores = jnp.einsum("bqhd,bkhd->bhqk", q, k, preferred_element_type=jnp.float32)
    scores = scores * sm_scale

    if causal:
        qp = q_positions if q_positions is not None else jnp.arange(sq)
        kp = kv_positions if kv_positions is not None else jnp.arange(sk)
        mask = qp[:, None] >= kp[None, :]  # [Sq, Sk]
        if window is not None:
            mask &= qp[:, None] - kp[None, :] < window
        scores = jnp.where(mask[None, None, :, :], scores, -jnp.inf)
    elif window is not None:
        raise ValueError("a sliding window needs the causal mask")

    m = jnp.max(scores, axis=-1, keepdims=True)
    # Fully-masked rows (non-square blocks in the CP ring) have m = -inf and
    # l = 0; they must produce out = 0 with lse = -inf so the ring's LSE merge
    # assigns them zero weight — not NaN from 0/0 or exp(-inf - -inf).
    m_safe = jnp.maximum(m, -1e30)
    p = jnp.exp(scores - m_safe)
    l = jnp.sum(p, axis=-1, keepdims=True)
    l_safe = jnp.where(l == 0.0, 1.0, l)
    out = jnp.einsum("bhqk,bkhd->bqhd", (p / l_safe).astype(v.dtype), v,
                     preferred_element_type=jnp.float32).astype(q.dtype)
    # Named so the "dots" remat policy saves the attention output on this
    # reference path too (the flash path names its outputs inside the VJP
    # fwd rule — ops/flash_attention.py — so each impl names exactly once).
    out = checkpoint_name(out, "attn_out")
    if return_lse:
        lse = jnp.where(l == 0.0, -jnp.inf, m_safe + jnp.log(l_safe)).squeeze(-1)
        return out, checkpoint_name(lse, "attn_lse")  # lse: [B, H, Sq] fp32
    return out
