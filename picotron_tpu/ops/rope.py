"""Rotary position embeddings.

Matches the reference semantics (ref: picotron/model.py:12-31): non-interleaved
"rotate-half" RoPE with HF-compatible frequencies, tables computed in fp32 and
cast to the compute dtype at application time. One table pair serves all
layers of one kind (the reference recomputes identical tables per layer,
ref: model.py:199 — a pure waste we drop); a model whose sliding-window
and full layers rotate by different laws has a pair a kind
(`models.llama.model_rope_tables`).

For context parallelism each cp shard applies the table rows of its own
contiguous sequence slice (ref: context_parallel.py:189-195); callers pass the
global positions of their local tokens instead of slicing tables by hand.
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp


def llama3_scale_freqs(inv_freq: jnp.ndarray, factor: float = 8.0,
                       low_freq_factor: float = 1.0,
                       high_freq_factor: float = 4.0,
                       original_max_position: int = 8192) -> jnp.ndarray:
    """Llama-3.1-style RoPE frequency scaling (the `rope_scaling:
    {"rope_type": "llama3"}` of Llama-3.1/3.2 HF configs): long-wavelength
    frequencies are divided by `factor` (context extension), short
    wavelengths are kept, and the band between `high_freq_factor` and
    `low_freq_factor` wavelengths-per-original-context interpolates
    smoothly between the two."""
    wavelen = 2.0 * jnp.pi / inv_freq
    low_wl = original_max_position / low_freq_factor
    high_wl = original_max_position / high_freq_factor
    # smooth factor in [0, 1]: 1 at high-frequency end, 0 at low-frequency
    smooth = (original_max_position / wavelen - low_freq_factor) / (
        high_freq_factor - low_freq_factor)
    smooth = jnp.clip(smooth, 0.0, 1.0)
    scaled = jnp.where(
        wavelen > low_wl, inv_freq / factor,
        jnp.where(wavelen < high_wl, inv_freq,
                  (1 - smooth) * inv_freq / factor + smooth * inv_freq))
    return scaled


def yarn_scale_freqs(inv_freq: jnp.ndarray, head_dim: int, base: float,
                     factor: float, original_max_position: int,
                     beta_fast: float = 32.0,
                     beta_slow: float = 1.0) -> jnp.ndarray:
    """YaRN (Peng et al. 2023; transformers' `_compute_yarn_parameters`,
    `truncate` at its default): frequencies that turn more than
    `beta_fast` times over the original context are kept (extrapolated),
    those that turn fewer than `beta_slow` times are divided by `factor`
    (interpolated), and a linear ramp over the dimension index blends the
    two between. The attention factor multiplies cos and sin, not the
    frequencies (`rope_tables`)."""
    def dim_of(rotations: float) -> float:
        return (head_dim * math.log(original_max_position
                                    / (rotations * 2.0 * math.pi))
                / (2.0 * math.log(base)))

    low = max(math.floor(dim_of(beta_fast)), 0)
    high = min(math.ceil(dim_of(beta_slow)), head_dim - 1)
    span = max(high - low, 0.001)  # transformers' guard for low == high
    ramp = jnp.clip((jnp.arange(head_dim // 2, dtype=jnp.float32) - low)
                    / span, 0.0, 1.0)
    return (1.0 - ramp) * inv_freq + ramp * inv_freq / factor


def rope_tables(max_seq_len: int, head_dim: int, base: float = 10000.0,
                rope_scaling: dict | None = None):
    """Precompute cos/sin tables, shape [max_seq_len, head_dim // 2], fp32.

    `rope_scaling`: optional HF-style dict; supported `rope_type`s:
    "llama3" (Llama-3.1/3.2 frequency banding), "linear" (positions
    divided by `factor`) and "yarn" (`yarn_scale_freqs`; cos and sin are
    both multiplied by `attention_factor`, 0.1 ln(factor) + 1 where the
    key is absent) and "none" (no rotation: cos 1, sin 0)."""
    assert head_dim % 2 == 0, "head_dim must be even for RoPE"
    exponent = jnp.arange(0, head_dim, 2, dtype=jnp.float32) / head_dim
    inv_freq = 1.0 / (base ** exponent)  # [head_dim/2]
    amplitude = 1.0
    if rope_scaling:
        kind = rope_scaling.get("rope_type", rope_scaling.get("type"))
        if kind == "none":
            # a layer kind that is not rotated (`ModelConfig.rope_parameters`):
            # the identity's tables, so that no caller branches
            shape = (max_seq_len, head_dim // 2)
            return jnp.ones(shape, jnp.float32), jnp.zeros(shape, jnp.float32)
        if kind == "llama3":
            inv_freq = llama3_scale_freqs(
                inv_freq,
                factor=rope_scaling.get("factor", 8.0),
                low_freq_factor=rope_scaling.get("low_freq_factor", 1.0),
                high_freq_factor=rope_scaling.get("high_freq_factor", 4.0),
                original_max_position=rope_scaling.get(
                    "original_max_position_embeddings", 8192))
        elif kind == "linear":
            inv_freq = inv_freq / rope_scaling.get("factor", 1.0)
        elif kind == "yarn":
            factor = float(rope_scaling["factor"])
            inv_freq = yarn_scale_freqs(
                inv_freq, head_dim, base, factor,
                int(rope_scaling["original_max_position_embeddings"]),
                float(rope_scaling.get("beta_fast", 32.0)),
                float(rope_scaling.get("beta_slow", 1.0)))
            amplitude = float(rope_scaling.get("attention_factor")
                              or 0.1 * math.log(factor) + 1.0)
        else:
            raise ValueError(
                f"unsupported rope_scaling type {kind!r} (supported: "
                f"'llama3', 'linear', 'yarn', 'none')")
    positions = jnp.arange(max_seq_len, dtype=jnp.float32)[:, None]  # [S, 1]
    angles = positions * inv_freq[None, :]  # [S, head_dim/2]
    if amplitude != 1.0:
        return jnp.cos(angles) * amplitude, jnp.sin(angles) * amplitude
    return jnp.cos(angles), jnp.sin(angles)


def apply_rope(x: jnp.ndarray, cos: jnp.ndarray, sin: jnp.ndarray,
               positions: jnp.ndarray | None = None) -> jnp.ndarray:
    """Apply rotate-half RoPE.

    x:    [batch, seq, heads, head_dim]
    cos/sin: [max_seq, head_dim/2] tables from `rope_tables`; tables of
        fewer columns rotate the head's leading 2 x columns dimensions and
        leave the rest as they are (a partly rotated head)
    positions: optional [seq] global positions of the local tokens (for CP
        shards); defaults to 0..seq-1.

    Equivalent to the reference's `x * cos + rotate_half(x) * sin` with
    `cos/sin` repeated (1,2) (ref: model.py:12-19,31) — written on the
    half-tables directly so no materialized repeat is needed.
    """
    seq_len = x.shape[1]
    if positions is None:
        if seq_len > cos.shape[0]:
            raise ValueError(
                f"sequence length {seq_len} exceeds the RoPE table length "
                f"{cos.shape[0]} (max_position_embeddings)"
            )
        c = cos[:seq_len]
        s = sin[:seq_len]
    else:
        # Bounds-check when positions are concrete (tracers — e.g. computed
        # from axis_index inside shard_map — can't be checked at trace time;
        # out-of-range gathers would silently clamp). The max itself can
        # come back traced even for a concrete `positions` when this runs
        # under an outer trace (a scan body closing over constant
        # positions), so concreteness is probed by attempting the int()
        # conversion — the public spelling (jax.errors) of the old
        # `isinstance(..., jax.core.Tracer)` checks, whose semi-private
        # namespace the shardcheck source lint forbids (ADVICE r5).
        try:
            pmax = int(jnp.max(positions))
        except jax.errors.ConcretizationTypeError:
            pmax = None  # traced: checkable only at runtime
        if pmax is not None and pmax >= cos.shape[0]:
            raise ValueError(
                f"position {pmax} exceeds the RoPE table length "
                f"{cos.shape[0]}")
        c = cos[positions]
        s = sin[positions]
    c = c[None, :, None, :]  # [1, S, 1, D/2]
    s = s[None, :, None, :]
    return rotate_half(x, c, s)


def rotate_half(x: jnp.ndarray, c: jnp.ndarray, s: jnp.ndarray) -> jnp.ndarray:
    """x's leading 2 x c.shape[-1] dimensions rotated by (c, s), which
    broadcast against x's leading axes; the dimensions behind them pass
    through (all of a wholly rotated head is rotated)."""
    half = c.shape[-1]
    x1 = x[..., :half]
    x2 = x[..., half:2 * half]
    # (x1, x2) * repeat(cos,2) + (-x2, x1) * repeat(sin,2)
    out1 = x1 * c - x2 * s
    out2 = x2 * c + x1 * s
    rest = [x[..., 2 * half:]] if x.shape[-1] > 2 * half else []
    return jnp.concatenate([out1, out2, *rest], axis=-1).astype(x.dtype)
