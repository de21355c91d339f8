"""Llama-family decoder-only model, written as pure functions over a param
pytree (capability parity with ref: picotron/model.py:227-272).

Architecture: Embedding -> N x (RMSNorm -> GQA-Attention -> residual ->
RMSNorm -> SwiGLU-MLP -> residual) -> final RMSNorm -> untied LM head
(ref: model.py:204-209, 265-272).

TPU-first design decisions (vs the reference's nn.Module tree):

- **Stacked layer params.** All decoder layers live in one pytree with a
  leading layer axis, so the layer loop is a `lax.scan` — one traced layer
  body, O(1) compile time in depth, and the pipeline-parallel stage slice is
  literally `tree_map(lambda x: x[stage_lo:stage_hi], layers)`.
- **Parallelism is injected, not hard-coded.** The model never reads env vars
  (the reference dispatches attention through `CONTEXT_PARALLEL`/`FLASH_ATTEN`
  env flags, ref: model.py:148-158). Instead a `ParallelCtx` carries the
  attention implementation and the TP/CP collective hooks; the single-device
  defaults are identities, and shard_map-level code swaps in psum/ppermute
  versions. Head counts are derived from the *local* weight shapes, so the
  same forward runs unsharded or TP-sharded unchanged.
- **fp32 master params, bf16 compute.** Params are stored fp32 and cast to
  the compute dtype at use; autodiff then naturally yields fp32 gradients
  (the reference gets this with a separate fp32 `main_grad` buffer system,
  ref: data_parallel.py:66-144).
- **Init matches the reference exactly** (ref: model.py:110-120, 173-182,
  221-222, 48-49): linear weights ~ U(±sqrt(1/fan_in)), embedding ~ N(0,1),
  norm weights = 1, untied head.
"""

from __future__ import annotations

import math
from dataclasses import dataclass
from functools import partial
from typing import Any, Callable, Optional

import jax
import jax.numpy as jnp
from jax.ad_checkpoint import checkpoint_name

from picotron_tpu.config import (
    GDN, KDA, MOE, RECURRENT, SSD, SSM, Block, ModelConfig, pattern_of,
    refuse_training,
)
from picotron_tpu.ops.attention import sdpa_attention
from picotron_tpu.ops.eva import chunk_summaries, eva_attention
from picotron_tpu.ops.gated_delta import causal_conv, gated_delta, l2_normalise
from picotron_tpu.ops.kda import kda
from picotron_tpu.ops.losses import cross_entropy, cross_entropy_sum_count
from picotron_tpu.ops.mla import mla_project, up_weights
from picotron_tpu.ops.rmsnorm import rms_norm
from picotron_tpu.ops.rope import apply_rope, rope_tables
from picotron_tpu.ops.selective_scan import scan_segment, tail_shape
from picotron_tpu.ops.ssd import ssd
from picotron_tpu.telemetry.scopes import scope


def model_rope_tables(cfg, max_len=None):
    """RoPE tables for a model config, honoring cfg.rope_scaling
    (Llama-3.1/3.2). All model-level paths must build tables through this
    helper so scaling cannot be silently dropped on one path.

    (cos, sin), each one table for a model whose layers all rotate by one
    law, or a dict {layer kind: table} for one that publishes a law a kind
    (`rope_parameters`: Mellum2's full layers rotate by YaRN, its sliding
    layers unscaled; K-EXAONE's full layers do not rotate, which is the
    identity's tables). `kind_tables` picks a layer's pair from either.
    (None, None) for a model whose latent attention does not rotate
    (`mla_use_nope`): nothing reads a table there, and none is built."""
    if cfg.mla_use_nope:
        return None, None
    n = max_len or cfg.max_position_embeddings
    if not cfg.rope_parameters:
        # rope_dim: the whole head, or latent attention's shared rotated
        # dimensions
        return rope_tables(n, cfg.rope_dim, cfg.rope_theta,
                           rope_scaling=cfg.rope_scaling_dict)
    pairs = {}
    for kind in sorted(set(cfg.layer_kinds) - set(RECURRENT) - {MOE}):
        theta, scaling = cfg.rope_law(kind)
        pairs[kind] = rope_tables(n, cfg.head_dim, theta,
                                  rope_scaling=scaling)
    return ({k: p[0] for k, p in pairs.items()},
            {k: p[1] for k, p in pairs.items()})


def kind_tables(cos, sin, kind: str):
    """The (cos, sin) a layer of `kind` rotates by, out of what
    `model_rope_tables` returned."""
    return (cos[kind], sin[kind]) if isinstance(cos, dict) else (cos, sin)


def layer_window(cfg, kind: str):
    """The band of a layer of `kind`: `sliding_window` positions on a
    sliding layer, None (every earlier position) on a full one."""
    return cfg.sliding_window if kind == "sliding_attention" else None


def by_period(layer_tree, period: int, whole: int):
    """The `whole` whole periods of a [L, ...]-stacked layer tree as
    [whole, period, ...]: what a scan over whole periods of the layer
    pattern iterates over. The rows after them (the layers left over, or
    those of them that hold the leaf) are the caller's to run
    (`pattern_of`)."""
    return jax.tree.map(
        lambda x: x[:whole * period].reshape(whole, period, *x.shape[1:]),
        layer_tree)


# A stack whose layers are of two kinds of mixer (softmax attention and a
# recurrent one: Gated DeltaNet or Mamba) holds each mixer's leaves stacked
# over the layers of ITS kind alone, in their order, beside the leaves every
# layer has (the norms, the MLP or the experts), stacked over all of them:
# no layer carries the other kind's matrices. These are the softmax
# attention's leaves (a latent attention's among them); a Gated DeltaNet
# mixer's are named `gdn_...`, a Mamba mixer's `ssm_...`, a Kimi Delta
# Attention mixer's `kda_...`, a Mamba-2 mixer's `ssd_...`. In a stack whose
# layers are ONE sublayer each (`one_each`: kinds "mamba2" / "experts" /
# "full_attention") a layer holds its one sublayer's leaves and the one norm
# in front of it, `input_norm`, which alone is stacked over every layer: the
# experts' leaves (router, banks, latent projections, shared expert) are
# stacked over the "experts" layers alone.
OWN_PREFIX = {GDN: "gdn_", SSM: "ssm_", KDA: "kda_", SSD: "ssd_"}
ATTENTION_LEAVES = ("q", "k", "v", "o", "q_norm", "k_norm", "b_q", "b_k",
                    "b_v", "q_a", "q_a_norm", "q_b", "kv_a", "kv_a_norm",
                    "kv_b")


def one_each(kinds) -> bool:
    """Whether a run of layers of `kinds` is layers of one sublayer each
    (`config.Block.alone`): a kind of "mamba2" or "experts" says so."""
    return SSD in kinds or MOE in kinds


def own_leaf(name: str, alone: bool = False) -> bool:
    """Whether `name` is a leaf of one kind of layer, stacked over the
    layers of that kind alone: the softmax attention's or a recurrent
    mixer's, and in a stack of layers of one sublayer each (`alone`) every
    leaf but the one norm."""
    return (name.startswith(tuple(OWN_PREFIX.values()))
            or name in ATTENTION_LEAVES or (alone and name != "input_norm"))


def holds(name: str, kind: str, alone: bool = False) -> bool:
    """Whether a layer of `kind` holds the stack's leaf `name`. Every layer
    holds every leaf of a stack without recurrent mixers; in a stack of
    layers of one sublayer each (`alone`) only an "experts" layer holds what
    is neither a mixer's nor the norm."""
    for own, prefix in OWN_PREFIX.items():
        if name.startswith(prefix):
            return kind == own
    if name in ATTENTION_LEAVES:
        return kind not in RECURRENT and kind != MOE
    return not alone or name == "input_norm" or kind == MOE


def leaf_row(name: str, kinds: tuple, i: int) -> int:
    """The row of leaf `name` that layer `i` of a run of layers of `kinds`
    reads: the layers before it that hold the leaf (i, where all do)."""
    alone = one_each(kinds)
    return sum(holds(name, k, alone) for k in kinds[:i])


def layer_leaves(stack, kinds: tuple, i: int):
    """Layer i's leaves out of a stack (or a period of one) of layers of
    `kinds`: each leaf it holds, at the leaf's row for it."""
    alone = one_each(kinds)
    return {n: w[leaf_row(n, kinds, i)] for n, w in stack.items()
            if holds(n, kinds[i], alone)}

Params = dict[str, Any]


# ---------------------------------------------------------------------------
# Parallel context — how parallelism plugs into the model
# ---------------------------------------------------------------------------


def _identity(x):
    return x


def _default_attn(q, k, v, positions, rope, window=None):
    # q/k arrive unrotated: each attention impl owns RoPE so the flash path
    # can rotate inside its kernels (parallel/api.py) while reference paths
    # use the jnp rotation. `window`: a sliding layer's band; the only
    # impl that has one (Config.validate refuses the others by name).
    q = apply_rope(q, *rope, positions)
    k = apply_rope(k, *rope, positions)
    return sdpa_attention(q, k, v, causal=True, window=window,
                          q_positions=positions, kv_positions=positions)


@dataclass(frozen=True)
class ParallelCtx:
    """Hooks that parallel wrappers override; defaults are single-device.

    f / g are Megatron's column-parallel entry / row-parallel exit collectives
    (ref: tp_communications.py:19-49): `f` = identity fwd / psum bwd, applied
    to activations entering column-parallel matmuls; `g` = psum fwd / identity
    bwd, applied to row-parallel matmul outputs.
    """

    # attention impl: (q, k, v, positions) -> out, all [B, S, H_local, D]
    attn: Callable = _default_attn
    # TP collectives
    f: Callable = _identity
    g: Callable = _identity
    # embedding lookup (vocab-parallel TP overrides this)
    embed_lookup: Optional[Callable] = None
    # fused head+CE returning (nll_sum, valid_count) (vocab-parallel TP
    # overrides to avoid full-logit gather)
    head_ce: Optional[Callable] = None
    # collective-free/merge split of head_ce for the pipeline engines' gated
    # last-stage scoring (parallel/tp.py vocab_parallel_ce_local_stats /
    # _merge); None when the split is unavailable (sequence parallelism —
    # its seq gather cannot live inside a divergent branch) and the engines
    # fall back to uniform masked scoring
    head_ce_local: Optional[Callable] = None
    head_ce_merge: Optional[Callable] = None
    # logits gather for eval under TP
    gather_logits: Callable = _identity
    # global positions of this shard's tokens [S_local] (context parallelism;
    # None = 0..S-1)
    positions: Optional[jnp.ndarray] = None
    # factor by which the residual stream's sequence dim is sharded relative
    # to the input ids (sequence parallelism: tp_size; otherwise 1). Pipeline
    # boundary buffers are sized S_local / seq_shard.
    seq_shard: int = 1
    # mesh axis for MoE expert parallelism ("ep" inside the composed step);
    # None = no all_to_all (single device, or outside shard_map)
    moe_ep_axis: Optional[str] = None
    # mesh axes to pmean router statistics over (layout-exact global aux;
    # config.router_aux_global) — None = per-device statistics
    moe_stat_axes: Optional[tuple] = None
    # makes the MoE aux-loss scalar tp-INVARIANT under sequence parallelism
    # (every tp rank computes it from the same gathered tokens, but the
    # gather's output is typed tp-varying; a pmean re-establishes the
    # replication so the loss fold stays tp-clean)
    moe_aux_sync: Callable = _identity
    # gradient checkpointing over decoder layers
    remat: bool = False
    # "full" | "dots" (save matmul outputs, recompute elementwise only)
    remat_policy: str = "dots"
    # (n_slots) -> float32[n_slots] mask of REAL (non-pad) layer slots in
    # this device's stacked-layer slice. Uneven-PP padding adds all-zero
    # identity layers (pp_layer_placement); their router statistics must not
    # enter the MoE aux loss / drop metric, and the mask is derived from the
    # STATIC placement (stage index + remainder rule), not from sniffing
    # router weights — a legitimately zero-initialized router would
    # otherwise lose its balance/z gradients silently (ADVICE r3). None =
    # every slot is real.
    layer_is_real: Optional[Callable] = None


DEFAULT_CTX = ParallelCtx()


# ---------------------------------------------------------------------------
# Initialization
# ---------------------------------------------------------------------------


def _uniform_fan_in(key, fan_in: int, shape) -> jnp.ndarray:
    bound = (1.0 / fan_in) ** 0.5
    return jax.random.uniform(key, shape, jnp.float32, -bound, bound)


def _norm_init(cfg: ModelConfig, shape) -> jnp.ndarray:
    """A block norm's weight at its start: the scale is 1 either way
    (`norm_weight`)."""
    return (jnp.zeros if cfg.norm_add_unit_offset else jnp.ones)(
        shape, jnp.float32)


def _init_stack(cfg: ModelConfig, block: Block, nl: int,
                key: jax.Array, kinds: tuple = ()) -> Params:
    """One stack of `nl` layers of one kind of block (`cfg.stacks`), fp32,
    stacked on a leading layer axis. The `layers` stack of a model of one
    kind draws what it always drew from `key`. `kinds`: the layers' kinds
    (`Stack.kinds`): the softmax attention's leaves are stacked over the
    layers that are not Gated DeltaNet mixers, the mixers' over those that
    are (`holds`)."""
    h = cfg.hidden_size
    i = cfg.intermediate_size
    d = cfg.head_dim
    q_out = cfg.num_attention_heads * d
    kv_out = cfg.num_key_value_heads * d
    n_gdn = tuple(kinds).count(GDN)
    n_ssm = tuple(kinds).count(SSM)
    n_kda = tuple(kinds).count(KDA)
    n_ssd = tuple(kinds).count(SSD)
    n_moe = tuple(kinds).count(MOE)
    # layers with a softmax attention
    na = nl - n_gdn - n_ssm - n_kda - n_ssd - n_moe
    # layers with an MLP: all of them, or the "experts" layers of a stack of
    # layers of one sublayer each
    ne = n_moe if block.alone else nl

    keys = jax.random.split(key, 14)
    # a layer of two (attention, dense MLP) pairs: every leaf of a pair has
    # a sublayer axis behind the layer axis, [nl, 2, ...]
    pair = (block.attentions,) if block.mlp == "shortcut" else ()

    def stacked(k, fan_in, shape, n=nl):
        ks = jax.random.split(k, n)
        return jnp.stack([_uniform_fan_in(ks[j], fan_in, shape) for j in range(n)])

    def paired(k, fan_in, shape, n=nl):
        return stacked(k, fan_in, pair + shape, n)

    layers = {"input_norm": _norm_init(cfg, (nl,) + pair + (h,))}
    if not block.alone:  # (a layer of one sublayer has one norm)
        layers["post_norm"] = _norm_init(cfg, (nl,) + pair + (h,))
    if block.sandwich:
        # norms on the attention's and the MLP's outputs (`post_norm` is
        # the MLP's input norm, as everywhere)
        layers.update({
            "attn_out_norm": jnp.ones((nl, h), jnp.float32),
            "mlp_out_norm": jnp.ones((nl, h), jnp.float32),
        })
    # (a stack of recurrent mixers alone, na = 0, holds no attention's leaf)
    if block.attn == "mla" and na:
        heads, rank, ql = (cfg.num_attention_heads, cfg.kv_lora_rank,
                           cfg.q_lora_rank)
        dn, dr, dv = (cfg.qk_nope_head_dim, cfg.qk_rope_head_dim,
                      cfg.v_head_dim)
        mk = jax.random.split(keys[1], 4)
        # (over the layers that hold an attention: `na`, all of them but
        # beside recurrent mixers)
        if ql:
            layers.update({
                "q_a": paired(mk[0], h, (h, ql), na),
                "q_a_norm": jnp.ones((na,) + pair + (ql,), jnp.float32),
            })
        layers.update({
            # without a bottleneck (q_lora_rank 0) q comes out of q_b alone
            "q_b": paired(mk[1], ql or h, (ql or h, heads * (dn + dr)), na),
            # [c | k_r]: the latent and the shared rotated dimensions
            "kv_a": paired(mk[2], h, (h, rank + dr), na),
            "kv_a_norm": jnp.ones((na,) + pair + (rank,), jnp.float32),
            # a head's [k_n | v] columns side by side (ops/mla.py)
            "kv_b": paired(mk[3], rank, (rank, heads * (dn + dv)), na),
            "o": paired(keys[4], heads * dv, (heads * dv, h), na),
        })
    elif block.attn != "mla" and na:
        # a gated attention's q holds each head's query, then its gate
        gated = 2 if cfg.attn_output_gate else 1
        layers.update({
            "q": stacked(keys[1], h, (h, gated * q_out), na),
            "k": stacked(keys[2], h, (h, kv_out), na),
            "v": stacked(keys[3], h, (h, kv_out), na),
            "o": stacked(keys[4], q_out, (q_out, h), na),
        })
    if n_gdn:
        hv, dv = cfg.linear_num_value_heads, cfg.linear_value_head_dim
        c, kern = cfg.gdn_channels, cfg.linear_conv_kernel_dim
        gk = jax.random.split(keys[13], 6)
        # a step's dt = softplus(dt_bias) log-uniform in [0.001, 0.1], as the
        # gated delta rule's authors start it (dt_bias its inverse softplus):
        # with A = U(0, 16) a step keeps exp(-A dt) of the state, 0.49 to
        # 0.996 over the middle nine tenths of the heads, so a state carries
        # tens to thousands of positions. (dt_bias = 1, the released
        # modelling code's placeholder, keeps under half in 31 heads of 32:
        # a mixer without a memory.)
        step = jnp.exp(jax.random.uniform(gk[5], (n_gdn, hv), jnp.float32,
                                          math.log(1e-3), math.log(1e-1)))
        layers.update({
            # [q | k | v | z]: the convolved channels, then the output gate
            "gdn_qkvz": stacked(gk[0], h, (h, c + hv * dv), n_gdn),
            "gdn_ba": stacked(gk[1], h, (h, 2 * hv), n_gdn),  # [b | a]
            "gdn_conv": stacked(gk[2], kern, (c, kern), n_gdn),
            # decays where a trained model's lie, neither all 1 nor all 0
            # (`assumed`: A = U(0, 16), the released code's draw)
            "gdn_A_log": jnp.log(jax.random.uniform(
                gk[3], (n_gdn, hv), jnp.float32, 1e-3, 16.0)),
            "gdn_dt_bias": step + jnp.log(-jnp.expm1(-step)),
            "gdn_norm": jnp.ones((n_gdn, dv), jnp.float32),  # a plain weight
            "gdn_out": stacked(gk[4], hv * dv, (hv * dv, h), n_gdn),
        })
    if n_kda:
        hv, dk, dv = (cfg.linear_num_value_heads, cfg.linear_key_head_dim,
                      cfg.linear_value_head_dim)
        c, kern = cfg.gdn_channels, cfg.linear_conv_kernel_dim
        kk = jax.random.split(keys[13], 10)
        # a step's dt = softplus(dt_bias + ...) around a draw log-uniform in
        # [0.001, 0.1] a CHANNEL of the key (dt_bias its inverse softplus)
        # and A = U(1, 16) a head (the released initialiser's): a step keeps
        # exp(-A dt) of a state's row, 0.37 to 0.999 over the middle nine
        # tenths of the channels, so a state carries a few to thousands of
        # positions, its channels side by side (a placeholder dt_bias makes
        # a mixer without a memory: PERF.md section 6, PR 51)
        step = jnp.exp(jax.random.uniform(kk[9], (n_kda, hv * dk), jnp.float32,
                                          math.log(1e-3), math.log(1e-1)))
        layers.update({
            "kda_qkv": stacked(kk[0], h, (h, c), n_kda),     # [q | k | v]
            "kda_conv": stacked(kk[1], kern, (c, kern), n_kda),
            # the decay's low-rank projection, hidden -> d_v -> heads x d_k
            "kda_f_a": stacked(kk[2], h, (h, dv), n_kda),
            "kda_f_b": stacked(kk[3], dv, (dv, hv * dk), n_kda),
            "kda_dt_bias": step + jnp.log(-jnp.expm1(-step)),
            "kda_A_log": jnp.log(jax.random.uniform(
                kk[4], (n_kda, hv), jnp.float32, 1.0, 16.0)),
            "kda_beta": stacked(kk[5], h, (h, hv), n_kda),
            # the output gate's, hidden -> d_v -> heads x d_v
            "kda_g_a": stacked(kk[6], h, (h, dv), n_kda),
            "kda_g_b": stacked(kk[7], dv, (dv, hv * dv), n_kda),
            "kda_norm": jnp.ones((n_kda, dv), jnp.float32),  # a plain weight
            "kda_out": stacked(kk[8], hv * dv, (hv * dv, h), n_kda),
        })
    if n_ssm:
        di, n, r = cfg.ssm_inner, cfg.mamba_d_state, cfg.mamba_dt_rank
        sk = jax.random.split(keys[13], 7)
        # a step's dt = softplus(b_dt + W_dt r) around a draw log-uniform in
        # [0.001, 0.1] (Gu and Dao's Mamba initialiser; b_dt its inverse
        # softplus), A = -(1 .. d_state) a channel and D = 1 (the released
        # initialiser's): a step keeps exp(dt A) of a (channel, state) pair,
        # 0.43 to 0.998 over the middle nine tenths of the pairs, so a state
        # carries tens to thousands of positions
        step = jnp.exp(jax.random.uniform(sk[6], (n_ssm, di), jnp.float32,
                                          math.log(1e-3), math.log(1e-1)))
        layers.update({
            "ssm_in": stacked(sk[0], h, (h, 2 * di), n_ssm),      # [u | z]
            "ssm_conv": stacked(sk[1], cfg.mamba_d_conv,
                                (di, cfg.mamba_d_conv), n_ssm),
            "ssm_x": stacked(sk[3], di, (di, r + 2 * n), n_ssm),  # [r | B | C]
            "ssm_dt_norm": jnp.ones((n_ssm, r), jnp.float32),
            "ssm_b_norm": jnp.ones((n_ssm, n), jnp.float32),
            "ssm_c_norm": jnp.ones((n_ssm, n), jnp.float32),
            "ssm_dt": stacked(sk[4], r, (r, di), n_ssm),
            "ssm_dt_bias": step + jnp.log(-jnp.expm1(-step)),
            "ssm_A_log": jnp.broadcast_to(
                jnp.log(jnp.arange(1, n + 1, dtype=jnp.float32)),
                (n_ssm, di, n)),
            "ssm_D": jnp.ones((n_ssm, di), jnp.float32),
            "ssm_out": stacked(sk[5], di, (di, h), n_ssm),
        })
        if cfg.mamba_conv_bias:
            layers["ssm_conv_bias"] = stacked(
                sk[2], cfg.mamba_d_conv, (di,), n_ssm)
    if n_ssd:
        di, c, hm = cfg.ssd_inner, cfg.ssd_channels, cfg.mamba_num_heads
        mk = jax.random.split(keys[13], 6)
        # a step's d = softplus(dt + dt_bias) around a draw log-uniform in
        # [0.001, 0.1] (the Mamba-2 initialiser's time_step_min / max;
        # dt_bias its inverse softplus) and A = -U(1, 16) a head: a step keeps
        # exp(d A) of a head's state, 0.37 to 0.999 over the middle nine
        # tenths of the heads, so a state carries a few to thousands of
        # positions (a placeholder dt_bias makes a mixer without a memory:
        # PERF.md section 6, PR 51); D = 1
        step = jnp.exp(jax.random.uniform(mk[5], (n_ssd, hm), jnp.float32,
                                          math.log(1e-3), math.log(1e-1)))
        layers.update({
            # [z | x B C | dt]: the gate, the convolved channels, the step
            "ssd_in": stacked(mk[0], h, (h, di + c + hm), n_ssd),
            "ssd_conv": stacked(mk[1], cfg.mamba_d_conv,
                                (c, cfg.mamba_d_conv), n_ssd),
            "ssd_dt_bias": step + jnp.log(-jnp.expm1(-step)),
            "ssd_A_log": jnp.log(jax.random.uniform(
                mk[3], (n_ssd, hm), jnp.float32, 1.0, 16.0)),
            "ssd_D": jnp.ones((n_ssd, hm), jnp.float32),
            "ssd_norm": jnp.ones((n_ssd, di), jnp.float32),  # a plain weight
            "ssd_out": stacked(mk[4], di, (di, h), n_ssd),
        })
        if cfg.mamba_conv_bias:
            layers["ssd_conv_bias"] = stacked(
                mk[2], cfg.mamba_d_conv, (c,), n_ssd)
    if block.attn == "eva":
        # EVA's pooling vectors, one a KV head: unit normal clamped to
        # [-1, 1] an element, so that a chunk's summary is far from its
        # mean (`assumed`: config.py, the EvaByte preset's comment)
        ek = jax.random.split(keys[13], 2)
        layers.update({
            name: jnp.clip(jax.random.normal(
                k, (nl, cfg.num_key_value_heads, d), jnp.float32), -1.0, 1.0)
            for name, k in zip(("eva_mu", "eva_phi"), ek)})
    if cfg.attention_bias:
        # Qwen2-style qkv bias (zero-init, the HF convention)
        layers.update({
            "b_q": jnp.zeros((nl, q_out), jnp.float32),
            "b_k": jnp.zeros((nl, kv_out), jnp.float32),
            "b_v": jnp.zeros((nl, kv_out), jnp.float32),
        })
    if cfg.qk_norm == "head":
        # K-EXAONE: RMSNorm weights over one head, shared by the heads
        layers.update({
            "q_norm": _norm_init(cfg, (na, d)),
            "k_norm": _norm_init(cfg, (na, d)),
        })
    elif cfg.qk_norm:
        # OLMoE: RMSNorm weights over the whole q / k projection
        layers.update({
            "q_norm": jnp.ones((nl, q_out), jnp.float32),
            "k_norm": jnp.ones((nl, kv_out), jnp.float32),
        })
    if block.mlp in ("experts", "shortcut") and ne:
        e, f = cfg.num_experts, cfg.expert_ffn_size
        w = cfg.expert_in_size  # the experts' own width: the latent's, or h
        layers.update({
            # router (over every expert of the model, held here or not) +
            # per-layer banks of the experts held [L, E, ...] (ops/moe.py)
            "router": stacked(keys[9], h, (h, cfg.router_width), ne),
            "w_up": stacked(keys[6], w, (e, w, f), ne),
            "w_down": stacked(keys[7], f, (e, f, w), ne),
        })
        if cfg.mlp_gated:  # (a "relu2" expert is down(relu(up x)^2))
            layers["w_gate"] = stacked(keys[5], w, (e, w, f), ne)
        if cfg.moe_latent_size:
            lk = jax.random.split(jax.random.fold_in(keys[9], 1), 2)
            layers.update({
                "latent_down": stacked(lk[0], h, (h, w), ne),
                "latent_up": stacked(lk[1], w, (w, h), ne),
            })
        if cfg.n_shared_experts:
            fs = cfg.shared_ffn_size
            layers.update({
                "shared_up": stacked(keys[11], h, (h, fs), ne),
                "shared_down": stacked(keys[12], fs, (fs, h), ne),
            })
            if cfg.mlp_gated:
                layers["shared_gate"] = stacked(keys[10], h, (h, fs), ne)
            if cfg.shared_expert_gate:
                layers["shared_out_gate"] = stacked(
                    jax.random.fold_in(keys[12], 1), h, (h,))
        if cfg.moe_selection_bias:
            # zeros, as a released checkpoint's buffer starts
            layers["router_bias"] = jnp.zeros((ne, cfg.router_width),
                                              jnp.float32)
    if block.mlp != "experts" and not block.alone:
        dk = jax.random.split(keys[10], 3) if pair else keys[5:8]
        layers.update({
            "gate": paired(dk[0], h, (h, i)),
            "up": paired(dk[1], h, (h, i)),
            "down": paired(dk[2], i, (i, h)),
        })
    return layers


# the leaves of a "shortcut" block's expert branch: one a layer, where every
# other leaf of the stack is one a sublayer
BRANCH = ("router", "router_bias", "w_gate", "w_up", "w_down")


def sublayer(lp, j: int):
    """A layer of two (attention, dense MLP) pairs as pair `j` sees it: the
    pair's own leaves (index j of their sublayer axis) under the names they
    have in a layer of one pair, and the expert branch's as they are."""
    return {n: (w if n in BRANCH else w[j]) for n, w in lp.items()}


def init_params(cfg: ModelConfig, key: jax.Array) -> Params:
    """Full (unsharded) parameter pytree, fp32.

    Layer weights are stacked on a leading layer axis, one stack a kind of
    block (`cfg.stacks`: `layers`, and `dense_layers` before it in a model
    with leading dense layers). Matmul weights are stored [in_features,
    out_features] (x @ w convention).
    """
    h = cfg.hidden_size
    v = cfg.vocab_size

    keys = jax.random.split(key, 14)
    # the last stack (`layers`) draws from `key` itself, as the one stack of
    # a model of one kind of block always did; a stack before it from a fold
    stacks = {st.name: _init_stack(cfg, st.block, st.layers,
                                   jax.random.fold_in(key, j) if j else key,
                                   st.kinds)
              for j, st in enumerate(reversed(cfg.stacks))}

    params = {
        "embedding": jax.random.normal(keys[0], (v, h), jnp.float32),
        **stacks,
        "final_norm": _norm_init(cfg, (h,)),
    }
    if not cfg.tie_word_embeddings:
        # num_pred_heads heads side by side: head j in columns j v .. (j+1) v
        params["lm_head"] = _uniform_fan_in(
            keys[8], h, (h, v * cfg.num_pred_heads))
    return params


def head_weight(params: Params) -> jnp.ndarray:
    # The LM-head matrix [H, V(/tp)]: the separate lm_head when the model
    # unties (the Llama family), else the transposed embedding (Qwen2-style
    # tying; gradients flow to the embedding through both uses, and under
    # TP the vocab-sharded [V/tp, H] embedding shard transposes to exactly
    # the head's [H, V/tp] layout).
    w = params.get("lm_head")
    return w if w is not None else params["embedding"].T


def served_head(params: Params, cfg: ModelConfig) -> jnp.ndarray:
    """The head a served token is sampled from: head 0 of a head of several
    prediction heads (its first vocab_size columns; the others draft, which
    is not built), else the whole head. The serve programs multiply these
    columns and no others."""
    w = head_weight(params)
    return w[:, :cfg.vocab_size] if cfg.num_pred_heads > 1 else w


def norm_weight(w, cfg: ModelConfig):
    """The scale of a block norm (input, post, final): `1 + w` where the
    config says so (norm_add_unit_offset). A transform of the weight, not a
    second norm: every site calls `rms_norm` with it. In float32, as the
    norm takes it: 1 + w in bfloat16 keeps 8 bits of w."""
    return 1.0 + w.astype(jnp.float32) if cfg.norm_add_unit_offset else w


def param_count(params: Params) -> int:
    return sum(x.size for x in jax.tree_util.tree_leaves(params))


# ---------------------------------------------------------------------------
# Uneven pipeline layer distribution (ref: pipeline_parallel.py:42-51)
# ---------------------------------------------------------------------------


def pp_layer_placement(num_layers: int, pp: int):
    """(padded_size, slot_index[num_layers]) for an uneven layer split.

    The stacked layer axis is padded to pp * ceil(L/pp) so P('pp') divides
    evenly; stage k holds L//pp (+1 for the first L%pp stages — remainder to
    early stages, the reference's distribute_layers rule) real layers in its
    leading slots. Pad slots hold all-zero layer params, which make the
    decoder layer an *exact identity with exactly-zero gradients*: the
    residual passes through, every projection output is 0, and every pad
    param's grad is 0 (each flows through a zero activation or zero weight),
    so Adam(+wd) keeps pads at zero forever. No masking needed anywhere.
    """
    import numpy as np

    per = -(-num_layers // pp)  # ceil
    counts = [num_layers // pp + (1 if k < num_layers % pp else 0)
              for k in range(pp)]
    slots = np.concatenate([
        np.arange(k * per, k * per + counts[k]) for k in range(pp)
    ]).astype(np.int32)
    return per * pp, slots


def pad_layers_for_pp(params: Params, num_layers: int, pp: int) -> Params:
    """Scatter the canonical [L]-stacked layer tree into its [Lp] padded
    layout (identity when L % pp == 0)."""
    padded, slots = pp_layer_placement(num_layers, pp)
    if padded == num_layers:
        return params

    def pad(x):
        out = jnp.zeros((padded,) + x.shape[1:], x.dtype)
        return out.at[slots].set(x)

    return {**params, "layers": jax.tree.map(pad, params["layers"])}


def unpad_layers(params: Params, num_layers: int, pp: int) -> Params:
    """Inverse of pad_layers_for_pp: gather back the canonical [L] stack."""
    padded, slots = pp_layer_placement(num_layers, pp)
    if padded == num_layers:
        return params
    return {**params,
            "layers": jax.tree.map(lambda x: x[slots], params["layers"])}


# ---------------------------------------------------------------------------
# Forward pieces (granular so PP schedules can compose them)
# ---------------------------------------------------------------------------


def compute_dtype(cfg: ModelConfig):
    """Activation/compute dtype for this model config (params stay fp32)."""
    return jnp.bfloat16 if cfg.dtype == "bfloat16" else jnp.float32


def embed(params: Params, input_ids: jnp.ndarray, cfg: ModelConfig,
          ctx: ParallelCtx = DEFAULT_CTX) -> jnp.ndarray:
    """Token embedding -> [B, S, H] in compute dtype."""
    w = params["embedding"]
    with scope("embed"):
        if ctx.embed_lookup is not None:
            x = ctx.embed_lookup(w, input_ids)
        else:
            x = w[input_ids]
        return residual_stream(x.astype(compute_dtype(cfg)), cfg)


def residual_stream(x, cfg: ModelConfig):
    """The residual stream the blocks add to: float32 where the config says
    so (fp32_skip_add), else as it is. A block's norm hands its matmuls the
    compute dtype either way."""
    return x.astype(jnp.float32) if cfg.fp32_skip_add else x


def qkv_proj(h, lp, d: int, eps: float = 1e-5, keep_flat: bool = False,
             qk_norm: bool = True):
    """Shared q/k/v projection (+ optional Qwen2 bias, tp-sharded with its
    output features; + optional QK-norm where the layer has `q_norm` /
    `k_norm` weights, an RMSNorm with `eps` before RoPE: over the WHOLE
    projected q and k vectors before the head split where the weights are
    that wide (OLMoE), over each head's `d` numbers where they are `d`
    wide (K-EXAONE: one weight vector for all heads; with one head the two
    forms are one) — tp = 1 only, Config.validate)
    -> ([B,S,Hq,D], [B,S,Hkv,D], [B,S,Hkv,D]); local head
    counts come from the (possibly TP-sharded) weight shapes. One
    implementation for the training block, the fused grad engine's
    segment VJP AND the KV-cache decode path (generate.py) so
    attention-input changes cannot silently diverge. `keep_flat` (the
    decode path's): the flat projections stay values of their own, see
    below. `qk_norm` false: the caller norms q and k itself (a gated
    attention, whose q holds more than the query)."""
    dt = h.dtype
    b, s, _ = h.shape
    q = h @ lp["q"].astype(dt)
    k = h @ lp["k"].astype(dt)
    v = h @ lp["v"].astype(dt)
    if "b_q" in lp:
        q = q + lp["b_q"].astype(dt)
        k = k + lp["b_k"].astype(dt)
        v = v + lp["b_v"].astype(dt)
    normed = qk_norm and "q_norm" in lp
    per_head = normed and lp["q_norm"].shape[-1] == d
    if normed and not per_head:
        q = rms_norm(q, lp["q_norm"], eps)
        k = rms_norm(k, lp["k_norm"], eps)
    # checkpoint-name the FLAT [B, S, H*D] projections, BEFORE the head
    # reshape: saved activations inherit the flat matmul layout, whose
    # (8, 128)-tiled minor dim is H*D. Naming the reshaped [B, S, H, 64]
    # form instead makes the remat policies store tensors whose 64-wide
    # minor dim tiles to 128 lanes — a 2x HBM pad on every saved q/k/v
    # (measured ~1.5 GB at SmolLM-1.7B mbs 2; PERF.md r4).
    q = checkpoint_name(q, "qkv_out")
    k = checkpoint_name(k, "qkv_out")
    v = checkpoint_name(v, "qkv_out")
    if keep_flat:
        # Folded into the matmul, the head split gives it a head-major
        # result ([rows, heads, d]{2,0,1}), and for that form the chip's
        # compiler wants the weight with the contracted dimension minor: a
        # serve program then re-lays the stacks' q, k and v once a dispatch
        # and writes each layer's slice of the copy out every step. A plain
        # [rows, in] x [in, out] matmul reads the layer's matrix where it
        # lies in the stack (tests/test_chip_compile.py weights_written)
        q, k, v = (jax.lax.optimization_barrier(x) for x in (q, k, v))
    q, k, v = (q.reshape(b, s, -1, d), k.reshape(b, s, -1, d),
               v.reshape(b, s, -1, d))
    if per_head:
        q = rms_norm(q, lp["q_norm"], eps)
        k = rms_norm(k, lp["k_norm"], eps)
    return q, k, v


def gated_qkv_proj(h, lp, cfg: ModelConfig, keep_flat: bool = False):
    """q/k/v of a gated attention (`attn_output_gate`) and its gate
    [B, S, Hq, D]: q's projection is twice as wide, each head's 2 D
    outputs its query, then its gate. The per-head QK-norm (with the
    block norms' scale, `norm_weight`) is the query's and the key's, not
    the gate's."""
    d, eps = cfg.head_dim, cfg.rms_norm_eps
    q, k, v = qkv_proj(h, lp, d, eps, keep_flat, qk_norm=False)
    q = q.reshape(*q.shape[:2], -1, 2, d)
    q, gate = q[..., 0, :], q[..., 1, :]
    if "q_norm" in lp:
        q = rms_norm(q, norm_weight(lp["q_norm"], cfg), eps)
        k = rms_norm(k, norm_weight(lp["k_norm"], cfg), eps)
    return q, k, v, gate


def gate_attention(out, gate):
    """The attention's output [B, S, Hq, D] times sigmoid of its gate."""
    with scope("attn_gate"):
        return out * jax.nn.sigmoid(gate.astype(jnp.float32)).astype(out.dtype)


def gdn_mixer(h, lp, cfg: ModelConfig, recur, tail, live):
    """A Gated DeltaNet mixer (ops/gated_delta.py) over a segment of every
    row. h [B, s, hidden]: the normed block input; tail [B, (kernel - 1) x
    channels] (the convolution's last inputs, position-major, as one row: a
    cache's pool then has no axis of 3 next to its last one, which a
    compiled program would carry in tiles of 4 and re-lay at its entry and
    exit): what the rows carry into the segment (zeros at a sequence's
    start); live [B, s]: the positions that hold a token, a prefix of each
    row. The recurrent state is the caller's, and so is the recurrence:
    `recur(q, k, v, g, beta)` (q, k [B, s, Hk, d_k], a row a KEY head, each
    of which serves Hv / Hk value heads side by side; v [B, s, Hv, d_v]; g,
    beta [B, s, Hv], float32, a position without a token inert) runs the
    gated delta rule over the segment from the state the caller's rows
    carry and returns (o [B, s, Hv, d_v], whatever the caller carries on):
    `ops.gated_delta.gated_delta` over a state [B, Hv, d_k, d_v] where the
    caller holds one (`_gdn_block`, `generate.HybridCache`), the cache's own
    answer where the state lives in a pool that a decode step updates in
    place (`serve.paged_cache.HybridPagedCache.recur`). Returns (out [B, s,
    hidden], what `recur` handed back, tail'): the tail after each row's
    last live position (as it was for a row with none). One body for
    `forward()`, prefill chunks and decode steps."""
    dt = h.dtype
    b, s, _ = h.shape
    hk, hv = cfg.linear_num_key_heads, cfg.linear_num_value_heads
    dk, dv = cfg.linear_key_head_dim, cfg.linear_value_head_dim
    nq, c = hk * dk, cfg.gdn_channels
    f32 = jnp.float32
    # the projections' outputs and the convolution's stay float32 up to the
    # recurrence, which is float32: a mixer answers an input's rounding with
    # twice its size, nine of them one after the other with eight times, so
    # the roundings inside it are not spent where they cost no time
    qkvz = jnp.matmul(h, lp["gdn_qkvz"].astype(dt), preferred_element_type=f32)
    ba = jnp.matmul(h, lp["gdn_ba"].astype(dt), preferred_element_type=f32)
    with scope("gdn_conv"):
        mixed, tail = causal_conv(qkvz[..., :c], tail.reshape(b, -1, c),
                                  lp["gdn_conv"], jnp.sum(live, axis=1))
        tail = tail.reshape(b, -1)
    q = l2_normalise(mixed[..., :nq].reshape(b, s, hk, dk)) * dk ** -0.5
    k = l2_normalise(mixed[..., nq:2 * nq].reshape(b, s, hk, dk))
    v = mixed[..., 2 * nq:].reshape(b, s, hv, dv).astype(f32)
    # a position without a token neither decays nor writes
    beta = jnp.where(live[..., None], jax.nn.sigmoid(ba[..., :hv]), 0.0)
    g = jnp.where(live[..., None], -jnp.exp(lp["gdn_A_log"].astype(f32))
                  * jax.nn.softplus(ba[..., hv:]
                                    + lp["gdn_dt_bias"].astype(f32)), 0.0)
    with scope("gdn_state"):
        o, carried = recur(q, k, v, g, beta)
    z = qkvz[..., c:].reshape(b, s, hv, dv)
    o = rms_norm(o, lp["gdn_norm"], cfg.rms_norm_eps) * jax.nn.silu(z)
    return (o.astype(dt).reshape(b, s, -1) @ lp["gdn_out"].astype(dt), carried,
            tail)


def gdn_start(cfg: ModelConfig, rows: int):
    """(state, tail) of `rows` sequences before their first position, both
    float32: the tail holds the projections' float32 outputs. A Kimi Delta
    Attention mixer's are shaped alike, from the same keys."""
    return (jnp.zeros((rows, cfg.linear_num_value_heads,
                       cfg.linear_key_head_dim, cfg.linear_value_head_dim),
                      jnp.float32),
            jnp.zeros((rows, (cfg.linear_conv_kernel_dim - 1)
                       * cfg.gdn_channels), jnp.float32))


@scope("gdn")
def _gdn_block(x, lp, cfg: ModelConfig):
    """RMSNorm -> Gated DeltaNet mixer over whole sequences from a zero
    state (the chunked form, which AD differentiates)."""
    h = rms_norm(x, norm_weight(lp["input_norm"], cfg), cfg.rms_norm_eps)
    state, tail = gdn_start(cfg, h.shape[0])
    out, _, _ = gdn_mixer(h, lp, cfg, partial(gated_delta, state=state), tail,
                          jnp.ones(h.shape[:2], bool))
    return out


def kda_mixer(h, lp, cfg: ModelConfig, recur, tail, live):
    """A Kimi Delta Attention mixer (ops/kda.py) over a segment of every
    row: `gdn_mixer`'s arrangement (h, tail, live, `recur` and what comes
    back are as described there, every head a key head and a value head),
    with what the mixer itself changes: q, k and v come out of one projection
    [q | k | v] and through three depthwise convolutions (one over all
    their channels); the decay is a CHANNEL of the key's, g [B, s, H, d_k] =
    -exp(A_log) softplus(W_f2 (W_f1 h) + dt_bias), through a bottleneck of
    d_v numbers; beta a head from its own projection; the output is each
    head's RMSNorm (a plain weight) times SIGMOID of a second low-rank
    projection of h. The recurrence stands under `kda_state` for a decode
    step and `kda_chunk` for a longer segment, the two low-rank projections
    and the softplus under `kda_gate`."""
    dt = h.dtype
    b, s, _ = h.shape
    hv, dk, dv = (cfg.linear_num_value_heads, cfg.linear_key_head_dim,
                  cfg.linear_value_head_dim)
    nq, c = hv * dk, cfg.gdn_channels
    f32 = jnp.float32

    def low_rank(a, b_):  # h -> d_v numbers -> the heads' channels, float32
        mid = jnp.matmul(h, lp[a].astype(dt), preferred_element_type=f32)
        return jnp.matmul(mid.astype(dt), lp[b_].astype(dt),
                          preferred_element_type=f32)

    # float32 up to the recurrence, as `gdn_mixer` keeps it and for its reason
    qkv = jnp.matmul(h, lp["kda_qkv"].astype(dt), preferred_element_type=f32)
    with scope("kda_conv"):
        mixed, tail = causal_conv(qkv, tail.reshape(b, -1, c), lp["kda_conv"],
                                  jnp.sum(live, axis=1))
        tail = tail.reshape(b, -1)
    q = l2_normalise(mixed[..., :nq].reshape(b, s, hv, dk)) * dk ** -0.5
    k = l2_normalise(mixed[..., nq:2 * nq].reshape(b, s, hv, dk))
    v = mixed[..., 2 * nq:].reshape(b, s, hv, dv).astype(f32)
    with scope("kda_gate"):
        g = -jnp.exp(lp["kda_A_log"].astype(f32))[:, None] * jax.nn.softplus(
            low_rank("kda_f_a", "kda_f_b").reshape(b, s, hv, dk)
            + lp["kda_dt_bias"].astype(f32).reshape(hv, dk))
        gate = low_rank("kda_g_a", "kda_g_b").reshape(b, s, hv, dv)
    # a position without a token neither decays nor writes
    g = jnp.where(live[..., None, None], g, 0.0)
    beta = jnp.where(live[..., None], jax.nn.sigmoid(jnp.matmul(
        h, lp["kda_beta"].astype(dt), preferred_element_type=f32)), 0.0)
    with scope("kda_state" if s == 1 else "kda_chunk"):
        o, carried = recur(q, k, v, g, beta)
    o = rms_norm(o, lp["kda_norm"], cfg.rms_norm_eps) * jax.nn.sigmoid(gate)
    return (o.astype(dt).reshape(b, s, -1) @ lp["kda_out"].astype(dt), carried,
            tail)


@scope("kda")
def _kda_block(x, lp, cfg: ModelConfig):
    """RMSNorm -> Kimi Delta Attention mixer over whole sequences from a
    zero state."""
    h = rms_norm(x, norm_weight(lp["input_norm"], cfg), cfg.rms_norm_eps)
    state, tail = gdn_start(cfg, h.shape[0])
    out, _, _ = kda_mixer(h, lp, cfg, partial(kda, state=state), tail,
                          jnp.ones(h.shape[:2], bool))
    return out


def mamba_mixer(h, lp, cfg: ModelConfig, conv, scan, carry, live):
    """A Mamba-1 mixer (ops/selective_scan.py) over a segment of every row.
    h [B, s, hidden]: the normed block input; live [B, s]: the positions that
    hold a token, a prefix of each row. What a sequence carries from segment
    to segment (the recurrent state and the convolution's tail: the last
    kernel - 1 inputs) is the caller's, `carry`, and so is every step that
    touches it:

    - `conv(carry, x, w, bias, n_valid)` (x [B, s, d_inner] float32; w
      [d_inner, kernel]; bias [d_inner] or None; n_valid [B]: each row's real
      positions) runs the causal convolution and the SiLU over the segment
      from the tail the rows carry (zeros at a sequence's start) and returns
      (u [B, s, d_inner], carry with the tail after each row's last real
      position);
    - `scan(carry, u, dt, b, c, a)` (u, dt [B, s, d_inner]; b, c [B, s,
      d_state]; a [d_state, d_inner] = -exp(A_log) transposed, as the state
      lies; float32, dt = 0 at a position without a token, which leaves the
      state as it was) runs the selective scan over the segment from the
      state the rows carry and returns (y [B, s, d_inner] without the D u
      term, carry with the state after it).

    `held_conv` / `held_scan` over a (state, tail) pair where the caller
    holds one (`_mamba_block`); a cache's own `conv` / `scan` where both live
    in pools that a decode step updates in place (`generate.HybridCache`,
    `serve.paged_cache.HybridPagedCache`). Returns (out [B, s, hidden], the
    carry after the segment). One body for `forward()`, prefill chunks and
    decode steps.

    Where it rounds: the matrix products take their inputs in the compute
    dtype (h, u before x_proj, the normed r before dt_proj, y silu(z) before
    out_proj: bfloat16 as served) and give float32; the convolution, the
    three inner norms, softplus, exp, the recurrence, D u and the gate are
    float32, and so are the state and the tail a sequence carries."""
    dt_ = h.dtype
    s = h.shape[1]
    di, n, r = cfg.ssm_inner, cfg.mamba_d_state, cfg.mamba_dt_rank
    f32, eps = jnp.float32, cfg.rms_norm_eps
    uz = jnp.matmul(h, lp["ssm_in"].astype(dt_), preferred_element_type=f32)
    # the recurrence's own scope holds every byte of state the segment moves
    # (the state's and, inside ssm_conv, the tail's) and the rule itself: a
    # decode step's `ssm_step`, a longer segment's `ssm_scan`
    moves = "ssm_step" if s == 1 else "ssm_scan"
    with scope("ssm_conv"):
        u, carry = conv(carry, uz[..., :di], lp["ssm_conv"],
                        lp.get("ssm_conv_bias"), jnp.sum(live, axis=1), moves)
    x = jnp.matmul(u.astype(dt_), lp["ssm_x"].astype(dt_),
                   preferred_element_type=f32)
    rr = rms_norm(x[..., :r], lp["ssm_dt_norm"], eps)
    bb = rms_norm(x[..., r:r + n], lp["ssm_b_norm"], eps).astype(f32)
    cc = rms_norm(x[..., r + n:], lp["ssm_c_norm"], eps).astype(f32)
    step = jax.nn.softplus(
        jnp.matmul(rr.astype(dt_), lp["ssm_dt"].astype(dt_),
                   preferred_element_type=f32)
        + lp["ssm_dt_bias"].astype(f32))
    # a position without a token neither decays nor writes
    step = jnp.where(live[..., None], step, 0.0)
    a = -jnp.exp(lp["ssm_A_log"].astype(f32)).T
    with scope(moves):
        y, carry = scan(carry, u, step, bb, cc, a)
    y = (y + lp["ssm_D"].astype(f32) * u) * jax.nn.silu(uz[..., di:])
    return y.astype(dt_) @ lp["ssm_out"].astype(dt_), carry


def conv_from_tail(x, tail, w, bias, n_valid):
    """`causal_conv` of x [B, s, d_inner] from a tail held in rows of 128
    lanes (`ops.selective_scan.tail_shape`, position-major) -> (u, the tail
    after each row's last real position, in the same shape)."""
    u, new = causal_conv(x, tail.reshape(x.shape[0], -1, x.shape[2]), w,
                         n_valid, bias)
    return u, new.reshape(tail.shape)


def held_conv(carry, x, w, bias, n_valid, moves=None):
    """`mamba_mixer`'s convolution over a (state, tail) pair the caller
    holds."""
    u, tail = conv_from_tail(x, carry[1], w, bias, n_valid)
    return u, (carry[0], tail)


def held_scan(carry, u, dt, b, c, a):
    """`mamba_mixer`'s recurrence over a (state, tail) pair the caller
    holds: state [B, d_state, d_inner]."""
    y, state = scan_segment(u, dt, b, c, a, carry[0])
    return y, (state, carry[1])


def mamba_start(cfg: ModelConfig, rows: int):
    """(state, tail) of `rows` sequences before their first position, both
    float32; the state transposed, [rows, d_state, d_inner], the tail in rows
    of 128 lanes (ops/selective_scan.py, `tail_shape`)."""
    return (jnp.zeros((rows, cfg.mamba_d_state, cfg.ssm_inner), jnp.float32),
            jnp.zeros((rows,) + tail_shape(cfg.ssm_inner, cfg.mamba_d_conv),
                      jnp.float32))


def recurrent_start(cfg: ModelConfig, rows: int):
    """(state, tail) of `rows` sequences before their first position, for
    the model's kind of recurrent mixer: what a cache's pools are shaped
    from."""
    return (mamba_start if cfg.ssm else mamba2_start if cfg.ssd
            else gdn_start)(cfg, rows)


def grouped_rms_norm(x, w, groups: int, eps: float):
    """RMSNorm of x [..., C] over each of `groups` equal runs of its
    channels, times the weight w [C]; float32."""
    x = x.astype(jnp.float32)
    g = x.reshape(*x.shape[:-1], groups, -1)
    g = g * jax.lax.rsqrt(jnp.mean(g * g, axis=-1, keepdims=True) + eps)
    return g.reshape(x.shape) * w.astype(jnp.float32)


def mamba2_mixer(h, lp, cfg: ModelConfig, conv, recur, carry, live):
    """A Mamba-2 mixer (ops/ssd.py) over a segment of every row. h [B, s,
    hidden]: the normed block input; live [B, s]: the positions that hold a
    token, a prefix of each row. What a sequence carries from segment to
    segment (the matrix state a head and the convolution's tail) is the
    caller's, `carry`, and so is every step that touches it, as
    `mamba_mixer`'s are:

    - `conv(carry, x, w, bias, n_valid, moves)`: `mamba_mixer`'s, over the
      channels [x | B | C];
    - `recur(carry, v, g, b, c)` (v [B, s, H, P] = d x; g [B, s, H] = d A
      <= 0; b, c [B, s, G, N], a row a GROUP; float32, g = 0 and v = 0 at a
      position without a token, which leaves the state as it was) runs the
      rule over the segment from the state the rows carry and returns (y [B,
      s, H, P] without the D x term, carry with the state after it).

    `held_conv` / `held_ssd` over a (state, tail) pair where the caller holds
    one (`_mamba2_block`); a cache's own `conv` / `ssd` where both live in
    pools (`generate.HybridCache`, `serve.paged_cache.HybridPagedCache`).
    [z | x B C | dt] come out of ONE projection; the step is d = softplus(dt +
    dt_bias), not clamped; the output is W_out [groupnorm(y * silu(z)) * w]:
    the gate BEFORE the norm, the RMS over each group's d_inner / n_groups
    channels. Where it rounds: as `mamba_mixer` (the projections take the
    compute dtype and give float32; everything between them is float32). The
    recurrence and its state and tail traffic stand under `ssd_step` in a
    decode step and `ssd_chunk` in a longer segment."""
    dt_ = h.dtype
    b_, s, _ = h.shape
    hm, p, grp, n = (cfg.mamba_num_heads, cfg.mamba_head_dim, cfg.n_groups,
                     cfg.ssm_state_size)
    di, c = cfg.ssd_inner, cfg.ssd_channels
    f32 = jnp.float32
    zxd = jnp.matmul(h, lp["ssd_in"].astype(dt_), preferred_element_type=f32)
    moves = "ssd_step" if s == 1 else "ssd_chunk"
    with scope("ssd_conv"):
        xbc, carry = conv(carry, zxd[..., di:di + c], lp["ssd_conv"],
                          lp.get("ssd_conv_bias"), jnp.sum(live, axis=1), moves)
    x = xbc[..., :di].reshape(b_, s, hm, p).astype(f32)
    bb = xbc[..., di:di + grp * n].reshape(b_, s, grp, n).astype(f32)
    cc = xbc[..., di + grp * n:].reshape(b_, s, grp, n).astype(f32)
    step = jax.nn.softplus(zxd[..., di + c:] + lp["ssd_dt_bias"].astype(f32))
    # a position without a token neither decays nor writes
    step = jnp.where(live[..., None], step, 0.0)
    with scope(moves):
        y, carry = recur(carry, x * step[..., None],
                         -jnp.exp(lp["ssd_A_log"].astype(f32)) * step, bb, cc)
    y = y + lp["ssd_D"].astype(f32)[:, None] * x
    y = y.reshape(b_, s, di) * jax.nn.silu(zxd[..., :di])
    y = grouped_rms_norm(y, lp["ssd_norm"], grp, cfg.rms_norm_eps)
    return y.astype(dt_) @ lp["ssd_out"].astype(dt_), carry


def held_ssd(carry, v, g, b, c):
    """`mamba2_mixer`'s recurrence over a (state, tail) pair the caller
    holds: state [B, H, P, N]."""
    y, state = ssd(v, g, b, c, carry[0])
    return y, (state, carry[1])


def mamba2_start(cfg: ModelConfig, rows: int):
    """(state, tail) of `rows` sequences before their first position, both
    float32: the state [rows, H, P, N], N along the lanes (ops/ssd.py), the
    tail in rows of 128 lanes (`ops.selective_scan.tail_shape`)."""
    return (jnp.zeros((rows, cfg.mamba_num_heads, cfg.mamba_head_dim,
                       cfg.ssm_state_size), jnp.float32),
            jnp.zeros((rows,) + tail_shape(cfg.ssd_channels, cfg.mamba_d_conv),
                      jnp.float32))


@scope("ssd_mixer")
def _mamba2_block(x, lp, cfg: ModelConfig):
    """RMSNorm -> Mamba-2 mixer over whole sequences from a zero state."""
    h = rms_norm(x, norm_weight(lp["input_norm"], cfg), cfg.rms_norm_eps)
    out, _ = mamba2_mixer(h, lp, cfg, held_conv, held_ssd,
                          mamba2_start(cfg, h.shape[0]),
                          jnp.ones(h.shape[:2], bool))
    return out


@scope("ssm_mixer")
def _mamba_block(x, lp, cfg: ModelConfig):
    """RMSNorm -> Mamba mixer over whole sequences from a zero state."""
    h = rms_norm(x, norm_weight(lp["input_norm"], cfg), cfg.rms_norm_eps)
    out, _ = mamba_mixer(h, lp, cfg, held_conv, held_scan,
                         mamba_start(cfg, h.shape[0]),
                         jnp.ones(h.shape[:2], bool))
    return out


@scope("attention")
def _attention_block(x, lp, cfg: ModelConfig, ctx: ParallelCtx, cos, sin,
                     kind: str = "full_attention"):
    """RMSNorm -> qkv -> RoPE -> attention -> out_proj (ref: model.py:122-162).
    `kind`: the layer's attention kind, which picks its RoPE tables and
    its band."""
    dt = x.dtype
    d = cfg.head_dim

    h = rms_norm(x, norm_weight(lp["input_norm"], cfg), cfg.rms_norm_eps)
    h = ctx.f(h)  # column-parallel entry: identity fwd / psum bwd; under
    # sequence parallelism an all_gather that restores the full sequence
    b, s, _ = h.shape
    # qkv_proj checkpoint-names the flat projections ("qkv_out"): the
    # "dots_attn" policy saves the attention-side dots (the flash VJP's
    # inputs) while the MLP recomputes — the memory/flops midpoint between
    # "dots" and "full" (the MLP's gate/up activations are ~2/3 of a
    # layer's saved bytes but its matmuls only ~+7% of step flops)
    gate = None
    if cfg.attn_output_gate:
        q, k, v, gate = gated_qkv_proj(h, lp, cfg)
    else:
        q, k, v = qkv_proj(h, lp, d, cfg.rms_norm_eps)
    n_q = q.shape[2]

    # K/V stay unexpanded (n_kv heads) — attention impls handle GQA so the
    # CP ring permutes and flash streams the small K/V. RoPE is applied by
    # the impl (in-kernel on the flash path), so q/k pass through raw.
    rope, window = kind_tables(cos, sin, kind), layer_window(cfg, kind)
    # the band goes only to an impl that was asked for one: the others
    # keep their signature, and never see a model with sliding layers
    band = {} if window is None else {"window": window}
    out = ctx.attn(q, k, v, ctx.positions, rope, **band)  # [B, S, n_q, D]
    if gate is not None:
        out = gate_attention(out, gate)
    # attn_out/attn_lse are checkpoint_name'd inside each attention impl
    # (flash VJP fwd rule / sdpa), so the "dots" remat policy saves the
    # kernel residuals exactly once and backward never re-runs the forward.
    out = out.reshape(b, s, n_q * d)
    out = out @ lp["o"].astype(dt)
    out = checkpoint_name(out, "attn_proj_out")
    return ctx.g(out)  # row-parallel exit: psum-over-tp fwd / identity bwd


@scope("attention")
def _eva_attention_block(x, lp, cfg: ModelConfig, ctx: ParallelCtx, cos, sin):
    """RMSNorm -> qkv -> RoPE -> EVA attention (ops/eva.py), dense: a mask
    over the sequence's keys and the summaries of its whole chunks ->
    out_proj. The one attention implementation of such a model on the
    training-shaped path (Config.validate refuses the kernels, the cp
    schedules and every sharded layout for it)."""
    dt = compute_dtype(cfg)
    h = rms_norm(x, norm_weight(lp["input_norm"], cfg),
                 cfg.rms_norm_eps).astype(dt)
    b, s, _ = h.shape
    q, k, v = qkv_proj(h, lp, cfg.head_dim, cfg.rms_norm_eps)
    pos = ctx.positions if ctx.positions is not None else jnp.arange(s)
    q, k = apply_rope(q, cos, sin, pos), apply_rope(k, cos, sin, pos)
    ks, vs = chunk_summaries(k, v, lp["eva_mu"], lp["eva_phi"],
                             cfg.chunk_size)
    out = eva_attention(q, k, v, ks, vs, pos, cfg.window_size,
                        cfg.chunk_size)
    out = out.reshape(b, s, -1) @ lp["o"].astype(dt)
    return checkpoint_name(out, "attn_proj_out")


@scope("attention")
def _mla_attention_block(x, lp, cfg: ModelConfig, ctx: ParallelCtx, cos, sin):
    """RMSNorm -> latent attention (ops/mla.py), expanded: every head's
    keys and values built from the latents, plain causal attention over
    heads of nope + rope wide keys and v wide values -> out_proj. The
    one attention implementation of a model with a latent cache on the
    training-shaped paths (Config.validate refuses the kernels and the cp
    schedules for it: one head width for q, k and v there)."""
    dt = x.dtype
    h = rms_norm(x, lp["input_norm"], cfg.rms_norm_eps)
    b, s, _ = h.shape
    q_n, q_r, c, k_r = mla_project(h, lp, cfg)
    pos = ctx.positions
    k_r = k_r[:, :, None, :]                                 # one for all heads
    if not cfg.mla_use_nope:
        q_r = apply_rope(q_r, cos, sin, pos)
        k_r = apply_rope(k_r, cos, sin, pos)
    w_uk, w_uv = up_weights(lp["kv_b"], cfg, dt)
    k_n = jnp.einsum("bsr,rhd->bshd", c, w_uk)
    v = jnp.einsum("bsr,rhd->bshd", c, w_uv)
    q = jnp.concatenate([q_n, q_r], axis=-1)
    k = jnp.concatenate([k_n, jnp.broadcast_to(k_r, q_r.shape)], axis=-1)
    out = sdpa_attention(q, k, v, causal=True, q_positions=pos,
                         kv_positions=pos)                   # [B, S, heads, v]
    with scope("mla_o"):
        out = out.reshape(b, s, -1) @ lp["o"].astype(dt)
    return checkpoint_name(out, "attn_proj_out")


def mlp_act(cfg: ModelConfig):
    """Gated-MLP activation on the gate branch: SwiGLU (silu, the Llama
    lineage, ref: model.py:184-186), exact-erf GeGLU ("gelu" — what
    transformers' ACT2FN "gelu" means), or tanh-approx GeGLU ("gelu_tanh",
    the Gemma-style variant) — shared by the dense MLP, the MoE expert
    bank, and the decode path so they cannot diverge. "relu2" is NOT a gated
    activation: the MLP is down(relu(up x)^2), two matrices and no gate
    branch (`cfg.mlp_gated` false; the expert banks then hold no `w_gate`
    and the shared expert no `shared_gate`), and this is what stands
    between them."""
    if cfg.hidden_act == "silu":
        return jax.nn.silu
    if cfg.hidden_act == "relu2":
        return relu2
    return _GELU[cfg.hidden_act == "gelu_tanh"]


def relu2(x):
    """relu(x)^2: one object, as `_GELU`'s are (a static argument of the
    served expert block's jit)."""
    return jnp.square(jax.nn.relu(x))


# one object a variant: the activation is a static argument of the served
# expert block's jit (ops/moe.py), which keys its traces by it
_GELU = {approximate: partial(jax.nn.gelu, approximate=approximate)
         for approximate in (False, True)}


@scope("mlp")
def _mlp_block(x, lp, cfg: ModelConfig, ctx: ParallelCtx):
    """RMSNorm -> gated MLP (ref: model.py:184-186)."""
    h = rms_norm(x, norm_weight(lp["post_norm"], cfg), cfg.rms_norm_eps)
    if cfg.fp32_skip_add:  # the stream is float32, the matmuls are not
        h = h.astype(compute_dtype(cfg))
    dt = h.dtype
    h = ctx.f(h)
    gate = checkpoint_name(h @ lp["gate"].astype(dt), "mlp_gate")
    up = checkpoint_name(h @ lp["up"].astype(dt), "mlp_up")
    out = (mlp_act(cfg)(gate) * up) @ lp["down"].astype(dt)
    return ctx.g(out)


def shared_expert(h, lp, cfg: ModelConfig):
    """The shared experts' MLP (gated, or of two matrices where the layer
    has no `shared_gate`) over the normed block input h: every
    token passes through it, with gate 1 or, where the layer has
    `shared_out_gate`, sigmoid of that 1-wide projection of the token. One
    implementation for the training block and the cached decode paths."""
    dt = h.dtype
    with scope("moe_shared"):
        up = h @ lp["shared_up"].astype(dt)
        if "shared_gate" in lp:
            up = mlp_act(cfg)(h @ lp["shared_gate"].astype(dt)) * up
        else:  # not gated (`mlp_act`)
            up = mlp_act(cfg)(up)
        out = up @ lp["shared_down"].astype(dt)
        if "shared_out_gate" in lp:
            with scope("moe_shared_gate"):
                out = out * jax.nn.sigmoid(
                    (h @ lp["shared_out_gate"].astype(dt)).astype(
                        jnp.float32))[..., None].astype(dt)
        return out


def expert_norm(lp):
    """The norm in front of a layer's experts: `post_norm` behind a mixer,
    the layer's one norm where the experts are the layer."""
    return lp["post_norm"] if "post_norm" in lp else lp["input_norm"]


def latent_in(h, lp):
    """LatentMoE: what the routed experts read, W_dn h (None where the
    experts read the token itself). Under `moe_latent`, with `latent_out`."""
    if "latent_down" not in lp:
        return None
    with scope("moe_latent"):
        return h @ lp["latent_down"].astype(h.dtype)


def latent_out(out, lp):
    """LatentMoE: the routed experts' weighted sum back on the full width,
    W_up (sum_i g_i E_i(l)); the sum itself where there is no latent."""
    if "latent_up" not in lp:
        return out
    with scope("moe_latent"):
        return out @ lp["latent_up"].astype(out.dtype)


def _experts(x, lp, cfg: ModelConfig, ctx: ParallelCtx, is_real=1.0):
    """RMSNorm -> top-k routed expert SwiGLU bank (beyond the reference;
    ops/moe.py), beside the shared expert where the model has one and the
    zero-compute experts' term where the router has some. Returns
    (out, aux [3]): the pre-weighted router loss, the capacity drop
    fraction and the busiest expert's load over the mean."""
    from picotron_tpu.ops.moe import moe_mlp

    h = rms_norm(x, norm_weight(expert_norm(lp), cfg), cfg.rms_norm_eps)
    h = ctx.f(h)
    # every expert on this device (ep = 1): the dropless dispatch; across
    # 'ep' the all_to_all needs the capacity path's fixed shapes
    ep = (jax.lax.psum(1, ctx.moe_ep_axis)
          if ctx.moe_ep_axis is not None else 1)
    out, aux, drop, load = moe_mlp(
        h, lp["router"], lp.get("w_gate"), lp["w_up"], lp["w_down"],
        num_experts=cfg.num_experts,
        top_k=cfg.num_experts_per_token,
        capacity_factor=cfg.capacity_factor if ep > 1 else None,
        act=mlp_act(cfg),
        ep_axis=ctx.moe_ep_axis,
        router_aux_coef=cfg.router_aux_coef,
        router_z_coef=cfg.router_z_coef,
        stat_axes=ctx.moe_stat_axes,
        norm_topk_prob=cfg.norm_topk_prob,
        scoring=cfg.moe_scoring, scale=cfg.routed_scaling_factor,
        expert_first=cfg.expert_first,
        bias=lp.get("router_bias"), zero=cfg.zero_experts,
        latent=latent_in(h, lp),
    )
    out = latent_out(out, lp)
    if "shared_up" in lp:
        out = out + shared_expert(h, lp, cfg)
    # Zero-padded PP layer slots (pad_layers_for_pp) must not contribute
    # router statistics: their all-zero router yields uniform logits whose
    # z-loss (log(E)^2 per token) and tie-broken top-k capacity overflow
    # would pollute the loss and the drop metric (code review r3). `is_real`
    # comes from the static placement (ctx.layer_is_real via run_layers),
    # not from the weights (ADVICE r3).
    return ctx.g(out), ctx.moe_aux_sync(
        jnp.stack([aux, drop, load]) * is_real)


_moe_block = scope("mlp")(_experts)  # a layer's MLP that is an expert block


def _shortcut_layer(x, lp, cfg: ModelConfig, ctx: ParallelCtx, cos, sin,
                    is_real=1.0):
    """A layer of two (latent attention, dense MLP) pairs and a
    shortcut-connected expert branch (LongCat-Flash; `Block.mlp ==
    "shortcut"`), N an RMSNorm:

        a1 = x  + Attn_0(N_in0(x))
        s  = Experts(N_post0(a1))          the branch starts here ...
        m1 = a1 + MLP_0(N_post0(a1))
        a2 = m1 + Attn_1(N_in1(m1))
        y  = a2 + MLP_1(N_post1(a2)) + s   ... and lands here

    The branch is computed where its input exists and added where the
    model adds it, in this order of the sums."""
    p0, p1 = sublayer(lp, 0), sublayer(lp, 1)
    a1 = x + _mla_attention_block(x, p0, cfg, ctx, cos, sin)
    with scope("scmoe_branch"):
        s, aux = _experts(a1, p0, cfg, ctx, is_real)
    m1 = a1 + _mlp_block(a1, p0, cfg, ctx)
    a2 = m1 + _mla_attention_block(m1, p1, cfg, ctx, cos, sin)
    return a2 + _mlp_block(a2, p1, cfg, ctx) + s, aux


def decoder_layer(x, lp, cfg: ModelConfig, ctx: ParallelCtx, cos, sin,
                  is_real=1.0, kind: str = "full_attention",
                  block: Optional[Block] = None):
    """Returns (x, aux [3]) — aux[0] is the pre-weighted router loss
    (balance + z, 0 for dense models), aux[1] the capacity drop fraction
    and aux[2] the busiest expert's load over the mean (observability;
    stop_gradient-free but weightless in the loss).
    `is_real` masks the aux of zero-padded PP layer slots (see
    ParallelCtx.layer_is_real). `block`: what the layer is made of
    (`cfg.stacks`); None = the last stack's, which is every layer's in a
    model of one kind of block."""
    block = block or cfg.stacks[-1].block
    if block.mlp == "shortcut":
        return _shortcut_layer(x, lp, cfg, ctx, cos, sin, is_real)
    if block.alone:
        # ONE sublayer behind one norm, named by the layer's kind
        if kind == MOE:
            out, aux = _moe_block(x, lp, cfg, ctx, is_real)
            return x + out, aux
        out = (_mamba2_block(x, lp, cfg) if kind == SSD
               else _attention_block(x, lp, cfg, ctx, cos, sin, kind))
        return x + out, jnp.zeros(3, jnp.float32)
    if kind == GDN:
        attn_out = _gdn_block(x, lp, cfg)
    elif kind == KDA:
        attn_out = _kda_block(x, lp, cfg)
    elif kind == SSM:
        attn_out = _mamba_block(x, lp, cfg)
    elif block.attn == "mla":
        attn_out = _mla_attention_block(x, lp, cfg, ctx, cos, sin)
    elif block.attn == "eva":
        attn_out = _eva_attention_block(x, lp, cfg, ctx, cos, sin)
    else:
        attn_out = _attention_block(x, lp, cfg, ctx, cos, sin, kind)
    if block.sandwich:
        attn_out = rms_norm(attn_out, lp["attn_out_norm"], cfg.rms_norm_eps)
    x = x + attn_out
    if block.mlp == "experts":
        mlp_out, aux = _moe_block(x, lp, cfg, ctx, is_real)
    else:
        mlp_out, aux = _mlp_block(x, lp, cfg, ctx), jnp.zeros(3, jnp.float32)
    if block.sandwich:
        mlp_out = rms_norm(mlp_out, lp["mlp_out_norm"], cfg.rms_norm_eps)
    return x + mlp_out, aux


def remat_policy_for(name: str):
    """jax.checkpoint policy for a config remat_policy name.

    "dots" saves matmul outputs + the named attention output, so only cheap
    elementwise work is recomputed in backward; "full" (None) recomputes
    everything. Shared by the layer scan here and the pipeline tick scan
    (parallel/pp.py) so both paths honor the same config knob.
    """
    if name in ("dots", "dots_norms"):
        # attn_lse rides along with attn_out (named inside the flash VJP's
        # fwd rule, ops/flash_attention.py) so the kernel's residuals are
        # fully saved and backward never re-runs the forward kernel.
        # "dots_norms" additionally saves the RMSNorm outputs — backward
        # skips the fp32 norm recompute at ~2 extra saved activations per
        # layer of HBM (measured slower on v5e; PERF.md).
        names = ("attn_out", "attn_lse")
        if name == "dots_norms":
            names += ("norm_out",)
        return jax.checkpoint_policies.save_from_both_policies(
            jax.checkpoint_policies.dots_with_no_batch_dims_saveable,
            jax.checkpoint_policies.save_only_these_names(*names),
        )
    if name == "dots_attn":
        # Save only the flash kernel's inputs and residuals (qkv
        # projections, out, lse) and recompute everything else in backward
        # — the MLP (its gate/up activations are ~2/3 of a layer's saved
        # bytes but its matmuls only ~+7% of step FLOPs) and the
        # o-projection (one matmul consuming the SAVED attn_out). The
        # policy that fits full-depth SmolLM-1.7B beside
        # optimizer_offload's fp32 grad tree on one v5e chip (PERF.md r4).
        return jax.checkpoint_policies.save_only_these_names(
            "attn_out", "attn_lse", "qkv_out")
    if name == "dots_lean":
        # "dots" minus the o-projection and down-projection outputs (each
        # is one matmul whose inputs ARE saved — attn_out and gate/up —
        # so recompute costs ~+2% step FLOPs for ~0.4 GB less saved HBM
        # at SmolLM-1.7B mbs 1). All saves are the flat named forms, so
        # none carry the 64-lane tile padding (PERF.md r4).
        return jax.checkpoint_policies.save_only_these_names(
            "attn_out", "attn_lse", "qkv_out", "mlp_gate", "mlp_up")
    if name == "dots_offload":
        # "dots" memory shape with the saved activations parked in pinned
        # HOST memory instead of HBM (offloaded on the forward, fetched in
        # backward): near-zero device activation residency for 2x the
        # activation bytes over PCIe per microbatch. Measured on v5e in
        # PERF.md round 4 — the PCIe cost exceeds the recompute it avoids
        # at these shapes; kept as a knob for shapes where it flips
        # (long-sequence activations >> PCIe budget is the wrong side; big
        # grad-accum with small activations the right one).
        # attn_lse stays device-saved: it is tiny ([B,H,S] vs the [B,S,H*D]
        # tensors) and offloading it crashes libtpu's host-offload
        # legalizer (host_offload_utils.cc "reduce has 2 operands" check —
        # the lse feeds a variadic reduce in the flash VJP)
        return jax.checkpoint_policies.save_and_offload_only_these_names(
            names_which_can_be_saved=["attn_lse"],
            names_which_can_be_offloaded=[
                "attn_out", "qkv_out", "attn_proj_out",
                "mlp_gate", "mlp_up"],
            offload_src="device", offload_dst="pinned_host")
    return None


def run_layers(layer_params: Params, x: jnp.ndarray, cfg: ModelConfig,
               ctx: ParallelCtx = DEFAULT_CTX,
               cos: jnp.ndarray | None = None,
               sin: jnp.ndarray | None = None,
               block: Optional[Block] = None,
               kinds: Optional[tuple] = None):
    """Scan a stacked layer pytree over x. Works on any contiguous stage
    slice, which is exactly what pipeline parallelism feeds it. `block`:
    what the stack's layers are made of (see `decoder_layer`). `kinds`:
    the attention kind of each layer of the slice (`Stack.kinds`); None =
    every layer of the model's one kind (a stage slice of any length).

    Returns (x, aux [3]) — aux[0] the summed pre-weighted MoE router loss
    over the scanned layers, aux[1] the summed capacity drop fraction,
    aux[2] the summed busiest-expert load ratio (all 0 for dense models)."""
    if cos is None:
        cos, sin = model_rope_tables(cfg)
    n_slots = layer_params["input_norm"].shape[0]
    if kinds is None:
        if len(cfg.layer_period) > 1:
            raise ValueError(
                "run_layers needs the `kinds` of its slice for a model "
                "with a layer pattern (`Stack.kinds`)")
        kinds = cfg.layer_period * n_slots
    # one scan iteration runs one whole period of the slice's own pattern:
    # one layer for a model of one kind, (S, S, S, F) for Mellum2, each
    # layer of the body traced with its own kind (tables, band); the
    # layers left over after the whole periods run after the scan
    period, whole, rest = pattern_of(tuple(kinds))

    def one(h, lp, real, kind):
        return decoder_layer(h, lp, cfg, ctx, cos, sin, real, kind, block)

    def body(h, xs):
        lp, real = xs
        if len(period) == 1:
            return one(h, lp, real, period[0])
        aux = jnp.zeros(3, jnp.float32)
        for j, kind in enumerate(period):
            h, a = one(h, layer_leaves(lp, period, j), real[j], kind)
            aux = aux + a
        # aux rides the scan's stacked outputs (not the carry: its varying
        # mesh axes differ from x's, which would unstabilize the carry type)
        return h, aux

    real = (ctx.layer_is_real(n_slots) if ctx.layer_is_real is not None
            else jnp.ones((n_slots,), jnp.float32))
    xs = (layer_params, real)
    if len(period) > 1:
        # a leaf is split by the layers of a period that hold it
        xs = ({n: by_period(w, leaf_row(n, period, len(period)), whole)
               for n, w in layer_params.items()},
              by_period(real, len(period), whole))
    left_over = one
    if ctx.remat:
        policy = remat_policy_for(ctx.remat_policy)
        body = jax.checkpoint(body, policy=policy)
        left_over = jax.checkpoint(one, policy=policy, static_argnums=(3,))
    x, aux_per_layer = jax.lax.scan(body, x, xs)  # [L // period, 3]
    aux = jnp.sum(aux_per_layer, axis=0)
    for j, kind in enumerate(rest, whole * len(period)):
        x, a = left_over(x, layer_leaves(layer_params, tuple(kinds), j),
                         real[j], kind)
        aux = aux + a
    return x, aux


def run_stacks(params: Params, x: jnp.ndarray, cfg: ModelConfig,
               ctx: ParallelCtx = DEFAULT_CTX, cos=None, sin=None):
    """Every stack of the layer tree in order (`cfg.stacks`), each one
    scan over the whole periods of its own slice of the layer pattern (and
    what is left of it after them): the leading dense layers, then the
    expert layers; the one `layers` stack of a model of one kind of block.
    Returns (x, aux [3]) as `run_layers`."""
    aux = jnp.zeros(3, jnp.float32)
    for st in cfg.stacks:
        x, a = run_layers(params[st.name], x, cfg, ctx, cos, sin, st.block,
                          st.kinds)
        aux = aux + a
    return x, aux


def final_hidden(params: Params, x: jnp.ndarray, cfg: ModelConfig) -> jnp.ndarray:
    return rms_norm(x, norm_weight(params["final_norm"], cfg),
                    cfg.rms_norm_eps)


def logits_from_hidden(params: Params, x: jnp.ndarray, cfg: ModelConfig,
                       ctx: ParallelCtx = DEFAULT_CTX) -> jnp.ndarray:
    # Under sequence parallelism x arrives seq-sharded; the column-parallel
    # entry hook re-gathers the sequence before the vocab-sharded head.
    x = ctx.f(x)
    logits = x @ head_weight(params).astype(x.dtype)
    return ctx.gather_logits(logits)


# ---------------------------------------------------------------------------
# Convenience compositions
# ---------------------------------------------------------------------------


def forward(params: Params, input_ids: jnp.ndarray, cfg: ModelConfig,
            ctx: ParallelCtx = DEFAULT_CTX) -> jnp.ndarray:
    """input_ids [B, S] -> logits [B, S, V] (full vocab; eval/debug path);
    [B, S, num_pred_heads, V] for a head of several prediction heads."""
    cos, sin = model_rope_tables(cfg)
    x = embed(params, input_ids, cfg, ctx)
    x, _ = run_stacks(params, x, cfg, ctx, cos, sin)
    x = final_hidden(params, x, cfg)
    logits = logits_from_hidden(params, x, cfg, ctx)
    if cfg.num_pred_heads > 1:
        logits = logits.reshape(*logits.shape[:2], cfg.num_pred_heads, -1)
    return logits


def loss_sum_count(params: Params, input_ids: jnp.ndarray, targets: jnp.ndarray,
                   cfg: ModelConfig, ctx: ParallelCtx = DEFAULT_CTX):
    """(sum of per-token NLL, valid-token count) — the reduction pieces, so
    data-parallel shards can psum both and divide once (a per-shard mean +
    unweighted pmean would mis-weight shards with different IGNORE_INDEX
    counts).

    Under TP, `ctx.head_ce` computes the pieces against vocab-sharded logits
    without materializing the full-vocab gather.

    For MoE models the (pre-weighted, ops/moe.py) router loss is folded in
    as `nll_sum + aux * count`, so the downstream `total / count` division
    yields `ce_mean + aux` — the reported loss includes the router terms
    (Mixtral convention) and their gradient flows with no extra plumbing
    through the dp/cp/pp reductions. The third return is an extras dict of
    token-weighted observability sums ({"moe_obs_weighted"} [2] for MoE:
    capacity drops and busiest-expert load; {} for dense) that ride the
    same psum path; the step normalizes them.
    """
    refuse_training(cfg)
    cos, sin = model_rope_tables(cfg)
    x = embed(params, input_ids, cfg, ctx)
    x, aux = run_stacks(params, x, cfg, ctx, cos, sin)
    with scope("head_ce"):
        x = final_hidden(params, x, cfg)
        if ctx.head_ce is not None:
            total, count = ctx.head_ce(x, head_weight(params), targets)
        else:
            logits = x @ head_weight(params).astype(x.dtype)
            total, count = cross_entropy_sum_count(logits, targets)
    extras = {}
    if cfg.num_experts:
        total = total + aux[0] * count
        extras["moe_obs_weighted"] = aux[1:] * count
    return total, count, extras


def loss_fn(params: Params, input_ids: jnp.ndarray, targets: jnp.ndarray,
            cfg: ModelConfig, ctx: ParallelCtx = DEFAULT_CTX) -> jnp.ndarray:
    """Token-mean cross-entropy training loss (ref: train.py:43-49)."""
    total, count, _ = loss_sum_count(params, input_ids, targets, cfg, ctx)
    return total / jnp.maximum(count, 1)
