"""Named scopes: the regions of the device programs that a trace reduction
can find by name after a refactor.

`scope(name)` is `jax.named_scope(name)` for a name declared here. The name
becomes an element of every enclosed operation's name stack, which the
compiler keeps as the instruction's `op_name` (`compiled.as_text()`, and the
`tf_op` stat of a device event in a profiler trace): under a transform it
reads `jvp(mlp)` or `transpose(jvp(mlp))`, so a reduction looks for the name
as a word of the path. It costs nothing at run time. The benchmark's metric
files (`benchmark/layer_metrics/*.json`) spell the same strings.

One rule, because this installation names a Pallas custom call after the
innermost element of the name stack at the call, and an accepted benchmark
metric (`flash_roofline.train`) finds the flash kernels of the fused grad
engine by the name they have there (`closed_call.N`, after the layer
scan's body): a scope is never entered directly around those kernel calls.
`parallel/fused_bwd.py` leaves `attention` before each call and enters it
again after, and tests/test_chip_compile.py holds the names. Collectives
are named after their primitive, so `tp_reduce` and `pp_boundary` leave
the events' names alone.
"""

from __future__ import annotations

import jax

SCOPES = (
    "embed",            # token embedding lookup (and its gradient)
    "attention",        # norm, q/k/v and o projections, RoPE; the AD engine's kernel calls
    "mlp",              # norm, gated MLP or expert block
    "moe_router",       # inside mlp: router matmul, softmax, top-k, slots, rows, aux terms
    "moe_dispatch",     # inside mlp: the permutations into and out of expert order, gated sum
    "moe_experts",      # inside mlp: the experts' three (grouped) matmuls and activation
    "head_ce",          # final norm, head matmul, cross entropy, their gradient
    "dw_accum",         # the fused engine's in-scan dW accumulation into the f32 stacks
    "optimizer",        # clip, Adam update, cast back
    "pp_boundary",      # the pipeline schedule's ppermutes
    "tp_reduce",        # tensor-parallel psum / psum_scatter / all_gather hooks
    "kv_write",         # serve: the paged pool update
    "paged_attention",  # serve: attention over the cache: the decode step's in-place kernel; prefill's gathered views, mask, softmax, PV
    "attn_full",        # inside paged_attention, a model with sliding layers: a full layer's attention
    "attn_window",      # inside paged_attention, the same model: a sliding-window layer's attention
    "sample",           # serve: next-token choice from the logits
    "mla_q",            # latent attention: the query's two projections and the norm between them
    "mla_kv_latent",    # latent attention: the projection to [c | k_r] and c's norm
    "mla_absorb",       # latent attention: the products with Wkvb's two halves (Wuk into the queries and Wuv out of P c when absorbed; a key tile's expansion otherwise)
    "mla_o",            # latent attention: the output projection
    "attn_latent",      # inside paged_attention, a model with a latent cache: the decode step's latent kernel; prefill's tiles, mask, softmax, PV
    "moe_shared",       # inside mlp: the shared expert's gated MLP
    "scmoe_branch",     # a layer of two attentions: its shortcut-connected expert branch as a whole (router, routed experts, zero-compute term); NOT under mlp, which is that layer's two dense MLPs
    "moe_zero",         # inside the expert block: the zero-compute experts' term, the token times the summed gates of its picks of them
    "gdn",              # a Gated DeltaNet mixer as a whole: its projections, convolution, recurrence, gated norm and output projection
    "gdn_conv",         # inside gdn: the causal depthwise convolution over [q | k | v] with its carried tail, and the SiLU
    "gdn_state",        # inside gdn: every byte of recurrent state a dispatch moves: in the decode program the gated delta rule as one kernel over the state pool in place (the live rows alone) and the tail's gather and scatter; in the prefill program the chunked rule as one kernel over the state pool in place (the rows with a real position alone; off a chip the rows' gather, the chunked form and the scatter) and the tail's gather and scatter
    "attn_gate",        # a gated attention: its output times sigmoid of the gate that came out of q's projection
    "moe_shared_gate",  # inside moe_shared: the shared expert's output times sigmoid of a 1-wide projection of the token
    "ssm_mixer",        # a Mamba-1 mixer as a whole: its projections, convolution, the three inner norms, recurrence, D u, gate and output projection
    "ssm_conv",         # inside ssm_mixer: the causal depthwise convolution with bias over the d_inner channels, and the SiLU
    "ssm_scan",         # inside ssm_mixer: every byte of recurrent state a longer segment moves (a prefill chunk's, forward()'s) and the rule itself: the rows' states and tails out of the pools, the selective scan token by token, the states and tails back
    "ssm_step",         # inside ssm_mixer: every byte of recurrent state a decode step moves and the rule itself: on a chip one kernel over the state pool in place (the live rows' states alone), else the rows' gather, the rule and the scatter; the tail's gather and scatter in every case
    "kda",              # a Kimi Delta Attention mixer as a whole: its projections, convolutions, decay and gate projections, recurrence, gated norm and output projection
    "kda_conv",         # inside kda: the three causal depthwise convolutions over [q | k | v] (one over all their channels) with the carried tail, and the SiLU
    "kda_gate",         # inside kda: the two low-rank projections (the decay's, the output gate's) and the decay's softplus
    "kda_state",        # inside kda: every byte of recurrent state a DECODE step moves and the rule itself: on a chip one kernel over the state pool in place (the live rows' matrices alone), else the rows' gather, the rule and the scatter; the tail's gather and scatter in every case
    "kda_chunk",        # inside kda: every byte of recurrent state a longer segment moves (a prefill chunk's, forward()'s) and the chunked per-channel rule: the rows' states and tails out of the pools, the sub-chunks one after the other, the states and tails back
    "ssd_mixer",        # a Mamba-2 mixer as a whole: the one projection to [z | x B C | dt], convolution, recurrence, D x, the gate, the grouped norm and the output projection
    "ssd_conv",         # inside ssd_mixer: the causal depthwise convolution with bias over [x | B | C], and the SiLU
    "ssd_step",         # inside ssd_mixer: every byte of recurrent state a DECODE step moves and the rule itself: on a chip one kernel over the state pool in place (the live rows' matrices alone) and one over the tail pool, else the rows' gather, the rule and the scatter
    "ssd_chunk",        # inside ssd_mixer: every byte of recurrent state a longer segment moves (a prefill chunk's, forward()'s) and the chunked rule: on a chip one kernel over the state pool in place (the rows with a real position alone), else the rows' gather, the chunked form and the scatter; the tail's gather and scatter in every case
    "moe_latent",       # inside mlp: LatentMoE's two projections around the routed experts, the token down to the latent and the experts' weighted sum back up
    "eva_summarise",    # EVA attention: the pooling of a chunk's keys and values into its summary row (ops/eva.py); the attention itself is under attention / paged_attention
)


def scope(name: str):
    """`jax.named_scope(name)`, context manager or decorator, for a
    declared name."""
    if name not in SCOPES:
        raise ValueError(f"scope {name!r} is not declared in telemetry/scopes.py")
    return jax.named_scope(name)
