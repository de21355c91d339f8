"""Parameter and batch PartitionSpecs over the (dp, pp, cp, tp) mesh.

This module is the declarative heart of DP/TP/PP: where the reference
surgically replaces nn.Linear modules with Column/Row/VocabParallel classes
(ref: tensor_parallel.py:9-52) and slices layer stacks per pipeline rank
(ref: pipeline_parallel.py:13-51), here one pytree of PartitionSpecs says
where every parameter lives and GSPMD materializes exactly that shard per
device:

- column-parallel (q/k/v/gate/up): output features on 'tp'
- row-parallel (o/down): input features on 'tp'
- vocab-parallel (embedding, lm_head): vocab dim on 'tp'
- stacked decoder layers: leading layer axis on 'pp' (the reference's
  contiguous stage slices, ref: pipeline_parallel.py:42-51, as a sharding)
- norms: replicated over tp (sequence-parallel sharding is a future option)
- everything: replicated over dp and cp (they are data axes; ZeRO-style
  param sharding over dp is a deliberate non-goal for parity — SURVEY.md
  §2.2 marks FSDP absent in the reference)
"""

from __future__ import annotations

from typing import Any

import jax
from jax.sharding import NamedSharding, PartitionSpec as P

from picotron_tpu.config import Config


def param_specs(cfg: Config) -> dict[str, Any]:
    """PartitionSpec pytree matching models.llama.init_params' structure."""
    # layers % pp divisibility is enforced by Config.validate().
    pp = "pp" if cfg.distributed.pp_size > 1 else None
    # Megatron's pairing: column shards for the entry matmul of a class
    # (qkv, gate/up), row shards for its exit (o, down)
    col, row = P(pp, None, "tp"), P(pp, "tp", None)
    m = cfg.model

    def stack(block) -> dict[str, Any]:
        """One stack's specs (`ModelConfig.stacks`), by what its block is
        made of."""
        layers = {"input_norm": P(pp, None), "o": row,
                  "post_norm": P(pp, None)}
        if block.sandwich:
            layers.update({"attn_out_norm": P(pp, None),
                           "mlp_out_norm": P(pp, None)})
        if block.attn == "mla":
            # latent attention runs on one device (Config.validate refuses
            # tp / pp > 1): replicated, `o` included
            layers.update({n: P(None, None, None)
                           for n in ("q_a", "q_b", "kv_a", "kv_b", "o")})
            layers.update({"q_a_norm": P(None, None),
                           "kv_a_norm": P(None, None)})
        else:
            layers.update({"q": col, "k": col, "v": col})
        if block.attn == "eva":
            # EVA's pooling vectors [L, Hkv, D]: one device only
            layers.update({"eva_mu": P(None, None, None),
                           "eva_phi": P(None, None, None)})
        if m.attention_bias:
            # qkv biases shard over tp with their output features
            layers.update({"b_q": P(pp, "tp"), "b_k": P(pp, "tp"),
                           "b_v": P(pp, "tp")})
        if m.qk_norm:
            # q/k norm weights, whole-vector or per-head: replicated (tp = 1
            # is validated for both forms)
            layers.update({"q_norm": P(pp, None), "k_norm": P(pp, None)})
        if block.mlp == "experts":
            # expert banks [L, E, ...]: expert dim over 'ep', ffn dim over
            # 'tp' (column-parallel gate/up, row-parallel down — same as the
            # dense MLP); the router is small and replicated.
            layers.update({
                "router": P(pp, None, None),
                "w_gate": P(pp, "ep", None, "tp"),
                "w_up": P(pp, "ep", None, "tp"),
                "w_down": P(pp, "ep", "tp", None),
            })
            if m.n_shared_experts:
                layers.update({"shared_gate": col, "shared_up": col,
                               "shared_down": row})
        else:
            layers.update({"gate": col, "up": col, "down": row})
        return layers

    specs = {
        "embedding": P("tp", None),
        **{st.name: stack(st.block) for st in m.stacks},
        "final_norm": P(),
    }
    if not cfg.model.tie_word_embeddings:
        specs["lm_head"] = P(None, "tp")
    return specs


def batch_spec() -> P:
    """[n_micro, batch, seq] token blocks: batch over dp, sequence over cp
    (the contiguous CP split, ref: data.py:105-109, as a sharding)."""
    return P(None, ("dp", "ep"), "cp")


def param_shardings(cfg: Config, mesh,
                    memory_kind: str | None = None) -> dict[str, Any]:
    """NamedShardings for every param leaf. `memory_kind='pinned_host'`
    places the same shards in host RAM — the optimizer-offload home for the
    fp32 master and Adam moments (each shards exactly like its param, so a
    multi-chip topology splits the host-resident state across hosts too)."""
    kw = {} if memory_kind is None else {"memory_kind": memory_kind}
    return jax.tree.map(lambda s: NamedSharding(mesh, s, **kw),
                        param_specs(cfg),
                        is_leaf=lambda x: isinstance(x, P))
