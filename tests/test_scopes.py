"""Names a trace reduction can find after a refactor: every scope declared
in picotron_tpu/telemetry/scopes.py is on the name stack of some operation
of the programs it belongs to, and the programs the benchmark's cells time
carry pinned module names. Lowered here at tiny sizes on the CPU mesh; the
described-chip twin, with the Pallas kernels, is tests/test_chip_compile.py."""

import functools
import math
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from picotron_tpu.config import (
    Config, DistributedConfig, ModelConfig, ServeConfig, TrainingConfig,
    resolve_preset,
)
from picotron_tpu.mesh import MeshEnv
from picotron_tpu.models.llama import init_params
from picotron_tpu.parallel.api import init_sharded_state, make_train_step
from picotron_tpu.serve import ServeEngine
from picotron_tpu.telemetry.scopes import SCOPES, scope

TRAIN = {"embed", "attention", "mlp", "head_ce", "optimizer", "tp_reduce"}
SERVE = {"kv_write", "paged_attention", "sample", "mlp"}
seen: set = set()  # scopes found by the cases below, for the closing test


def scopes_in(text: str) -> set:
    """The declared scopes that are a word of some location's name stack
    (`jit(train_step)/jvp(mlp)/dot_general`)."""
    words = set()
    for path in re.findall(r'loc\("([^"]+)"', text):
        words.update(re.split(r"[/()]+", path))
    return words & set(SCOPES)


def module_name(text: str) -> str:
    return re.search(r"module @(\S+)", text).group(1)


MOE = {"moe_router", "moe_dispatch", "moe_experts"}


def train_cfg(engine: str, pp: int, moe: bool = False) -> Config:
    if moe:  # OLMoE-shaped: QK-norm and the dropless dispatch need tp = 1
        model = ModelConfig(**{**resolve_preset("debug-tiny-olmoe"),
                               "max_position_embeddings": 64})
    else:
        model = ModelConfig(num_attention_heads=8, num_key_value_heads=4,
                            num_hidden_layers=2, hidden_size=64,
                            intermediate_size=96, vocab_size=256,
                            max_position_embeddings=64, attention_bias=True)
    return Config(
        distributed=DistributedConfig(tp_size=1 if moe else 2, pp_size=pp,
                                      dp_size=1, pp_engine="1f1b"),
        model=model,
        training=TrainingConfig(grad_engine=engine, seq_length=32,
                                micro_batch_size=1,
                                gradient_accumulation_steps=2, remat=True,
                                remat_policy="dots_attn"))


@functools.lru_cache(maxsize=None)
def lowered_train_step(engine: str, pp: int, moe: bool):
    cfg = train_cfg(engine, pp, moe=moe)
    menv = MeshEnv.from_config(cfg)
    state = init_sharded_state(cfg, menv, jax.random.key(0), abstract=True)
    t = cfg.training
    b = jax.ShapeDtypeStruct(
        (t.gradient_accumulation_steps, t.micro_batch_size, t.seq_length),
        jnp.int32, sharding=menv.batch_sharding())
    text = make_train_step(cfg, menv).lower(state, (b, b)).as_text(debug_info=True)
    return cfg, text


@pytest.mark.parametrize("engine,pp,extra", [
    ("fused", 1, {"dw_accum"}),
    ("ad", 1, set()),
    ("ad", 2, {"pp_boundary"}),
    # PR 63: the 1F1B tick's backward unit runs the manual backward's scans
    ("fused", 2, {"dw_accum", "pp_boundary"}),
    ("fused", 1, {"dw_accum"} | MOE),
    ("ad", 1, MOE),
], ids=["fused", "ad", "ad-pp2", "fused-pp2", "fused-moe", "ad-moe"])
def test_train_step_scopes_and_module_name(engine, pp, extra):
    _, text = lowered_train_step(engine, pp, extra >= MOE)
    assert module_name(text) == "jit_train_step"
    found = scopes_in(text)
    assert found == TRAIN | extra
    seen.update(found)


@pytest.mark.parametrize("engine,pp,moe", [
    ("fused", 1, False), ("ad", 2, False), ("fused", 2, False),
    ("fused", 1, True),
], ids=["fused", "1f1b", "1f1b-fused", "fused-moe"])
def test_label_backward_is_under_head_ce(engine, pp, moe):
    """The label pick's backward (ops/losses.py) is a custom_vjp rule, traced
    where the engine applies the VJP and not where the forward's `head_ce`
    decorator was: the rule enters the scope itself, so its one
    vocabulary-sized operation, the compare against the iota, has `head_ce`
    as the innermost element of its name stack. Without it
    `head_ce_ms.train` would read a false gain."""
    cfg, text = lowered_train_step(engine, pp, moe)
    vshard = cfg.model.vocab_size // cfg.distributed.tp_size
    locs = dict(re.findall(r'^(#loc\d+) = loc\("([^"]+)"', text, re.M))
    compares = re.findall(
        rf"stablehlo\.compare\s+EQ,.*x{vshard}xi1> loc\((#loc\d+)\)", text)
    paths = [locs[c] for c in compares]
    assert paths and all(p.endswith("head_ce/eq") for p in paths), paths
    # and the step scatters into no copy of a microbatch's logits (the
    # gather's own transpose did; the described-chip twin of this count is
    # tests/test_chip_compile.py's)
    t = cfg.training
    logits = t.micro_batch_size * t.seq_length * vshard
    operands = re.findall(
        r'"stablehlo\.scatter"\(.*?\}\) : \(tensor<([\dx]+)x\w+>', text, re.S)
    assert operands and all(
        math.prod(map(int, o.split("x"))) != logits for o in operands), operands


@pytest.fixture(scope="module")
def engine():
    mcfg = ModelConfig(dtype="float32", **{
        **resolve_preset("debug-tiny"), "max_position_embeddings": 64})
    eng = ServeEngine(init_params(mcfg, jax.random.key(0)), mcfg,
                      ServeConfig(decode_slots=2, block_size=4, num_blocks=16,
                                  prefill_chunk=4, max_model_len=32,
                                  decode_interval=2), temperature=0.7, top_k=4)
    yield eng
    eng.close()


def lowered_serve(e, program: str) -> str:
    """The text of one of an engine's two programs, lowered on what the
    engine feeds it: the same call whatever the kind of its cache."""
    s = e.num_slots
    vec = jnp.zeros((s,), jnp.int32)
    common = dict(cfg=e.cfg, temperature=e.temperature, top_k=e.top_k,
                  cache_cls=type(e.cache))
    head = (e.params, e._kv, tuple(jnp.asarray(t) for t in e._tables))
    tail = (e.base_key, e.cos, e.sin)
    if program == "serve_prefill":
        lowered = e._prefill_jit.lower(
            *head, jnp.zeros((s, e.scfg.prefill_chunk), jnp.int32), vec, vec,
            vec, vec, *tail, **common)
    else:
        lowered = e._decode_jit.lower(*head, vec, vec, vec, vec, vec, *tail,
                                      interval=2, eos_token_id=None, **common)
    text = lowered.as_text(debug_info=True)
    assert module_name(text) == f"jit_{program}"
    return text


@pytest.mark.parametrize("program", ["serve_prefill", "serve_decode"])
def test_serve_program_scopes_and_module_names(engine, program):
    text = lowered_serve(engine, program)
    found = scopes_in(text)
    assert found == SERVE
    seen.update(found)


@pytest.mark.parametrize("program", ["serve_prefill", "serve_decode"])
def test_mixed_model_serve_program_scopes(program):
    """A model with experts and sliding-window layers: both serve programs
    carry the expert scopes and, inside `paged_attention`, one word a layer
    kind (`benchmark/layer_metrics/*.serve.json` read them)."""
    mcfg = ModelConfig(dtype="float32", **resolve_preset("debug-tiny-mellum2"))
    e = ServeEngine(init_params(mcfg, jax.random.key(0)), mcfg,
                    ServeConfig(decode_slots=2, block_size=4, prefill_chunk=4,
                                max_model_len=32, decode_interval=2))
    text = lowered_serve(e, program)
    e.close()
    found = scopes_in(text)
    assert found == SERVE | MOE | {"attn_full", "attn_window"}
    seen.update(found)


MLA = {"mla_q", "mla_kv_latent", "mla_absorb", "mla_o", "attn_latent", "moe_shared"}


# a model with one pool and one table a slot that is not the plain one: its
# preset, the scopes its serve programs carry, a prefill chunk it takes
ONE_TABLE = {
    "latent": ("debug-tiny-pangu-moe", SERVE | MOE | MLA, 4),
    "eva": ("debug-tiny-evabyte", SERVE | {"eva_summarise"}, 8),
    # a layer of two latent attentions with a shortcut-connected expert branch
    # and zero-compute experts: the branch's own scopes, and no shared expert
    "shortcut": ("debug-tiny-longcat",
                 SERVE | MOE | (MLA - {"moe_shared"}) | {"scmoe_branch", "moe_zero"}, 4),
    # Gated DeltaNet mixers over a state pool beside gated full attentions
    # over the K/V pool (a second table, of one column), a gated shared expert
    "hybrid": ("debug-tiny-qwen3-next",
               SERVE | MOE | {"gdn", "gdn_conv", "gdn_state", "attn_gate", "attn_full",
                              "moe_shared", "moe_shared_gate"}, 8),
    # Mamba mixers over a state pool beside unrotated multi-query attentions
    # over the K/V pool: a prefill chunk's recurrence under `ssm_scan`, a
    # decode step's under `ssm_step`
    "mamba": ("debug-tiny-jamba",
              {"serve_prefill": SERVE | {"ssm_mixer", "ssm_conv", "ssm_scan", "attn_full"},
               "serve_decode": SERVE | {"ssm_mixer", "ssm_conv", "ssm_step", "attn_full"}}, 8),
    # Kimi Delta Attention mixers over a state pool beside unrotated latent
    # attentions over a LATENT pool: a prefill chunk's recurrence under
    # `kda_chunk`, a decode step's under `kda_state`
    "kda": ("debug-tiny-kimi-linear",
            {"serve_prefill": SERVE | MOE | MLA | {"kda", "kda_conv", "kda_gate", "kda_chunk"},
             "serve_decode": SERVE | MOE | MLA | {"kda", "kda_conv", "kda_gate", "kda_state"}}, 8),
    # layers of ONE sublayer each: Mamba-2 mixers over a state pool, unrotated
    # attentions over the K/V pool and non-gated experts on a latent beside a
    # shared expert: a prefill chunk's recurrence under `ssd_chunk`, a decode
    # step's under `ssd_step`, the latent's two projections under `moe_latent`
    "mamba2": ("debug-tiny-nemotron-h",
               {"serve_prefill": SERVE | MOE | {"ssd_mixer", "ssd_conv", "ssd_chunk", "attn_full",
                                                "moe_shared", "moe_latent"},
                "serve_decode": SERVE | MOE | {"ssd_mixer", "ssd_conv", "ssd_step", "attn_full",
                                               "moe_shared", "moe_latent"}}, 8),
}


@pytest.mark.parametrize("program", ["serve_prefill", "serve_decode"])
@pytest.mark.parametrize("model", ONE_TABLE)
def test_latent_and_eva_model_serve_program_scopes(model, program):
    """A model with latent attention, a shared expert and a dense layer before
    its expert layers: both serve programs carry the latent scopes beside the
    expert scopes (`benchmark/layer_metrics/mla_*.serve.json` and
    `moe_shared_ms.serve.json` read them). A model with EVA attention: both
    carry `eva_summarise` (`eva_summarise_ms.serve.json` reads it), and its
    attention is under `paged_attention` like any other's. A model whose
    layers hold two latent attentions and a shortcut-connected expert branch:
    `scmoe_branch` and `moe_zero` beside them (`scmoe_branch_ms.serve.json`).
    A model of Gated DeltaNet mixers and gated attentions: `gdn` with
    `gdn_conv` and `gdn_state` inside it (`gdn_*.serve.json`), `attn_gate`,
    `moe_shared_gate`. A model of Mamba mixers: `ssm_mixer` with `ssm_conv`
    and the recurrence's scope of that program inside it (`ssm_*.serve.json`).
    A model of Kimi Delta Attention mixers and latent attentions: `kda` with
    `kda_conv`, `kda_gate` and the recurrence's scope of that program inside it
    (`kda_*.serve.json`) beside the latent scopes. A model whose layers are one
    sublayer each (Mamba-2 mixers, attentions, experts on a latent):
    `ssd_mixer` with `ssd_conv` and the recurrence's scope of that program
    inside it (`ssd_*.serve.json`), `moe_latent` (`moe_latent_ms.serve.json`)
    beside the expert scopes."""
    preset, want, chunk = ONE_TABLE[model]
    want = want[program] if isinstance(want, dict) else want
    mcfg = ModelConfig(dtype="float32", **resolve_preset(preset))
    e = ServeEngine(init_params(mcfg, jax.random.key(0)), mcfg,
                    ServeConfig(decode_slots=2, block_size=4, prefill_chunk=chunk,
                                max_model_len=32, decode_interval=2))
    text = lowered_serve(e, program)
    e.close()
    found = scopes_in(text)
    assert found == want
    seen.update(found)


def test_every_declared_scope_is_used_and_none_is_undeclared():
    """Runs after the cases above (same file, same worker)."""
    assert seen == set(SCOPES)
    with pytest.raises(ValueError):
        scope("atention")
    assert np.all([re.fullmatch(r"[a-z_]+", s) for s in SCOPES])
