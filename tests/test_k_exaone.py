"""K-EXAONE's mechanisms at the tiny preset (`debug-tiny-exaone-moe`) on the
CPU: a layer pattern cut over two stacks (a dense sliding layer, then S, S, F,
S expert layers), each stack scanning the whole periods of its own slice and
running what is left over after them, a window shorter than the prompt and
than the prefill chunk, per-head QK-norm, unrotated full layers, a shared
expert, sigmoid routing and a held share of the experts, through `forward()`,
`generate()` and `ServeEngine` with both pools. The program is held to
`benchmark/reference_k_exaone.py` (plain float32 jax.numpy, no cache, its own
band, norms and router), which imports nothing from it. The compiled kernels
are held by tests/test_chip_compile.py and tests/test_paged_attention.py."""

import importlib.util
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from picotron_tpu.config import (
    Config, DistributedConfig, ModelConfig, ServeConfig, TrainingConfig,
    model_config_from_hf_json, num_params, pattern_of, resolve_preset,
)
from picotron_tpu.generate import generate
from picotron_tpu.models.llama import (
    forward, init_params, mlp_act, param_count, shared_expert,
)
from picotron_tpu.ops.moe import moe_mlp_served
from picotron_tpu.serve import ServeEngine

# loaded by its path: `benchmark/` is not put on sys.path, where its own
# `tests` package would shadow this one
_spec = importlib.util.spec_from_file_location(
    "reference_k_exaone", os.path.join(os.path.dirname(__file__), "..", "benchmark",
                                       "reference_k_exaone.py"))
reference = importlib.util.module_from_spec(_spec)
_spec.loader.exec_module(reference)

S, F = "sliding_attention", "full_attention"
# 4 of 16 experts held, routed over all 16: the cell's arrangement in small
SHARES = {"whole": {}, "share": dict(num_experts=4, router_experts=16, expert_first=4)}
# the cut the chip runs (1 + 4), and one whose expert stack has whole periods AND
# layers left over after them: (S, S, F, S) + (S, S, F)
DEPTHS = {"1+4": {}, "1+7": dict(num_hidden_layers=8, layer_types=(S, S, S, F) * 2)}


def tiny(**over) -> ModelConfig:
    cfg = ModelConfig(dtype="float32", **{**resolve_preset("debug-tiny-exaone-moe"), **over})
    cfg.validate()
    return cfg


def weights(cfg, seed=1):
    p = init_params(cfg, jax.random.key(seed))
    # norm weights that are not all one, so that a skipped or misplaced norm shows,
    # and a trained model's embedding scale, so that the layers show in the logits
    for j, st in enumerate(cfg.stacks):
        norms = {n: 1.0 + 0.5 * jax.random.normal(jax.random.key(10 * j + i), p[st.name][n].shape)
                 for i, n in enumerate(("q_norm", "k_norm"))}
        p[st.name] = dict(p[st.name], **norms)
    return dict(p, embedding=p["embedding"] * 0.1)


def published(cfg: ModelConfig) -> dict:
    """The keys `reference_k_exaone` reads, from a ModelConfig."""
    return dict(
        vocab_size=cfg.vocab_size, hidden_size=cfg.hidden_size,
        num_hidden_layers=cfg.num_hidden_layers,
        num_attention_heads=cfg.num_attention_heads,
        num_key_value_heads=cfg.num_key_value_heads, head_dim=cfg.head_dim,
        rms_norm_eps=cfg.rms_norm_eps, layer_types=list(cfg.layer_kinds),
        sliding_window=cfg.sliding_window,
        rope_parameters=dict(rope_theta=cfg.rope_theta, rope_type="default"),
        first_k_dense_replace=cfg.first_k_dense_replace,
        intermediate_size=cfg.intermediate_size,
        moe_intermediate_size=cfg.moe_intermediate_size, num_experts=cfg.num_experts,
        num_shared_experts=cfg.n_shared_experts,
        num_experts_per_tok=cfg.num_experts_per_token, norm_topk_prob=cfg.norm_topk_prob,
        routed_scaling_factor=cfg.routed_scaling_factor, scoring_func=cfg.moe_scoring,
        tie_word_embeddings=cfg.tie_word_embeddings, router_experts=cfg.router_width,
        expert_first=cfg.expert_first)


def ref_logits(params, cfg, ids, rows=None, **faults):
    ids = jnp.asarray(ids, jnp.int32)
    rows = jnp.arange(ids.shape[0]) if rows is None else jnp.asarray(rows)
    return np.asarray(reference.logits_at(params, ids, rows, published(cfg), **faults))


# ---------------------------------------------------------------------------
# the pattern, cut where the stacks are
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("kinds,want", [
    ((F,) * 5, ((F,), 5, ())),
    ((S, S, S, F) * 2, ((S, S, S, F), 2, ())),
    ((S,), ((S,), 1, ())),
    ((S, S, F) + (S, S, S, F) * 11, ((S, S, F, S), 11, (S, S, F))),
    ((S, S, F, S, S, S, F), ((S, S, F, S), 1, (S, S, F))),
    ((S, S, F, S), ((S, S, F), 1, (S,))),
    ((S, F, F), ((S, F, F), 1, ())),
], ids=["one_kind", "mellum2", "one_layer", "k_exaone_47", "seven", "the_cut", "no_period"])
def test_pattern_of(kinds, want):
    period, whole, rest = pattern_of(kinds)
    assert (period, whole, rest) == want
    assert period * whole + rest == kinds and len(rest) < len(period)


def test_the_published_pattern_builds_and_its_kinds_match_layer_types():
    """All 48 layers at tiny widths: the stacks carry the published kinds
    between them, the expert stack one layer into the pattern, and the
    model runs (11 whole periods in a scan, three layers after it)."""
    kinds = (S, S, S, F) * 12
    cfg = tiny(num_hidden_layers=48, layer_types=kinds, num_experts=4,
               moe_intermediate_size=16, intermediate_size=32)
    dense, experts = cfg.stacks
    assert (dense.name, dense.layers, dense.kinds) == ("dense_layers", 1, (S,))
    assert (experts.name, experts.layers) == ("layers", 47)
    assert dense.kinds + experts.kinds == kinds == cfg.layer_kinds
    assert pattern_of(experts.kinds) == ((S, S, F, S), 11, (S, S, F))
    params = weights(cfg)
    assert params["layers"]["q"].shape[0] == 47 and param_count(params) == num_params(cfg)
    ids = jax.random.randint(jax.random.key(0), (1, 20), 0, cfg.vocab_size)
    with jax.default_matmul_precision("highest"):
        got = np.asarray(jax.jit(lambda p, i: forward(p, i, cfg))(params, ids))[0]
    np.testing.assert_allclose(got, ref_logits(params, cfg, ids[0]), atol=5e-5)
    # the published sizes themselves validate, pattern and stacks and all
    full = ModelConfig(**resolve_preset("K-EXAONE-236B-A23B"))
    full.validate()
    assert [st.layers for st in full.stacks] == [1, 47] and full.layer_kinds == kinds


def test_built_tree_has_two_stacks_and_the_counted_parameters():
    cfg = tiny()
    params = init_params(cfg, jax.random.key(0))
    assert [(st.name, st.kinds) for st in cfg.stacks] == [
        ("dense_layers", (S,)), ("layers", (S, S, F, S))]
    assert params["dense_layers"]["gate"].shape == (1, 64, 128)
    assert params["layers"]["w_gate"].shape == (4, 16, 64, 32)
    # one norm vector a layer for all the heads of q, one for k
    assert params["layers"]["q_norm"].shape == params["layers"]["k_norm"].shape == (4, 32)
    assert param_count(params) == num_params(cfg)
    share = tiny(**SHARES["share"])
    assert param_count(init_params(share, jax.random.key(0))) == num_params(share)


def test_published_sizes_count_236b_and_the_cut():
    cfg = ModelConfig(**resolve_preset("K-EXAONE-236B-A23B"))
    assert 235e9 < num_params(cfg) < 238e9
    assert 22e9 < num_params(cfg, active_only=True) < 24e9
    # one chip's share at the benchmark's cut (ISSUE 39's arithmetic + the norms)
    cut = ModelConfig(**{**resolve_preset("K-EXAONE-236B-A23B"), "num_hidden_layers": 5,
                         "layer_types": (S, S, S, F, S), "num_experts": 16,
                         "router_experts": 128, "vocab_size": 19200})
    matrices = 452_984_832 + 4 * 755_761_152 + 235_929_600
    assert matrices == 3_711_959_040
    assert num_params(cut) == matrices + 5 * (2 * 6144 + 2 * 128) + 6144


# ---------------------------------------------------------------------------
# the model against the reference
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("depth", DEPTHS)
@pytest.mark.parametrize("share", SHARES)
def test_forward_matches_the_reference(share, depth):
    cfg = tiny(**SHARES[share], **DEPTHS[depth])
    params = weights(cfg)
    ids = jax.random.randint(jax.random.key(2), (2, 40), 0, cfg.vocab_size)
    with jax.default_matmul_precision("highest"):
        got = np.asarray(jax.jit(lambda p, i: forward(p, i, cfg))(params, ids))
    for b in range(2):
        want = ref_logits(params, cfg, ids[b])
        # float32 both sides at `highest`: what is left is the order of the sums
        np.testing.assert_allclose(got[b], want, atol=3e-5)
    assert np.abs(want).max() > 1.0  # the layers show


@pytest.mark.parametrize("fault", reference.FAULTS)
def test_each_of_the_probes_faults_moves_the_reference(fault):
    """What the tolerance probe's controls leave out is in the mathematics:
    the reference with the fault differs from the program by far more than
    the program differs from the sound reference (40 positions > window 8)."""
    cfg = tiny(**SHARES["share"])
    params = weights(cfg)
    ids = jax.random.randint(jax.random.key(2), (40,), 0, cfg.vocab_size)
    sound = ref_logits(params, cfg, ids)
    assert np.abs(ref_logits(params, cfg, ids, **{fault: True}) - sound).max() > 1e-2


def test_per_head_and_whole_vector_qk_norm_differ():
    """One weight vector over each head's 32 numbers against OLMoE's norm
    over the whole projection: other parameters, other numbers."""
    head, whole = tiny(), tiny(qk_norm=True)
    ids = jax.random.randint(jax.random.key(3), (1, 24), 0, 256)
    p_head, p_whole = weights(head), init_params(whole, jax.random.key(1))
    assert p_whole["layers"]["q_norm"].shape == (4, 4 * 32)
    assert p_whole["layers"]["k_norm"].shape == (4, 2 * 32)
    # the same matrices under both norms, the norm weights all one
    for name in ("dense_layers", "layers"):
        p_whole[name] = {n: (w if n in ("q_norm", "k_norm") else p_head[name][n])
                         for n, w in p_whole[name].items()}
        p_head[name] = dict(p_head[name], q_norm=jnp.ones_like(p_head[name]["q_norm"]),
                            k_norm=jnp.ones_like(p_head[name]["k_norm"]))
    p_whole["embedding"] = p_head["embedding"]
    a, b = forward(p_head, ids, head), forward(p_whole, ids, whole)
    assert np.abs(np.asarray(a) - np.asarray(b)).max() > 1e-2
    with pytest.raises(ValueError, match="qk_norm must be"):
        tiny(qk_norm="row")


def test_a_full_layer_unrotated_and_rotated_differ():
    plain = tiny()
    rotated = tiny(rope_parameters=dict(
        sliding_attention=dict(rope_type="default", rope_theta=10000.0),
        full_attention=dict(rope_type="default", rope_theta=10000.0)))
    params = weights(plain)
    ids = jax.random.randint(jax.random.key(3), (1, 24), 0, 256)
    with jax.default_matmul_precision("highest"):
        a, b = forward(params, ids, plain), forward(params, ids, rotated)
    assert np.abs(np.asarray(a) - np.asarray(b)).max() > 1e-2
    np.testing.assert_allclose(np.asarray(b)[0], ref_logits(params, plain, ids[0],
                                                            full_rotated=True), atol=3e-5)
    # the law 'none' is the identity's tables: no caller branches on it
    from picotron_tpu.models.llama import model_rope_tables
    cos, sin = model_rope_tables(plain, max_len=16)
    assert (np.asarray(cos[F]) == 1).all() and (np.asarray(sin[F]) == 0).all()
    assert np.asarray(sin[S]).any()


@pytest.mark.parametrize("depth", DEPTHS)
def test_ad_runs_through_both_stacks_and_the_layers_left_over(depth):
    """`forward()` under AD (the one training path that takes the model):
    every layer of both stacks gets a gradient, the layers after the scan
    among them, and rematerialisation changes none of it."""
    from picotron_tpu.models.llama import ParallelCtx, loss_fn

    cfg = tiny(**DEPTHS[depth])
    params = weights(cfg)
    ids = jax.random.randint(jax.random.key(1), (2, 24), 0, cfg.vocab_size)
    # (jitted: eagerly each layer scan compiles anew, a minute a depth)
    plain = jax.jit(jax.grad(lambda p: loss_fn(p, ids, ids, cfg)))(params)
    remat = jax.jit(jax.grad(lambda p: loss_fn(
        p, ids, ids, cfg, ParallelCtx(remat=True, remat_policy="dots"))))(params)
    for st in cfg.stacks:
        per_layer = np.abs(np.asarray(plain[st.name]["q"])).max(axis=(1, 2))
        assert per_layer.shape == (st.layers,) and (per_layer > 0).all()
    for a, b in zip(jax.tree.leaves(plain), jax.tree.leaves(remat)):
        np.testing.assert_allclose(a, b, atol=1e-6)


@pytest.mark.parametrize("depth", DEPTHS)
def test_generate_matches_the_reference(depth):
    """Prefill, then decode through the contiguous cache (the band a mask):
    every generated token is the reference's argmax under teacher forcing,
    past the window."""
    cfg = tiny(**SHARES["share"], **DEPTHS[depth])
    params = weights(cfg)
    ids = jax.random.randint(jax.random.key(3), (2, 12), 0, cfg.vocab_size)
    out = np.asarray(generate(params, cfg, ids, 8))
    for b in range(2):
        want = ref_logits(params, cfg, out[b], rows=range(11, 19))
        assert (out[b, 12:] == want.argmax(-1)).all()


def run_engine(params, cfg, requests, **over):
    scfg = ServeConfig(**{**dict(decode_slots=2, block_size=4, prefill_chunk=16,
                                 max_model_len=64, decode_interval=2), **over})
    eng = ServeEngine(params, cfg, scfg)
    out = eng.run(requests)
    eng.close()
    assert (eng.pool.in_use, eng.wpool.in_use) == (0, 0)
    return eng, out


@pytest.mark.parametrize("share,depth,chunk", [
    ("share", "1+4", 16), ("whole", "1+4", 16), ("share", "1+7", 16), ("share", "1+4", 4)])
def test_engine_matches_the_reference(share, depth, chunk):
    """Chunked prefill on the rungs (a chunk of 16 > the window of 8, and
    one of 4 < it), then decode through both pools, rings wrapping: each
    served token's logit against the reference's full forward under teacher
    forcing. At 1 + 7 the expert stack's leftover layers are exercised."""
    cfg = tiny(**SHARES[share], **DEPTHS[depth])
    params = weights(cfg)
    rng = np.random.default_rng(5)
    requests = [(list(map(int, rng.integers(0, cfg.vocab_size, size=n))), m)
                for n, m in ((37, 8), (6, 5), (21, 7), (45, 4))]
    eng, out = run_engine(params, cfg, requests, prefill_chunk=chunk)
    assert len(out) == 4 and eng.stats["decode_compiles"] <= 1  # the one decode program
    assert eng.sched.ring_blocks == -(-(8 + chunk) // 4) + 1
    for (prompt, _), res in zip(requests, sorted(out, key=lambda r: r["id"])):
        toks = res["tokens"]
        want = ref_logits(params, cfg, prompt + toks,
                          rows=range(len(prompt) - 1, len(prompt) + len(toks) - 1))
        assert (want.argmax(-1) == np.asarray(toks)).all()
        # float32 on the CPU: the tiled online softmax against one softmax a row
        np.testing.assert_allclose(res["logits"], want[np.arange(len(toks)), toks], atol=2e-4)
    # the expert counters and the window counters ride one program's spans
    if share == "share":
        assert 0 < eng.stats["picks_here"] < eng.stats["picks_all"]
    else:
        assert eng.stats["picks_here"] == eng.stats["picks_all"] > 0
    n_expert_layers = cfg.num_hidden_layers - 1
    assert eng.stats["expert_slots"] % (n_expert_layers * cfg.num_experts) == 0
    assert 0 < eng.stats["experts_touched"] <= eng.stats["expert_slots"]
    # the pools hold the layers of their kind, of both stacks
    n_full = cfg.layer_kinds.count(F)
    k, wk, _, _ = eng._kv
    assert k.shape[1] == n_full and wk.shape[1] == cfg.num_hidden_layers - n_full


def test_engine_counts_banded_reads_over_both_stacks():
    cfg = tiny()
    eng = ServeEngine(weights(cfg), cfg, ServeConfig(
        decode_slots=2, block_size=4, prefill_chunk=16, max_model_len=64, decode_interval=2))
    eng.submit(list(range(1, 30)), 4)
    while eng.sched.slots[0] is None or not eng.sched.slots[0].generated:
        eng.step(0.0)
    counts = eng.cache.decode_counts([(eng.sched.slots[0].write_pos, 2)], cfg)
    assert counts["kv_blocks"] == 8
    # 1 full layer reads every block, the 4 sliding ones (the dense layer among
    # them) the band's
    assert counts["kv_blocks_full"] == 8 and counts["kv_blocks_unwindowed"] == 5 * 8
    assert counts["kv_blocks_window"] % 4 == 0 and counts["kv_blocks_window"] <= 4 * 3
    eng.close()


def test_engine_agrees_with_generate():
    cfg = tiny()
    params = weights(cfg)
    prompt = list(map(int, np.random.default_rng(1).integers(0, 256, size=19)))
    _, out = run_engine(params, cfg, [(prompt, 9)])
    want = np.asarray(generate(params, cfg, jnp.asarray([prompt]), 9))[0, 19:]
    assert out[0]["tokens"] == list(map(int, want))


# ---------------------------------------------------------------------------
# the held share of the experts (the guide's section 4)
# ---------------------------------------------------------------------------


def test_eight_shares_add_up_to_the_uncut_layer():
    """The routed parts of all 8 shares (2 of 16 experts each) plus the
    shared expert once are the uncut reference's expert layer."""
    cfg = tiny()
    params = weights(cfg)
    lp = {n: w[1] for n, w in params["layers"].items()}
    x = jax.random.normal(jax.random.key(4), (2, 9, cfg.hidden_size), jnp.float32)
    live = jnp.ones((2, 9), bool)
    total = shared_expert(x, lp, cfg)
    seen = 0
    with jax.default_matmul_precision("highest"):
        for first in range(0, 16, 2):
            held = {n: params["layers"][n][:, first:first + 2]
                    for n in ("w_gate", "w_up", "w_down")}
            routed, counts = moe_mlp_served(
                x, lp["router"], held["w_gate"], held["w_up"], held["w_down"],
                top_k=2, act=mlp_act(cfg), norm_topk_prob=True, live=live, layer=1,
                scoring="sigmoid", scale=2.5, expert_first=first)
            total = total + routed
            seen += int(counts[2])
            assert int(counts[3]) == 2 * 9 * 2
        assert seen == 2 * 9 * 2  # every pick lands on exactly one share
        want = reference._experts(x.reshape(18, -1), lp, published(cfg),
                                  frozenset()).reshape(x.shape)
    np.testing.assert_allclose(total, want, atol=2e-5)


# ---------------------------------------------------------------------------
# configuration: the published keys, and what is refused by name
# ---------------------------------------------------------------------------

HF = {  # the catalog row's `config`, as published
    "first_k_dense_replace": 1, "head_dim": 128, "hidden_act": "silu", "hidden_size": 6144,
    "intermediate_size": 18432, "layer_types": [S, S, S, F] * 12,
    "max_position_embeddings": 262144, "mlp_layer_types": ["dense"] + ["sparse"] * 47,
    "model_type": "exaone_moe", "moe_intermediate_size": 2048,
    "mtp_layer_types": [F], "mtp_sliding_windows": [0], "n_group": 1,
    "norm_topk_prob": True, "num_attention_heads": 64, "num_experts": 128,
    "num_experts_per_tok": 8, "num_hidden_layers": 48, "num_key_value_heads": 8,
    "num_nextn_predict_layers": 1, "num_shared_experts": 1, "rms_norm_eps": 1e-05,
    "rope_parameters": {"rope_theta": 1000000, "rope_type": "default"},
    "routed_scaling_factor": 2.5, "scoring_func": "sigmoid", "sliding_window": 128,
    "sliding_window_pattern": "LLLG", "sliding_windows": [128, 128, 128, 0] * 12,
    "tie_word_embeddings": False, "topk_group": 1, "vocab_size": 153600}


def test_hf_reader_round_trips_the_published_keys():
    got = ModelConfig(**model_config_from_hf_json(HF))
    got.validate()
    want = ModelConfig(**resolve_preset("K-EXAONE-236B-A23B"))
    assert got == ModelConfig(**{**want.__dict__, "name": got.name})
    assert (got.first_k_dense_replace, got.num_experts, got.num_experts_per_token,
            got.n_shared_experts, got.sliding_window) == (1, 128, 8, 1, 128)
    assert (got.moe_scoring, got.routed_scaling_factor, got.qk_norm) == ("sigmoid", 2.5, "head")
    assert got.rope_law(S) == (1e6, None)
    assert got.rope_law(F)[1] == {"rope_type": "none"}


@pytest.mark.parametrize("change,match", [
    (dict(n_group=8, topk_group=4), "n_group"),
    (dict(sliding_windows=[128, 128, 0, 0] * 12), "sliding_windows"),
    (dict(mlp_layer_types=["sparse", "dense"] + ["sparse"] * 46), "mlp_layer_types"),
])
def test_hf_reader_refuses_what_is_not_built(change, match):
    with pytest.raises(ValueError, match=match):
        model_config_from_hf_json(dict(HF, **change))


REFUSED = {
    "flash": dict(model=dict(attn_impl="flash")),
    "ring": dict(model=dict(attn_impl="ring"), distributed=dict(cp_size=2)),
    "cp": dict(distributed=dict(cp_size=2)),
    "fused": dict(training=dict(grad_engine="fused")),
    "pp": dict(distributed=dict(pp_size=2)),
    "tp": dict(distributed=dict(tp_size=2)),
    "ep": dict(distributed=dict(ep_size=2)),
    "fleet": dict(serve=dict(fleet_size=2)),
}


@pytest.mark.parametrize("path", REFUSED)
def test_paths_that_cannot_run_the_model_refuse_it_by_name(path):
    over = REFUSED[path]
    cfg = Config(
        distributed=DistributedConfig(**over.get("distributed", {})),
        model=ModelConfig(**{**resolve_preset("debug-tiny-exaone-moe"),
                             "attn_impl": "reference", **over.get("model", {})}),
        training=TrainingConfig(seq_length=64, **over.get("training", {})),
        serve=ServeConfig(**over.get("serve", {})))
    with pytest.raises(ValueError, match="sliding_attention|MoE|model has"):
        cfg.validate()


@pytest.mark.parametrize("feature,over", [
    ("per-head QK-norm", dict(qk_norm="head")),
    ("not rotated", dict(layer_types=(S, F, S, F), sliding_window=8, rope_parameters=dict(
        sliding_attention=dict(rope_type="default"), full_attention=dict(rope_type="none")))),
])
def test_each_new_feature_is_fenced_by_its_own_name(feature, over):
    """Without the experts and the pattern that other fences catch first:
    a dense model with only this feature is still refused, by name, on a
    path that has never run it."""
    model = ModelConfig(**{**resolve_preset("debug-tiny"), "attn_impl": "reference", **over})
    for dist in (dict(tp_size=2), dict(pp_size=2)):
        cfg = Config(distributed=DistributedConfig(**dist), model=model,
                     training=TrainingConfig(seq_length=64))
        if "layer_types" in over:
            with pytest.raises(ValueError, match="sliding_attention"):
                cfg.validate()
            continue
        with pytest.raises(ValueError, match=feature):
            cfg.validate()
    Config(model=model, training=TrainingConfig(seq_length=64)).validate()
