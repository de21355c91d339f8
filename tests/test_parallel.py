"""Numerical-equivalence tests: every parallel layout must compute the same
model as the single-device baseline (the reference's strongest implicit
invariant, SURVEY.md §7 step 9; its TP test does the same against an
unsharded nn.Linear, ref: tests/test_tensor_parallel.py)."""

from functools import partial

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import NamedSharding, PartitionSpec as P

from picotron_tpu import compat
from picotron_tpu.config import Config, DistributedConfig, ModelConfig, TrainingConfig
from picotron_tpu.mesh import MeshEnv
from picotron_tpu.models.llama import (
    forward, init_params, pad_layers_for_pp, pp_layer_placement, unpad_layers,
)
from picotron_tpu.ops.losses import IGNORE_INDEX, cross_entropy, pick_label
from picotron_tpu.parallel.api import init_sharded_state, make_train_step
from picotron_tpu.parallel.tp import (
    vocab_parallel_ce, vocab_parallel_ce_sum_count, vocab_parallel_embed,
)
from picotron_tpu.train_step import init_train_state, make_train_step as make_single_step


def tiny_cfg(**dist) -> Config:
    gas = dist.pop("gas", 2)
    layers = dist.pop("layers", 4)
    attn_impl = dist.pop("attn_impl", "auto")
    return Config(
        distributed=DistributedConfig(**dist),
        # 8 q heads / 4 kv heads so GQA survives tp up to 4
        model=ModelConfig(dtype="float32", num_attention_heads=8,
                          num_key_value_heads=4, num_hidden_layers=layers,
                          attn_impl=attn_impl),
        training=TrainingConfig(seq_length=32, micro_batch_size=2,
                                gradient_accumulation_steps=gas,
                                learning_rate=1e-3, remat=False),
    )


def global_batch(cfg, key=0):
    """(ids, targets) [n_micro, dp*mbs, seq] — same global content for every
    layout."""
    t = cfg.training
    b_global = t.micro_batch_size * cfg.distributed.dp_size
    toks = jax.random.randint(jax.random.key(key),
                              (t.gradient_accumulation_steps, b_global,
                               t.seq_length + 1),
                              0, cfg.model.vocab_size)
    return toks[..., :-1], toks[..., 1:]


def run_parallel(cfg, steps=3):
    from picotron_tpu.data import cp_sequence_permutation

    menv = MeshEnv.from_config(cfg)
    state = init_sharded_state(cfg, menv, jax.random.key(0))
    step = make_train_step(cfg, menv)
    sh = NamedSharding(menv.mesh, P(None, "dp", "cp"))
    ids, tgt = global_batch(cfg)
    perm = cp_sequence_permutation(cfg)
    if perm is not None:
        # mirror the dataloader's zigzag reorder (the parity invariant: the
        # permuted layout must train identically to the single-device run)
        ids, tgt = ids[..., perm], tgt[..., perm]
    batch = (jax.device_put(ids, sh), jax.device_put(tgt, sh))
    losses = []
    for _ in range(steps):
        state, metrics = step(state, batch)
        losses.append(float(metrics["loss"]))
    return losses, state


def run_single(cfg_parallel, steps=3):
    """Single-device ground truth on the same global batch."""
    cfg = Config(model=cfg_parallel.model,
                 training=cfg_parallel.training)
    params = init_params(cfg.model, jax.random.key(0))
    state = init_train_state(cfg, params)
    step = jax.jit(make_single_step(cfg))
    batch = global_batch(cfg_parallel)
    losses = []
    for _ in range(steps):
        state, loss = step(state, batch)
        losses.append(float(loss))
    return losses, state


@pytest.mark.parametrize("dist", [
    # Pruned to one sweep entry per axis COMBINATION (r5, VERDICT r4 #8):
    # e.g. dp2xtp4 fell to dp2xtp2 + tp4, dp2xpp2 to dp2xpp2xcp2 +
    # dp2xpp2xtp2 — every axis pair below is still covered by exactly one
    # surviving entry, and single-axis cases stay.
    dict(dp_size=8),
    dict(tp_size=4),
    dict(dp_size=2, tp_size=2),
    dict(cp_size=4),
    dict(cp_size=4, cp_layout="contiguous"),
    dict(dp_size=2, cp_size=2, tp_size=2),
    dict(pp_size=2),
    dict(pp_size=2, tp_size=2),
    dict(pp_size=4, gas=4),
    dict(pp_size=4, gas=4, pp_engine="afab"),
    # uneven layer splits: 5 layers pad to 6/8 slots, remainder to early
    # stages (ref: pipeline_parallel.py:42-51)
    dict(pp_size=2, layers=5, pp_engine="afab"),
    dict(pp_size=4, layers=5, gas=4, tp_size=2),
    dict(dp_size=2, pp_size=2, cp_size=2),
    dict(dp_size=2, pp_size=2, tp_size=2),
    # Ulysses all-to-all sequence parallelism: head-scatter instead of the
    # K/V ring, same numbers (zigzag layout still applies)
    dict(cp_size=4, attn_impl="ulysses"),
    dict(cp_size=2, tp_size=2, attn_impl="ulysses"),
    dict(cp_size=2, tp_size=2, attn_impl="ulysses", sequence_parallel=True),
    # Megatron-style sequence parallelism over tp (seq-sharded residual
    # stream, all_gather/reduce-scatter f/g) must be numerically invisible
    dict(tp_size=4, sequence_parallel=True),
    dict(dp_size=2, tp_size=2, sequence_parallel=True, cp_size=2),
    dict(pp_size=2, tp_size=2, sequence_parallel=True),
    dict(pp_size=2, tp_size=2, sequence_parallel=True, pp_engine="afab"),
])
def test_layouts_match_single_device(dist):
    cfg = tiny_cfg(**dist)
    par_losses, par_state = run_parallel(cfg)
    ref_losses, ref_state = run_single(cfg)
    np.testing.assert_allclose(par_losses, ref_losses, rtol=2e-4, atol=2e-5)
    par_params = unpad_layers(par_state.params, cfg.model.num_hidden_layers,
                              cfg.distributed.pp_size)
    # Parameters after 3 updates agree. Tolerance note: Adam divides by
    # sqrt(v) which amplifies fp32 reduction-order differences between the
    # sharded and dense reductions during the first steps, so this is
    # necessarily looser than the loss check.
    q_par = np.asarray(par_params["layers"]["q"])
    q_ref = np.asarray(ref_state.params["layers"]["q"])
    np.testing.assert_allclose(q_par, q_ref, rtol=2e-2, atol=1e-3)
    emb_par = np.asarray(par_state.params["embedding"])
    emb_ref = np.asarray(ref_state.params["embedding"])
    np.testing.assert_allclose(emb_par, emb_ref, rtol=2e-2, atol=1e-3)


def test_pp_layer_placement_remainder_to_early_stages():
    # 5 layers on pp=4: stages get 2,1,1,1 (ref: pipeline_parallel.py:42-51)
    padded, slots = pp_layer_placement(5, 4)
    assert padded == 8
    assert slots.tolist() == [0, 1, 2, 4, 6]  # per-stage leading slots
    padded, slots = pp_layer_placement(4, 2)  # even split: canonical
    assert padded == 4 and slots.tolist() == [0, 1, 2, 3]


def test_zero_padded_layers_are_identity():
    """The uneven-PP padding contract: all-zero layer slots change neither
    the forward values nor any real parameter's gradient."""
    from picotron_tpu.ops.losses import cross_entropy

    cfg = ModelConfig(dtype="float32", num_hidden_layers=5,
                      num_attention_heads=8, num_key_value_heads=4)
    params = init_params(cfg, jax.random.key(0))
    padded = pad_layers_for_pp(params, 5, 2)
    assert padded["layers"]["q"].shape[0] == 6
    ids = jax.random.randint(jax.random.key(1), (2, 16), 0, cfg.vocab_size)
    tgt = jax.random.randint(jax.random.key(2), (2, 16), 0, cfg.vocab_size)
    np.testing.assert_allclose(
        np.asarray(forward(params, ids, cfg)),
        np.asarray(forward(padded, ids, cfg)), rtol=1e-6)

    def loss(p):
        return cross_entropy(forward(p, ids, cfg), tgt)

    g_pad = jax.grad(loss)(padded)
    g_ref = jax.grad(loss)(params)
    # pad slots get exactly-zero grads; real slots match the unpadded grads
    jax.tree.map(
        lambda gp, gr: np.testing.assert_allclose(
            np.asarray(unpad_layers({"layers": {"x": gp}}, 5, 2)["layers"]["x"]),
            np.asarray(gr), rtol=1e-5, atol=1e-7),
        g_pad["layers"], g_ref["layers"])
    slots_set = set(pp_layer_placement(5, 2)[1].tolist())
    pad_slots = [i for i in range(6) if i not in slots_set]
    for leaf in jax.tree.leaves(g_pad["layers"]):
        assert np.all(np.asarray(leaf)[pad_slots] == 0.0)


def test_vocab_parallel_embed_matches_lookup():
    menv = MeshEnv.create(tp=8)
    w = jax.random.normal(jax.random.key(0), (64, 16))
    ids = jax.random.randint(jax.random.key(1), (2, 8), 0, 64)

    out = jax.jit(compat.shard_map(
        vocab_parallel_embed, mesh=menv.mesh,
        in_specs=(P("tp", None), P()), out_specs=P(),
    ))(w, ids)
    np.testing.assert_allclose(np.asarray(out), np.asarray(w[ids]), rtol=1e-6)


def test_vocab_parallel_ce_matches_dense():
    menv = MeshEnv.create(tp=8)
    h = jax.random.normal(jax.random.key(0), (2, 8, 16))
    head = jax.random.normal(jax.random.key(1), (16, 64))
    tgt = jax.random.randint(jax.random.key(2), (2, 8), 0, 64)
    tgt = tgt.at[0, :2].set(-100)  # exercise ignore_index

    loss = jax.jit(compat.shard_map(
        vocab_parallel_ce, mesh=menv.mesh,
        in_specs=(P(), P(None, "tp"), P()), out_specs=P(),
    ))(h, head, tgt)
    want = cross_entropy(h @ head, tgt)
    np.testing.assert_allclose(float(loss), float(want), rtol=1e-5)


def test_vocab_parallel_ce_grad_matches_dense():
    menv = MeshEnv.create(tp=8)
    h = jax.random.normal(jax.random.key(0), (2, 8, 16))
    head = jax.random.normal(jax.random.key(1), (16, 64))
    tgt = jax.random.randint(jax.random.key(2), (2, 8), 0, 64)

    def sharded_loss(h, head):
        return vocab_parallel_ce(h, head, tgt)

    g_par = jax.jit(compat.shard_map(
        jax.grad(sharded_loss, argnums=(0, 1)), mesh=menv.mesh,
        in_specs=(P(), P(None, "tp")), out_specs=(P(), P(None, "tp")),
    ))(h, head)
    g_ref = jax.grad(lambda h, w: cross_entropy(h @ w, tgt), argnums=(0, 1))(h, head)
    np.testing.assert_allclose(np.asarray(g_par[0]), np.asarray(g_ref[0]),
                               rtol=1e-5, atol=1e-6)
    np.testing.assert_allclose(np.asarray(g_par[1]), np.asarray(g_ref[1]),
                               rtol=1e-5, atol=1e-6)


# ---------------------------------------------------------------------------
# the label pick's hand-written backward (ops/losses.py pick_label): a
# compare against an iota where the gather's own transpose scatters into a
# zero-filled copy of the logits
# ---------------------------------------------------------------------------


def _gather_pick(logits, rel):
    """The pick as it was before pick_label, with the gather's own
    transpose: what dlogits must equal to the bit."""
    v = logits.shape[-1]
    ok = (rel >= 0) & (rel < v)
    relc = jnp.clip(rel, 0, v - 1)
    return (jnp.take_along_axis(logits, relc[..., None], axis=-1)
            .squeeze(-1) * ok.astype(jnp.float32))


@pytest.mark.parametrize("tied", [False, True], ids=["untied", "tied"])
@pytest.mark.parametrize("tp", [1, 2])
@pytest.mark.parametrize("chunk", [0, 8])
def test_label_pick_backward_matches_dense_grad(tied, tp, chunk):
    """Hidden and head gradients of the vocab-parallel CE, through the
    label pick's hand-written backward, against plain jax.grad of the dense
    loss: tied (the [V, H] embedding, transposed) and untied head, one
    shard and two (a label off this shard), the fused branch and the
    chunked (8 divides both shards), rows with IGNORE_INDEX."""
    menv = MeshEnv.create(tp=tp)
    h = jax.random.normal(jax.random.key(0), (2, 8, 16))
    w = jax.random.normal(jax.random.key(1), (64, 16) if tied else (16, 64))
    tgt = jax.random.randint(jax.random.key(2), (2, 8), 0, 64)
    tgt = tgt.at[0, :2].set(IGNORE_INDEX).at[1, 5].set(IGNORE_INDEX)
    # labels on both shards, and the first and last column of each
    tgt = tgt.at[1, :4].set(jnp.array([0, 31, 32, 63]))
    head = (lambda p: p.T) if tied else (lambda p: p)

    def sharded_loss(h, p):
        total, count = vocab_parallel_ce_sum_count(h, head(p), tgt,
                                                   chunk_size=chunk)
        return total / jnp.maximum(count, 1)

    w_spec = P("tp", None) if tied else P(None, "tp")
    loss, g_par = jax.jit(compat.shard_map(
        jax.value_and_grad(sharded_loss, argnums=(0, 1)), mesh=menv.mesh,
        in_specs=(P(), w_spec), out_specs=(P(), (P(), w_spec)),
    ))(h, w)
    want, g_ref = jax.value_and_grad(
        lambda h, p: cross_entropy(h @ head(p), tgt), argnums=(0, 1))(h, w)
    np.testing.assert_allclose(float(loss), float(want), rtol=1e-5)
    for got, ref in zip(g_par, g_ref):
        np.testing.assert_allclose(np.asarray(got), np.asarray(ref),
                                   rtol=1e-5, atol=1e-6)


@pytest.mark.parametrize("lo,width", [(0, 64), (32, 32), (40, 8)],
                         ids=["whole-vocab", "second-shard", "chunk"])
def test_label_pick_dlogits_equal_the_gather_transpose_to_the_bit(lo, width):
    """dlogits of one shard's (or chunk's) softmax statistics: the sum of
    the same two terms, whichever way the label term is formed. Labels
    below, inside and above the window, and both of its edges."""
    logits = 4.0 * jax.random.normal(jax.random.key(3), (3, 16, width))
    tgt = jax.random.randint(jax.random.key(4), (3, 16), 0, 64)
    tgt = tgt.at[0, :4].set(jnp.array([lo, lo + width - 1, 0, 63]))
    g_se = jax.random.normal(jax.random.key(5), (3, 16))
    g_lab = jax.random.normal(jax.random.key(6), (3, 16))

    def stats(pick, lg):
        m = jax.lax.stop_gradient(jnp.max(lg, axis=-1))
        se = jnp.sum(jnp.exp(lg - m[..., None]), axis=-1)
        return jnp.sum(se * g_se) + jnp.sum(pick(lg, tgt - lo) * g_lab)

    val, got = jax.jit(jax.value_and_grad(partial(stats, pick_label)))(logits)
    val0, want = jax.jit(jax.value_and_grad(partial(stats, _gather_pick)))(logits)
    assert float(val) == float(val0)
    hit = np.asarray((tgt >= lo) & (tgt < lo + width))
    assert hit.any() and (width == 64 or not hit.all())
    np.testing.assert_array_equal(np.asarray(got).view(np.uint32),
                                  np.asarray(want).view(np.uint32))


def test_zero1_moments_sharded_and_parity():
    """ZeRO-1: moments shard over dp; training is numerically identical to
    the unsharded-optimizer run (GSPMD inserts the per-shard update +
    all-gather; the math never changes)."""
    cfg = tiny_cfg(dp_size=4, zero1=True)
    par_losses, par_state = run_parallel(cfg)
    ref_losses, ref_state = run_single(cfg)
    np.testing.assert_allclose(par_losses, ref_losses, rtol=2e-4, atol=2e-5)

    # some moment leaf matching the q weight's shape must be dp-sharded
    q_shape = par_state.params["layers"]["q"].shape
    moment_specs = [
        leaf.sharding.spec for leaf in jax.tree.leaves(par_state.opt_state)
        if getattr(leaf, "shape", None) == q_shape]
    assert moment_specs, "no Adam moment found for the q weight"

    def flat_axes(spec):
        return [a for part in spec if part is not None
                for a in (part if isinstance(part, (tuple, list)) else (part,))]

    assert all("dp" in flat_axes(s) for s in moment_specs), moment_specs


def test_zero1_moment_footprint_shrinks_dp_fold():
    """The claimed ~dp_size x cut in resident optimizer-state memory
    (config.py zero1 docstring, measured at scale in PERF.md r4), asserted
    structurally on the virtual mesh: per-device moment bytes under zero1
    must be ~1/dp of the unsharded layout (abstract state — no arrays
    materialize)."""
    from picotron_tpu.parallel.api import init_sharded_state

    def per_device_opt_bytes(cfg):
        menv = MeshEnv.from_config(cfg)
        st = init_sharded_state(cfg, menv, jax.random.key(0), abstract=True)
        total = 0
        for leaf in jax.tree.leaves(st.opt_state):
            shard = leaf.sharding.shard_shape(leaf.shape)
            total += int(np.prod(shard)) * leaf.dtype.itemsize
        return total

    base = per_device_opt_bytes(tiny_cfg(dp_size=4))
    z1 = per_device_opt_bytes(tiny_cfg(dp_size=4, zero1=True))
    # small non-divisible leaves (norms) stay replicated, so slightly
    # above exactly 4x; anything < 3x would mean the annotation regressed
    assert base / z1 > 3.0, (base, z1)


def test_ce_chunking_matches_fused_across_layouts():
    """ce_chunk_size streams the LM-head CE over vocab chunks without
    materializing [tokens, vocab] logits; it must match the fused path to
    fp precision, including through the pipeline engines' gated last-stage
    scoring cond (whose branches must stay collective-free — the chunk
    scan's carry anchoring is the load-bearing detail)."""
    import dataclasses

    base = tiny_cfg(pp_size=2, tp_size=2)
    losses = {}
    for chunk in (0, 16):
        cfg = Config(
            distributed=base.distributed,
            model=base.model,
            training=dataclasses.replace(base.training,
                                         ce_chunk_size=chunk),
        )
        cfg.validate()
        losses[chunk], _ = run_parallel(cfg)
    np.testing.assert_allclose(losses[0], losses[16], rtol=1e-6, atol=1e-7)


# ---------------------------------------------------------------------------
# multi-slice: `slices` lays the job out (mesh.py, the checkpoint manifest);
# it selects no second reduction schedule
# ---------------------------------------------------------------------------


def _grads_and_collectives(cfg):
    """(loss, grads, lowered collectives) of one `_device_grads` call — the
    engines' output before any optimizer touches it."""
    from functools import partial

    from picotron_tpu.analysis.collectives import parse_collectives
    from picotron_tpu.parallel.api import _device_grads
    from picotron_tpu.parallel.sharding import batch_spec, param_specs

    cfg.validate()
    d, t = cfg.distributed, cfg.training
    menv = MeshEnv.from_config(cfg)
    params = init_sharded_state(cfg, menv, jax.random.key(0)).params
    toks = jax.random.randint(
        jax.random.key(7),
        (t.gradient_accumulation_steps,
         t.micro_batch_size * d.dp_size * d.ep_size, t.seq_length + 1),
        0, cfg.model.vocab_size)
    sh = menv.batch_sharding()
    batch = (jax.device_put(toks[..., :-1], sh),
             jax.device_put(toks[..., 1:], sh))
    lowered = jax.jit(compat.shard_map(
        partial(_device_grads, cfg=cfg), mesh=menv.mesh,
        in_specs=(param_specs(cfg), (batch_spec(), batch_spec())),
        out_specs=(param_specs(cfg), P(), P()))).lower(params, batch)
    grads, loss, _ = lowered.compile()(params, batch)
    ops = [(op.kind, op.group_size, op.n_groups, op.nbytes)
           for op in parse_collectives(lowered.as_text()) if op.effective]
    return float(loss), jax.tree.map(np.asarray, grads), ops


_FUSED = dict(grad_engine="fused", remat=True, remat_policy="dots_attn")


@pytest.mark.parametrize("dist,moe,train", [
    (dict(dp_size=2, tp_size=2), False, {}),
    (dict(dp_size=2, tp_size=2), False, _FUSED),
    (dict(dp_size=4), False, {}),
    (dict(dp_size=4), False, _FUSED),
    (dict(dp_size=2, pp_size=2, pp_engine="1f1b"), False, {}),
    (dict(dp_size=2, pp_size=2, pp_engine="afab"), False, {}),
    (dict(dp_size=2, ep_size=2), True, {}),
    (dict(dp_size=2, ep_size=2), True, _FUSED),
], ids=["dp2+tp2-ad", "dp2+tp2-fused", "dp4-ad", "dp4-fused",
        "dp2+pp2-1f1b", "dp2+pp2-afab", "dp2+ep2-ad", "dp2+ep2-fused"])
def test_multislice_reduces_flat(dist, moe, train):
    """A two-slice job whose dp axis carries the slice granule reduces its
    gradients with the flat psum over the data axes, exactly as its
    single-slice twin does: the same loss, the same gradients, the same
    collectives in the lowered program. (XLA decomposes the flat psum over
    a hybrid mesh itself; a hand-written intra-slice / cross-slice schedule
    keyed on `slices` would show here as extra collectives.)"""
    def run(slices):
        return _grads_and_collectives(Config(
            distributed=DistributedConfig(slices=slices, **dist),
            model=ModelConfig(
                dtype="float32", num_attention_heads=8,
                num_key_value_heads=4,
                **(dict(num_experts=4, num_experts_per_token=2)
                   if moe else {})),
            training=TrainingConfig(
                seq_length=32, micro_batch_size=2,
                gradient_accumulation_steps=2,
                **{"remat": False, **train})))

    loss1, grads1, ops1 = run(1)
    loss2, grads2, ops2 = run(2)
    assert ops1, "a dp > 1 layout must lower a gradient all-reduce"
    assert ops2 == ops1
    assert loss2 == loss1
    jax.tree.map(np.testing.assert_array_equal, grads2, grads1)
