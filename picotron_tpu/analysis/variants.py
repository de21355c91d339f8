"""Static jit-variant prover — compile-once, certified before any run.

Every `jit` entry point mints a fresh compile whenever the *abstract
signature* of a call changes: any leaf's shape, dtype, weak-type,
sharding, or committed-ness, or any static argument. The failure mode is
always silent — PR 7's serving stack found a 0.6 s mid-trace recompile
only because CompileWatch was listening at runtime. This module makes the
property *provable* on the host, before a chip is touched:

- `signature_of(tree, statics)` canonicalizes one call's abstract
  signature (`AbstractSig`): per-leaf (path, shape, dtype, spec,
  committed, weak_type) plus the static-argument tuple. Two calls compile
  separately iff their `AbstractSig`s differ.
- `audit_feeds(feeds)` enumerates the signature space a call site can
  produce and flags exactly the three variant-minting hazards the issue
  names: an uncommitted array joining a committed signature, a varying
  shape/dtype, and a sharding variant.
- `prove_train_step(cfg)` certifies the training entry point: the initial
  signature (abstract sharded state + batch) must equal the steady-state
  signature (the step's own output fed back in), and every input leaf
  must carry an explicit sharding. One signature -> exactly one compile
  for the whole run, fused-bwd and 1f1b interiors included (they live
  inside this jit; a custom-vjp path cannot mint an outer variant).
- `prove_serve_programs(...)` / `check_engine_feed(engine)` certify the
  decode + prefill programs: slot count is the decode program's only
  shape carrier (its inputs are [S]-shaped, request identity is data),
  and the prefill program's is the row count of its compacted batch,
  which the engine draws from the fixed ladder `prefill_rungs(S)` and
  nowhere else ([R, C]-shaped inputs, which slots prefill is data; a
  tick may lay its rows onto several rungs, `prefill_cover`, each a
  dispatch at one of the ladder's signatures). So
  the signature space is one decode signature and one prefill signature
  per rung, closed iff every persistent input is committed and every
  per-step upload goes through the engine's single replicated sharding
  — the commit-everything discipline, now checked instead of trusted.

What "proven" covers — and does not. The proof is over the abstract
signature space: it shows no *input-side* variant can occur. It does not
model jit-cache eviction, explicitly different static arguments (a new
`interval` is a new program — intended), or a JAX upgrade changing
lowering itself. Output shardings are not observable without compiling;
for the train step the stability check pins output avals == input avals,
and the runtime CompileWatch twin tests (tests/test_variants.py) confirm
the end-to-end claim on the real cache.
"""

from __future__ import annotations

from dataclasses import dataclass

import jax

from picotron_tpu.analysis.report import ERROR, INFO, WARNING, Report

CHECK = "variants"


@dataclass(frozen=True)
class AbstractSig:
    """One jit call's canonical abstract signature."""

    treedef: str
    leaves: tuple    # ((path, shape, dtype, spec, committed, weak), ...)
    statics: tuple = ()

    def diff(self, other: "AbstractSig") -> list:
        """Human-readable component differences vs `other`."""
        out = []
        if self.treedef != other.treedef:
            out.append("pytree structure differs")
        a = {leaf[0]: leaf[1:] for leaf in self.leaves}
        b = {leaf[0]: leaf[1:] for leaf in other.leaves}
        names = ("shape", "dtype", "sharding", "committed", "weak_type")
        for path in sorted(set(a) | set(b)):
            if path not in a or path not in b:
                out.append(f"{path}: leaf only on one side")
                continue
            for name, x, y in zip(names, a[path], b[path]):
                if x != y:
                    out.append(f"{path}: {name} {x!r} vs {y!r}")
        if self.statics != other.statics:
            out.append(f"statics {self.statics!r} vs {other.statics!r}")
        return out


def _leaf_sig(path: str, x) -> tuple:
    shape = tuple(getattr(x, "shape", ()))
    dtype = str(getattr(x, "dtype", "?"))
    sharding = getattr(x, "sharding", None)
    if isinstance(x, jax.ShapeDtypeStruct):
        # abstract leaf: an attached sharding *declares* the commitment
        committed = sharding is not None
    else:
        committed = bool(getattr(x, "committed", False))
    spec = None
    if committed and sharding is not None:
        spec = (str(tuple(sharding.spec)) if hasattr(sharding, "spec")
                else type(sharding).__name__)
    weak = bool(getattr(x, "weak_type", False))
    return (path, shape, dtype, spec, committed, weak)


def signature_of(tree, statics: tuple = ()) -> AbstractSig:
    """Canonical `AbstractSig` of one call's argument pytree. `statics`
    must already be hashable (jit would reject them otherwise)."""
    from picotron_tpu.analysis.spec_lint import dict_by_path

    leaves = tuple(_leaf_sig(p, x) for p, x in dict_by_path(tree).items())
    treedef = str(jax.tree_util.tree_structure(tree))
    return AbstractSig(treedef, leaves, tuple(statics))


def audit_feeds(feeds, *, entry: str = "<jit>", statics=None) -> Report:
    """Enumerate the signature space of a call site's possible feeds.

    `feeds`: list of argument pytrees one call site can pass (each
    optionally paired with statics via the `statics` list). More than one
    distinct signature means the entry point compiles more than once; any
    uncommitted concrete leaf is flagged even when the space is closed,
    because commitment spreads through jit outputs — one uncommitted feed
    poisons downstream signatures (the serve-engine hazard)."""
    rep = Report()
    statics = statics or [()] * len(feeds)
    sigs = []
    for tree, st in zip(feeds, statics):
        sigs.append(signature_of(tree, st))
        for path, shape, dtype, spec, committed, weak in sigs[-1].leaves:
            if not committed:
                rep.add(CHECK, WARNING, f"{entry}/{path}",
                        f"feed can be UNCOMMITTED ({dtype}{list(shape)}): "
                        f"a committed array later reaching this leaf keys "
                        f"a different jit signature and mints a recompile "
                        f"— commit it up front with jax.device_put(x, "
                        f"<sharding>)")
    uniq = []
    for s in sigs:
        if s not in uniq:
            uniq.append(s)
    if len(uniq) > 1:
        diffs = uniq[0].diff(uniq[1])
        rep.add(CHECK, ERROR, entry,
                f"{len(uniq)} distinct abstract signatures reach this jit "
                f"entry — compile-once is NOT provable. First divergence: "
                f"{'; '.join(diffs[:4]) or 'statics differ'}")
    rep.info[CHECK] = {"entry": entry, "feeds": len(feeds),
                       "signatures": len(uniq),
                       "proven": len(uniq) <= 1 and rep.ok()}
    return rep


# ---------------------------------------------------------------------------
# Train step
# ---------------------------------------------------------------------------


def prove_train_step(cfg, menv=None, *, low=None) -> Report:
    """Certify the training entry point compiles exactly once.

    Signature space: {initial call} ∪ {steady-state calls}. The initial
    signature comes from the abstract sharded state + batch; the
    steady-state signature feeds the step's own output state back in
    (avals via eval_shape — shardings are not observable abstractly, so
    leaf shardings are compared on the declared input side and aval
    stability covers the output side). Proven iff both signatures agree
    and every input leaf carries an explicit sharding."""
    rep = Report()
    if low is None:
        from picotron_tpu.analysis.trace import lower_train_step

        low = lower_train_step(cfg, menv)
    state, batch = low.state, low.batch

    sig0 = signature_of((state, batch))
    uncommitted = [leaf[0] for leaf in sig0.leaves if not leaf[4]]
    for path in uncommitted:
        rep.add(CHECK, ERROR, path,
                "train-step input leaf has no explicit sharding: the "
                "first committed array reaching it re-keys the jit cache "
                "(init_sharded_state must hand every leaf a NamedSharding)")

    out = jax.eval_shape(low.step_fn, state, batch)
    new_state = out[0] if isinstance(out, tuple) else out
    # steady state: output state replaces input state, batch aval repeats
    drift = []
    if (jax.tree_util.tree_structure(new_state)
            != jax.tree_util.tree_structure(state)):
        drift = ["pytree structure differs across the step"]
    else:
        from picotron_tpu.analysis.spec_lint import dict_by_path

        ins, outs = dict_by_path(state), dict_by_path(new_state)
        for path, a in ins.items():
            b = outs[path]
            if (tuple(a.shape) != tuple(b.shape) or a.dtype != b.dtype
                    or getattr(a, "weak_type", False)
                    != getattr(b, "weak_type", False)):
                drift.append(
                    f"state/{path}: {a.dtype}{list(a.shape)} in, "
                    f"{b.dtype}{list(b.shape)} out")
    for d in drift:
        rep.add(CHECK, ERROR, d.split(":")[0],
                f"steady-state signature differs from the initial one "
                f"({d}): step 2 presents a new abstract signature and "
                f"recompiles — a varying-shape call site by construction")

    proven = rep.ok()
    rep.info[CHECK] = {
        "entry": "train_step",
        "signatures": 1 if proven else 2,
        "proven": proven,
        "leaves": len(sig0.leaves),
        "uncommitted": len(uncommitted),
        # interior grad paths (fused-bwd custom_vjp, 1f1b scan) live
        # inside this jit: they cannot mint an outer variant
        "covers": ("train_step", "fused-bwd interior", "pp interior"),
    }
    if proven:
        rep.add(CHECK, INFO, "train_step",
                f"compile-once proven: one abstract signature "
                f"({len(sig0.leaves)} committed leaves, stable avals "
                f"across the step)")
    return rep


# ---------------------------------------------------------------------------
# MPMD stage programs
# ---------------------------------------------------------------------------


def prove_mpmd_stages(cfg, menv=None) -> Report:
    """Certify every MPMD per-stage program compiles exactly once.

    mpmd.mpmd_entry_feeds enumerates, per stage program (fwd and bwd of
    each virtual stage), the abstract argument tuple of EVERY call the
    config's schedule table makes — committed ShapeDtypeStructs with the
    submesh shardings the executor device_puts. audit_feeds then closes
    each entry's signature space; a stage whose scheduled calls disagree
    in abstract signature (a second executable minted mid-schedule) is an
    ERROR, which shardcheck renders as a fatal row.

    The schedule TABLE itself is also re-linted here (mpmd.lint_schedule:
    balanced produce/consume per boundary buffer, no consume-before-
    produce, bounded in-flight live set) so the CLI surfaces the same
    static proof build_schedule enforces at construction time."""
    from picotron_tpu.mesh import MeshEnv
    from picotron_tpu.parallel.mpmd import (build_schedule, lint_schedule,
                                            mpmd_entry_feeds)

    rep = Report()
    menv = menv if menv is not None else MeshEnv.from_config(cfg)
    feeds = mpmd_entry_feeds(cfg, menv)
    entries = {}
    proven_all = True
    for entry in sorted(feeds):
        sub = audit_feeds(feeds[entry], entry=entry)
        rep.findings.extend(sub.findings)
        info = sub.info.get(CHECK, {})
        entries[entry] = info
        proven_all = proven_all and bool(info.get("proven"))
    n_micro = cfg.training.gradient_accumulation_steps
    pp = cfg.distributed.pp_size
    kind = cfg.pipeline.schedule
    table = build_schedule(kind, n_micro, pp, cfg.pipeline.interleave)
    problems = lint_schedule(table, n_micro, pp, cfg.pipeline.interleave,
                             kind=kind)
    for p in problems:  # unreachable via build_schedule (it raises), but
        rep.add(CHECK, ERROR, "schedule", p)  # guards future generators
    lint_info = {"kind": kind, "ops": len(table),
                 "ticks": (max(op.tick for op in table) + 1) if table else 0,
                 "problems": len(problems), "proven": not problems}
    rep.info[CHECK] = {"entry": "mpmd_stages", "programs": len(feeds),
                       "proven": proven_all and not problems,
                       "entries": entries, "schedule_lint": lint_info}
    if proven_all:
        rep.add(CHECK, INFO, "mpmd_stages",
                f"compile-once proven for all {len(feeds)} stage programs "
                f"(schedule {cfg.pipeline.schedule}, every scheduled call "
                f"presents one committed abstract signature per program)")
    return rep


# ---------------------------------------------------------------------------
# Serve programs
# ---------------------------------------------------------------------------

_PERSISTENT = ("params", "_kv", "cos", "sin", "base_key")
_UPLOADED = ("tables", "toks", "positions", "rids", "tidx")


def check_engine_feed(engine) -> Report:
    """Certify a ServeEngine's decode + prefill signature spaces, from the
    arrays the engine actually holds (duck-typed; no serve import).

    Closed iff (a) every persistent device input — params leaves, the KV
    pool, rope tables, the sampling key — is committed, and (b) the
    per-step host uploads all route through the engine's single
    `_rep_sh` sharding (true by construction; recorded here). Slot count
    is the decode program's only shape carrier, and the prefill program's
    is the row count of its compacted batch, always a rung of the
    engine's `prefill_rungs` (a function of the slot count; the
    constructor compiles each, and `prefill_cover` picks a tick's pieces
    from them alone). So with (a) and (b) the signature space
    is exactly one decode signature and one prefill signature per rung:
    `info["signatures"]` counts them."""
    from picotron_tpu.analysis.spec_lint import dict_by_path

    rep = Report()
    uncommitted = []
    for name in _PERSISTENT:
        tree = getattr(engine, name, None)
        if tree is None:
            continue
        for path, leaf in dict_by_path(tree).items():
            if hasattr(leaf, "committed") and not leaf.committed:
                uncommitted.append(
                    name if path == "<root>" else f"{name}/{path}")
    for path in uncommitted:
        rep.add(CHECK, WARNING, path,
                "persistent serve input is UNCOMMITTED: commitment "
                "spreads through jit outputs, so the first committed "
                "array joining a call (e.g. place_for_decode'd params) "
                "re-keys the decode signature and mints a mid-trace "
                "recompile — device_put it with an explicit sharding at "
                "engine construction")
    proven = not uncommitted
    rungs = tuple(getattr(engine, "prefill_rungs", ()))
    rep.info[CHECK] = {
        "entry": "serve_decode+prefill",
        # one decode signature and one prefill signature a rung; an
        # uncommitted input can mint each a second time
        "signatures": (1 + len(rungs)) * (1 if proven else 2),
        "prefill_rows": list(rungs),
        "proven": proven,
        "uncommitted": uncommitted,
        "upload_sharding": type(getattr(engine, "_rep_sh", None)).__name__,
        "slots": getattr(engine, "num_slots", None),
    }
    if proven:
        rep.add(CHECK, INFO, "serve",
                "compile-once proven for decode, and for prefill at each "
                f"row count of its ladder {list(rungs)}: every persistent "
                "input committed; host uploads share one replicated "
                "sharding; slot count and the rung are the only static "
                "shapes")
    return rep


def prove_serve_programs(model_cfg, serve_cfg=None, *, params=None) -> \
        Report:
    """Static (engine-less) proof for the serve programs of a config:
    constructs the decode/prefill abstract signatures exactly as
    ServeEngine feeds them and certifies the space is closed. With
    `params` (a concrete pytree), their commitment is checked too —
    otherwise params are assumed committed and the engine-side
    `check_engine_feed` covers the live check."""
    import jax.numpy as jnp

    from picotron_tpu.config import ServeConfig
    from picotron_tpu.serve.engine import prefill_rungs
    from picotron_tpu.serve.paged_cache import init_paged_cache
    from picotron_tpu.serve.scheduler import blocks_for

    scfg = serve_cfg or ServeConfig()
    scfg.validate()
    rep = Report()
    max_len = scfg.max_model_len or model_cfg.max_position_embeddings
    max_blocks = blocks_for(max_len, scfg.block_size)
    num_blocks = scfg.num_blocks or scfg.decode_slots * max_blocks
    s = scfg.decode_slots
    rungs = prefill_rungs(s)

    # abstract: the real pool for a 7B model is GBs of zeros — the proof
    # only needs the shapes ServeEngine would feed
    cache = jax.eval_shape(lambda: init_paged_cache(
        model_cfg, num_blocks, scfg.block_size, s, max_blocks))
    i32 = lambda *shape: jax.ShapeDtypeStruct(shape, jnp.int32)  # noqa: E731
    decode_args = {
        "k": jax.ShapeDtypeStruct(cache.k.shape, cache.k.dtype),
        "v": jax.ShapeDtypeStruct(cache.v.shape, cache.v.dtype),
        "tables": i32(s, max_blocks), "toks": i32(s),
        "positions": i32(s), "rids": i32(s), "tidx": i32(s),
    }
    # one signature for decode and one per rung for prefill: every shape
    # is a pure function of (model_cfg, serve_cfg) — request identity,
    # positions, block tables and WHICH slots prefill are DATA; how MANY
    # prefill picks a rung of the fixed ladder, nothing else
    sig_d = signature_of(decode_args)
    sigs_p = {r: signature_of({
        "k": decode_args["k"], "v": decode_args["v"],
        "tables": i32(r, max_blocks),
        "chunk_ids": i32(r, scfg.prefill_chunk),
        "start_pos": i32(r), "n_valid": i32(r), "rids": i32(r),
        "tidx": i32(r),
    }) for r in rungs}
    uncommitted = []
    if params is not None:
        from picotron_tpu.analysis.spec_lint import dict_by_path

        uncommitted = [p for p, leaf in dict_by_path(params).items()
                       if hasattr(leaf, "committed") and not leaf.committed]
        for p in uncommitted:
            rep.add(CHECK, WARNING, f"params/{p}",
                    "serve params leaf is uncommitted — commit via "
                    "generate.place_for_decode (or device_put with an "
                    "explicit sharding) before engine construction")
    proven = not uncommitted
    rep.info[CHECK] = {
        "entry": "serve_decode+prefill",
        "signatures": (1 + len(sigs_p)) * (1 if proven else 2),
        "prefill_rows": list(rungs),
        "proven": proven,
        "decode_leaves": len(sig_d.leaves),
        "prefill_leaves": len(sigs_p[s].leaves),
        "uncommitted": uncommitted,
    }
    return rep


# ---------------------------------------------------------------------------
# The check (runner wiring)
# ---------------------------------------------------------------------------


def audit_variants(cfg, *, low=None, menv=None) -> Report:
    """The `variants` check run_shardcheck dispatches: the train-step
    proof, plus the static serve proof when the config's model is
    servable (always — the serve programs depend only on ModelConfig)."""
    rep = prove_train_step(cfg, menv, low=low)
    info = {"train_step": rep.info.get(CHECK, {})}
    if cfg.pipeline.executor == "mpmd":
        # per-stage programs: the host executor's jits live OUTSIDE the
        # (twin-lowered) train_step jit, so they need their own proof
        stage_rep = prove_mpmd_stages(cfg, menv)
        rep.findings.extend(stage_rep.findings)
        info["mpmd_stages"] = stage_rep.info.get(CHECK, {})
    try:
        serve_rep = prove_serve_programs(cfg.model)
        rep.findings.extend(serve_rep.findings)
        info["serve"] = serve_rep.info.get(CHECK, {})
    except Exception as e:  # serve stack optional for exotic models
        info["serve"] = {"unavailable": f"{type(e).__name__}: {e}"}
    rep.info[CHECK] = info
    return rep
