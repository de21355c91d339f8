#!/usr/bin/env python3
"""What the numbers `correct` compares in a sparse-expert training cell
(`runners/train_step_moe.py`) move by when the model is wrong in the ways the
tolerances have to catch, at the cell's widths on the chip: the program's own
bf16 forward against `reference_moe`, and the reference against itself with
gates renormalised, the k-th expert left out, QK-norm skipped, a non-causal
mask, matmuls at the chip's default precision, and matmul operands rounded to
float8 (the nearest precision below the bfloat16 the configuration states).
Also the gap between the k-th and (k+1)-th router probabilities, which sets
the tie margin. With `steps` above 0 the same at the weights that many
training steps leave (the runner's cycle of 4 batches, the probed sequences
taken from its first batch): a collapsed router, skewed groups and an expert
block that is no longer small, as at the end of the cell's window. Run by hand
on the chip, which it insists on; its first line names the device, and PERF.md
records what it printed.

    python3 benchmark/tools/tolerance_probe_moe.py <configuration> [sequences] [seed] [steps]
"""
import importlib.util
import json
import os
import sys

HERE = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, HERE)
sys.path.insert(0, os.path.dirname(HERE))

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402

import reference_moe  # noqa: E402
from picotron_tpu.config import config_from_dict  # noqa: E402
from picotron_tpu.mesh import MeshEnv  # noqa: E402
from picotron_tpu.parallel.api import init_sharded_state, make_train_step  # noqa: E402
from picotron_tpu.utils import require_platform  # noqa: E402


def runner():
    path = os.path.join(HERE, "runners", "train_step_moe.py")
    spec = importlib.util.spec_from_file_location("train_step_moe", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def stats(e) -> str:
    e = np.asarray(e)
    return (f"min {e.min():.3e} median {np.median(e):.3e} p90 {np.percentile(e, 90):.3e} "
            f"p95 {np.percentile(e, 95):.3e} max {e.max():.3e}") if e.size else "(none)"


def main() -> None:
    require_platform("tolerance_probe_moe", allow_cpu=False)
    rn = runner()
    with open(os.path.join(HERE, "configs", sys.argv[1] + ".json")) as f:
        c = json.load(f)
    n_seq = int(sys.argv[2]) if len(sys.argv) > 2 else 2
    seed = int(sys.argv[3]) if len(sys.argv) > 3 else 1
    steps = int(sys.argv[4]) if len(sys.argv) > 4 else 0
    cfg = config_from_dict({k: c[k] for k in ("distributed", "model", "training")})
    t, m, k = cfg.training, c["model"], cfg.model.num_experts_per_token
    s, ga = t.seq_length, t.gradient_accumulation_steps
    menv = MeshEnv.from_config(cfg)
    print(f"device {jax.devices()[0].device_kind} x {len(jax.devices())}; configuration "
          f"{sys.argv[1]}, {n_seq} sequences, seed {seed}, weights after {steps} steps", flush=True)
    # the runner's state and batches: a cycle of 4 of [ga, b, s + 1]
    state = init_sharded_state(cfg, menv, jax.random.key(seed))
    b_global = t.micro_batch_size * cfg.distributed.dp_size
    cycle = jax.random.randint(jax.random.key(seed + 1), (4, ga, b_global, s + 1), 0,
                               cfg.model.vocab_size, jnp.int32)
    if steps:
        step, sharding = make_train_step(cfg, menv), menv.batch_sharding()
        for i in range(steps):
            state, metrics = step(state, (jax.device_put(cycle[i % 4, ..., :-1], sharding),
                                          jax.device_put(cycle[i % 4, ..., 1:], sharding)))
        print(f"after {steps} steps: loss {float(metrics['loss']):.4f}, busiest expert over "
              f"the mean {float(metrics['moe_load_max_over_mean']):.3f}", flush=True)
    params = state.params
    del state
    toks = np.asarray(cycle[0, :n_seq, 0])  # [n_seq, s + 1]: the first batch's sequences
    del cycle
    rows = np.sort(np.random.default_rng(seed).choice(s, size=min(rn.LOGITS_ROWS, s),
                                                      replace=False))
    program = rn.program_logits_fn(cfg, menv)
    variants = {
        "gates renormalised": dict(renorm_gates=True),
        f"expert {k} of {k} left out": dict(drop_last_expert=True),
        "QK-norm skipped": dict(skip_qk_norm=True),
        "non-causal mask": dict(causal=False),
        "default matmul precision": dict(precision="default"),
        "operands rounded to float8_e4m3fn": dict(round_to=jnp.float8_e4m3fn),
        "operands rounded to bfloat16": dict(round_to=jnp.bfloat16),
    }
    def reference(**kw):
        return jax.jit(lambda p, i, g, r: reference_moe.evaluate(p, i, g, r, m, **kw))

    right = reference()
    for j in range(n_seq):
        ids, tgt = jnp.asarray(toks[j, :-1]), jnp.asarray(toks[j, 1:])
        base = jax.device_get(right(params, ids, tgt, jnp.asarray(rows)))
        want = base["logits"]
        got = np.asarray(program(params, jax.device_put(
            ids[None], menv.sharding(("dp", "ep"), "cp")))[0, rows].astype(jnp.float32))
        top = -np.sort(-np.asarray(base["probs"], np.float64), axis=-1)
        gaps = np.log(top[..., k - 1] / top[..., k]).min(axis=0)
        chosen = np.argsort(-np.asarray(base["probs"]), axis=-1)[..., :k]
        e_n = base["probs"].shape[-1]
        print(f"--- sequence {j}: log gap between router probabilities {k} and {k + 1} over "
              f"{len(rows)} positions: {stats(gaps)}; top-{k} mass {stats(top[..., :k].sum(-1))}; "
              f"busiest expert there {np.bincount(chosen.reshape(-1), minlength=e_n).max() * e_n / chosen.size:.2f} x the mean")
        errs = rn.row_errors(got, want)
        for margin in (0.0, 0.002, 0.005, 0.01, 0.02, 0.05):
            ties = gaps < margin
            print(f"program vs reference, tie margin {margin}: {int(ties.sum())} left out; "
                  f"kept: {stats(errs[~ties])}; left out: {stats(errs[ties])}", flush=True)
        print(f"reference loss (with auxiliary terms) {float(base['loss']):.6f}; balance "
              f"{float(base['balance']):.4f} z {float(base['z']):.4f}")
        for name, kw in variants.items():
            try:
                r = jax.device_get(reference(**kw)(params, ids, tgt, jnp.asarray(rows)))
                e = rn.row_errors(r["logits"], want)
                d = (float(r["loss"]) - float(base["loss"])) / float(base["loss"])
                kept = gaps >= rn.TIE_MARGIN
                share = rn.fault_share(got[kept], want[kept], r["logits"][kept])
                print(f"{name}: {stats(e)}; at the kept positions {stats(e[kept])}; loss "
                      f"{float(r['loss']):.6f} ({d:+.2e} of it); its share in the program's "
                      f"error {share:+.4f}", flush=True)
            except Exception as ex:  # noqa: BLE001 (a dtype this chip cannot convert)
                print(f"{name}: not computed ({type(ex).__name__}: {str(ex)[:200]})", flush=True)

if __name__ == "__main__":
    main()
