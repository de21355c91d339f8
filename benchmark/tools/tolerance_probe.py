#!/usr/bin/env python3
"""What the number `correct` compares in a training cell moves by when the
reference is wrong in the ways the tolerance has to catch: a dropped layer, a
non-causal mask, matmuls at the chip's default (bf16-pass) precision. Run by
hand on the chip; PERF.md records what it printed.

    python3 benchmark/tools/tolerance_probe.py <configuration> [sequences]
"""
import json
import os
import sys

HERE = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, HERE)
sys.path.insert(0, os.path.dirname(HERE))

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402

import reference  # noqa: E402
from picotron_tpu.config import config_from_dict  # noqa: E402
from picotron_tpu.models.llama import init_params  # noqa: E402
from picotron_tpu.utils import require_platform  # noqa: E402


def main() -> None:
    require_platform("tolerance_probe", allow_cpu=False)
    with open(os.path.join(HERE, "configs", sys.argv[1] + ".json")) as f:
        c = json.load(f)
    n_seq = int(sys.argv[2]) if len(sys.argv) > 2 else 2
    cfg = config_from_dict({k: c[k] for k in ("model", "training")})
    m, s = c["model"], cfg.training.seq_length
    params = jax.jit(lambda k: init_params(cfg.model, k))(jax.random.key(1))
    toks = jax.random.randint(jax.random.key(2), (n_seq, s + 1), 0, cfg.model.vocab_size, jnp.int32)
    mid = m["num_hidden_layers"] // 2
    variants = {"reference": {}, f"layer {mid} dropped": dict(skip_layers=(mid,)),
                "non-causal mask": dict(causal=False), "default matmul precision": dict(precision="default")}
    out = {}
    for name, kw in variants.items():
        f = jax.jit(lambda p, i, g, kw=kw: reference.nll_sum(p, i, g, m, **kw)[0])
        out[name] = sum(float(f(params, toks[j, :-1], toks[j, 1:])) for j in range(n_seq)) / (n_seq * s)
        print(f"{name}: mean loss {out[name]:.6f}  delta {out[name] - out['reference']:+.2e}", flush=True)


if __name__ == "__main__":
    main()
