"""run.py end to end on the CPU at debug-tiny sizes, and its refusal to run
there unasked.

The device override lives here, not in run.py: the test replaces
`run.require_chip` (which demands a TPU from peaks.json) with one that hands
out CPU devices and a made-up peak, and tells the trace reduction to take the
CPU client's XLA lines for a device. Nothing measured here is a device number.
"""
import importlib.util
import io
import json
import os
import re
import shutil
import subprocess
import sys
from contextlib import redirect_stdout

os.environ["JAX_PLATFORMS"] = "cpu"
os.environ["XLA_FLAGS"] = (os.environ.get("XLA_FLAGS", "")
                           + " --xla_force_host_platform_device_count=4")

import numpy as np  # noqa: E402
import pytest  # noqa: E402

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
REPO = os.path.dirname(BENCH)

TINY = dict(vocab_size=256, hidden_size=64, intermediate_size=128, num_hidden_layers=4,
            num_attention_heads=4, num_key_value_heads=2, max_position_embeddings=2048,
            rope_theta=10000.0, rms_norm_eps=1e-5, hidden_act="silu")
TRAIN = {"seq_length": 128, "micro_batch_size": 1, "gradient_accumulation_steps": 4,
         "learning_rate": 3e-3, "adam_moments_dtype": "bfloat16", "remat": True,
         "remat_policy": "dots_attn", "grad_engine": "auto"}
CONFIGS = {
    "tiny-qwen": {"distributed": {}, "training": TRAIN, "initializer_range": 0.02,
                  "model": {"name": "debug-tiny-qwen", **TINY, "attention_bias": True,
                            "tie_word_embeddings": True, "dtype": "float32"},
                  "serve": {"decode_slots": 4, "block_size": 16, "prefill_chunk": 32,
                            "max_model_len": 256, "decode_interval": 2}},
    "tiny-tp2pp2": {"distributed": {"tp_size": 2, "pp_size": 2}, "training": TRAIN,
                    "model": {"name": "debug-tiny", **TINY, "attention_bias": False,
                              "tie_word_embeddings": False, "dtype": "float32"}},
}
TRAFFIC = {"generator": "chat_lognormal", "rate_per_s": 10.0, "shape_seed": 1,
           "prompt_tokens": {"median": 40, "sigma": 0.8, "min": 8, "max": 150},
           "output_tokens": {"median": 12, "sigma": 0.7, "min": 4, "max": 40}}
TRAIN_E2E = {"train_tok_s_chip": "tokens_per_s_per_chip", "setup_s": "setup_s"}
SERVE_E2E = {"latency_per_token_p90_ms": "latency_per_token_ms_p90", "setup_s": "setup_s"}
CELLS = {
    "tiny-qwen.train": dict(config="tiny-qwen", chips=1, runner="train_step", end_to_end=TRAIN_E2E),
    "tiny-tp2pp2.train": dict(config="tiny-tp2pp2", chips=4, runner="train_step",
                              end_to_end=TRAIN_E2E),
    "tiny-qwen.chat": dict(config="tiny-qwen", chips=1, runner="serve_open_loop",
                           end_to_end=SERVE_E2E, traffic=TRAFFIC, drain_limit_s=30),
}
# not in the parametrised lists: the chat cell with the control of `correct` switched on
CONTROL = {"tiny-qwen.chat-control": dict(CELLS["tiny-qwen.chat"], control="int4")}


@pytest.fixture(scope="module")
def tree(tmp_path_factory):
    """A checkout in miniature: the benchmark's files, the program (a link),
    and a BENCHMARK.json of tiny cells over the real metric files."""
    root = tmp_path_factory.mktemp("checkout")
    shutil.copytree(BENCH, root / "benchmark", ignore=shutil.ignore_patterns("__pycache__"))
    os.symlink(os.path.join(REPO, "picotron_tpu"), root / "picotron_tpu")
    with open(os.path.join(REPO, "BENCHMARK.json")) as f:
        real = json.load(f)
    bench = dict(real, configs=[], workloads=[])
    for name, c in CONFIGS.items():
        with open(root / "benchmark" / "configs" / f"{name}.json", "w") as f:
            json.dump(c, f)
        bench["configs"].append(dict(name=name, file=f"benchmark/configs/{name}.json"))
    for name, w in {**CELLS, **CONTROL}.items():
        with open(root / "benchmark" / "workloads" / f"{name}.json", "w") as f:
            json.dump(dict(name=name, **w), f)
        bench["workloads"].append(dict(name=name, config=w["config"], chips=w["chips"]))
    runner_of = {}
    for cell in (w["name"] for w in real["workloads"]):
        with open(os.path.join(BENCH, "workloads", cell + ".json")) as f:
            runner_of[cell] = json.load(f)["runner"]
    for m in bench["end_to_end"] + bench["per_layer"]:
        if "workloads" in m:  # the real cells' lists -> the tiny cells of the same runners
            runners = {runner_of[cell] for cell in m["workloads"]}
            end_to_end = m in bench["end_to_end"]
            m["workloads"] = [n for n, w in {**CELLS, **CONTROL}.items() if w["runner"] in runners
                              and (not end_to_end or m["name"] in w["end_to_end"])]
    with open(root / "BENCHMARK.json", "w") as f:
        json.dump(bench, f)
    return root


def load_run(tree):
    spec = importlib.util.spec_from_file_location("bench_run", tree / "benchmark" / "run.py")
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    import jax

    def cpu_chip(chips):  # the test-only device override
        return dict(devices=jax.devices()[:chips],
                    peak=dict(bf16_flops_per_s=1e12, hbm_bytes_per_s=1e11, hbm_bytes=1e10))

    mod.require_chip = cpu_chip
    return mod


def cpu_device_ops(planes):
    """Test-only: the CPU client's XLA threads stand in for device 0."""
    ev = [e for p, lines in planes if p.startswith("/host:") for ln, evs in lines
          if ln.startswith("tf_XLA") for e in evs if e[2] > 0 and not e[0].startswith("Thread")]
    return {0: ev}


def run_cell(tree, cell, trace, monkeypatch, seed=2**31 + 5, seconds=1.5, correct=True):
    mod = load_run(tree)
    monkeypatch.syspath_prepend(str(tree / "benchmark"))
    import trace_reduce
    monkeypatch.setattr(trace_reduce, "device_ops", cpu_device_ops)
    out = io.StringIO()
    with redirect_stdout(out):
        rc = mod.main(["--workload", cell, "--seed", str(seed), "--seconds", str(seconds),
                       "--trace", str(trace)])
    assert rc == 0, out.getvalue()
    line = json.loads(out.getvalue().strip().splitlines()[-1])
    assert set(line) >= {"correct", "attempted", "failed", "metrics", "device"}
    assert line["correct"] is correct, out.getvalue()
    assert line["failed"] == 0 and line["attempted"] > 0
    return line, out.getvalue()


@pytest.mark.parametrize("cell", list(CELLS))
def test_end_to_end_metrics(tree, cell, monkeypatch):
    line, _ = run_cell(tree, cell, 0, monkeypatch)
    assert set(line["metrics"]) == set(CELLS[cell]["end_to_end"])
    assert all(v["value"] > 0 for v in line["metrics"].values())
    assert line["device"]["count"] == CELLS[cell]["chips"]


@pytest.mark.parametrize("cell", list(CELLS))
def test_per_layer_metrics_and_breakdown(tree, cell, monkeypatch):
    line, text = run_cell(tree, cell, 1, monkeypatch)
    with open(tree / "BENCHMARK.json") as f:
        bench = json.load(f)
    allowed = {m["name"] for m in bench["per_layer"]
               if "workloads" not in m or cell in m["workloads"]}
    assert set(line["metrics"]) <= allowed and "compile_s" in line["metrics"], text
    assert line["device"]["busy_s"] > 0 and line["device"]["window_s"] > 0
    assert 1 <= len(line["breakdown"]["device_ops"]) <= 10
    assert len(line["breakdown"]["idle_gaps"]) <= 10
    if cell.endswith(".train"):
        assert {"step_ms.train", "mfu_required.train", "device_idle.train"} <= set(line["metrics"])
    else:
        assert {"decode_dispatch_ms.serve", "slot_occupancy.serve", "kv_pool_fill.serve",
                "device_idle.serve"} <= set(line["metrics"])


def test_chat_cell_writes_one_record_a_request(tree, monkeypatch):
    line, text = run_cell(tree, "tiny-qwen.chat", 0, monkeypatch, seed=7)
    with open(tree / ".bench_out" / "tiny-qwen.chat.requests.jsonl") as f:
        rows = [json.loads(ln) for ln in f]
    assert len(rows) == line["attempted"] and len({r["id"] for r in rows}) == len(rows)
    for r in rows:
        assert r["due_s"] <= r["submitted_s"] <= r["first_token_seen_s"] <= r["done_s"]
        assert r["chunks"] == -(-r["prompt_tokens"] // 32) and r["output_tokens"] > 0
    per_token = [(r["done_s"] - r["due_s"]) / r["output_tokens"] * 1e3 for r in rows]
    ttft = [(r["first_token_seen_s"] - r["due_s"]) * 1e3 for r in rows]
    assert line["metrics"]["latency_per_token_p90_ms"]["value"] == pytest.approx(
        np.percentile(per_token, 90), rel=1e-9)
    for key, label, values in (("latency_per_token_ms_p90", "latency a token", per_token),
                               ("ttft_ms_p90", "ttft", ttft)):
        p90 = line["facts"][key]
        assert p90 == pytest.approx(np.percentile(values, 90), rel=1e-9)
        starred = [float(x.split()[1]) for x in text.split(label + " p90 = rank")[1].splitlines()[0]
                   .split(": ", 1)[1].split("; ") if x.startswith("*")]
        assert min(starred) <= p90 <= max(starred)  # the printed neighbours bracket it
    in_step, loop = (float(x) for x in re.search(
        r"; ([\d.]+) s inside engine.step of ([\d.]+) s of loop", text).groups())
    assert 0 < in_step <= loop


def test_a_token_altered_where_it_is_produced_is_not_correct(tree, monkeypatch):
    """The rest of a run with the timed path broken underneath: the engine's
    sampler puts the least likely token first."""
    import jax
    import jax.numpy as jnp

    from picotron_tpu.serve import engine

    monkeypatch.setattr(engine, "_sample_slots",
                        lambda logits, *a, **k: jnp.argmin(logits, axis=-1).astype(jnp.int32))
    jax.clear_caches()  # an earlier test's traced programs hold the sound sampler
    try:
        _, text = run_cell(tree, "tiny-qwen.chat", 0, monkeypatch, correct=False)
    finally:
        jax.clear_caches()
    assert "correct=False" not in text  # that line goes to standard error
    gap = float(text.split("worst gap to the top logit ")[1].split()[0])
    assert gap > 1.0, text


def test_the_control_is_not_correct(tree, monkeypatch):
    """The reference with int8 weights in the program's place (the nearest
    precision below bfloat16) is outside the tie band; the program is inside."""
    _, text = run_cell(tree, "tiny-qwen.chat-control", 0, monkeypatch, correct=False)
    program = float(text.split("worst gap to the top logit ")[1].split()[0])
    control = float(text.split("in the program's place: worst gap to the top logit ")[1].split()[0])
    assert program <= 1.0 < control, text


def test_no_tpu_exits_nonzero_with_one_line():
    with open(os.path.join(REPO, "BENCHMARK.json")) as f:
        cell = json.load(f)["workloads"][0]["name"]
    p = subprocess.run([sys.executable, os.path.join(BENCH, "run.py"), "--workload", cell,
                        "--seed", "1", "--seconds", "1", "--trace", "0"],
                       capture_output=True, text=True, cwd=REPO,
                       env={**os.environ, "JAX_PLATFORMS": "cpu"})
    assert p.returncode != 0
    assert not any(ln.startswith("{") for ln in p.stdout.splitlines())
    assert len(p.stderr.strip().splitlines()) == 1 and "benchmark" in p.stderr


def test_outside_a_checkout_exits_nonzero(tmp_path):
    shutil.copytree(BENCH, tmp_path / "benchmark", ignore=shutil.ignore_patterns("__pycache__"))
    shutil.copy(os.path.join(REPO, "BENCHMARK.json"), tmp_path / "BENCHMARK.json")
    with open(tmp_path / "BENCHMARK.json") as f:
        cell = json.load(f)["workloads"][0]["name"]
    p = subprocess.run([sys.executable, "benchmark/run.py", "--workload", cell, "--seed", "1",
                        "--seconds", "1", "--trace", "0"], capture_output=True, text=True,
                       cwd=tmp_path)
    assert p.returncode != 0 and not any(ln.startswith("{") for ln in p.stdout.splitlines())
