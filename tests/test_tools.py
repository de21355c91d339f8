"""Experiment-tooling tests: config generator round-trip, metrics harvester
parsing (the log-line format is a de-facto API between utils.training_log_line
and tools/extract_metrics.py — same contract the reference has between
train.py prints and its extract_metrics regexes), and the job scheduler's
status state machine."""

import importlib.util
import os
import sys

import pytest

TOOLS = os.path.join(os.path.dirname(__file__), "..", "tools")


def load_tool(name):
    spec = importlib.util.spec_from_file_location(
        name, os.path.join(TOOLS, f"{name}.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def test_create_config_roundtrip(tmp_path):
    cc = load_tool("create_config")
    args = cc.build_parser().parse_args([
        "--exp-name", "dp2_tp2", "--out-dir", str(tmp_path),
        "--model", "debug-tiny", "--dp", "2", "--tp", "2",
        "--seq-len", "64", "--mbs", "2", "--grad-acc", "3",
    ])
    path = cc.create_single_config(args)
    from picotron_tpu.config import load_config
    cfg = load_config(path)
    assert cfg.distributed.dp_size == 2 and cfg.distributed.tp_size == 2
    assert cfg.global_batch_size == 2 * 3 * 2


def test_create_config_has_no_flag_for_a_second_engine(tmp_path, capsys):
    """`--serve-disagg` went with the engine it selected: argparse refuses
    it by name, and writes nothing."""
    cc = load_tool("create_config")
    with pytest.raises(SystemExit) as e:
        cc.build_parser().parse_args([
            "--exp-name", "x", "--out-dir", str(tmp_path),
            "--model", "debug-tiny", "--serve-disagg"])
    assert e.value.code == 2
    assert "unrecognized arguments: --serve-disagg" in capsys.readouterr().err
    assert not list(tmp_path.iterdir())


def test_create_config_rejects_bad_layout(tmp_path):
    cc = load_tool("create_config")
    args = cc.build_parser().parse_args([
        "--exp-name", "bad", "--out-dir", str(tmp_path),
        "--model", "debug-tiny", "--tp", "3",  # 4 heads % 3 != 0
    ])
    with pytest.raises(ValueError):
        cc.create_single_config(args)


def test_extract_metrics_parses_log_line(tmp_path):
    from picotron_tpu.utils import training_log_line
    em = load_tool("extract_metrics")

    run = tmp_path / "dp4_tp2_pp1_cp1"
    run.mkdir()
    lines = [training_log_line(s, 5.0 - 0.1 * s, 12345.0, 1543.1, 0.1854,
                               s * 512) for s in range(1, 8)]
    (run / "train.log").write_text("\n".join(lines) + "\n")

    stats = em.process_file(str(run / "train.log"), skip_steps=3)
    assert stats["steps"] == 4  # steps 4..7
    assert stats["final_loss"] == pytest.approx(4.3)
    assert stats["mean_mfu_pct"] == pytest.approx(18.54)
    assert stats["mean_tokens_per_sec"] == pytest.approx(12300, rel=0.01)

    rows = em.aggregate(str(tmp_path), skip_steps=3)
    assert rows[0]["dp"] == 4 and rows[0]["tp"] == 2
    assert (run / "metrics.csv").exists()


def test_parse_human_inverts_human_format():
    from picotron_tpu.utils import human_format
    em = load_tool("extract_metrics")
    for v in (950.0, 12300.0, 27200000.0):
        assert em.parse_human(human_format(v)) == pytest.approx(v, rel=0.01)


def test_job_status_machine(tmp_path):
    sj = load_tool("submit_jobs")
    run = tmp_path / "run_a"
    run.mkdir()
    (run / "config.json").write_text("{}")

    jobs = sj.discover_jobs(str(tmp_path))
    assert len(jobs) == 1
    job = jobs[0]
    assert job.status == "init"
    job.set_status("running")
    assert job.status == "running"

    # post-mortem classification (ref: base_job.slurm:82-94)
    (run / "train.log").write_text("... RESOURCE_EXHAUSTED: out of memory ...")
    assert job.classify(returncode=1) == "oom"
    (run / "train.log").write_text("... DEADLINE_EXCEEDED ...")
    assert job.classify(returncode=1) == "timeout"
    (run / "train.log").write_text("some other crash")
    assert job.classify(returncode=1) == "fail"
    assert job.classify(returncode=0) == "completed"


def test_create_config_from_hf_config_json(tmp_path):
    """--from-hf-config: the offline AutoConfig (VERDICT r3 missing #1) —
    a non-preset Llama-family model resolves from its local config.json;
    Qwen2 model_type implies qkv bias; Mixtral fields map to the MoE
    knobs; unsupported architectures are rejected."""
    import json

    hf = {
        "model_type": "qwen2", "vocab_size": 1024, "hidden_size": 64,
        "intermediate_size": 128, "num_hidden_layers": 2,
        "num_attention_heads": 4, "num_key_value_heads": 2,
        "max_position_embeddings": 512, "rope_theta": 1e6,
        "rms_norm_eps": 1e-6, "tie_word_embeddings": True,
    }
    p = tmp_path / "config.json"
    p.write_text(json.dumps(hf))

    cc = load_tool("create_config")
    args = cc.build_parser().parse_args([
        "--exp-name", "custom", "--out-dir", str(tmp_path),
        "--model", "my-custom-model", "--from-hf-config", str(p),
        "--dp", "2", "--seq-len", "64", "--mbs", "1", "--grad-acc", "1",
        "--use-cpu",
    ])
    path = cc.create_single_config(args)
    from picotron_tpu.config import load_config, model_config_from_hf_json
    cfg = load_config(path)
    assert cfg.model.vocab_size == 1024
    assert cfg.model.num_hidden_layers == 2
    assert cfg.model.rope_theta == 1e6
    assert cfg.model.attention_bias is True  # qwen2 implies qkv bias
    assert cfg.model.tie_word_embeddings is True
    cfg.validate()

    moe = model_config_from_hf_json({
        "model_type": "mixtral", "vocab_size": 512, "hidden_size": 32,
        "intermediate_size": 64, "num_hidden_layers": 2,
        "num_attention_heads": 4, "num_local_experts": 8,
        "num_experts_per_tok": 2,
    })
    assert moe["num_experts"] == 8 and moe["num_experts_per_token"] == 2
    assert moe["attention_bias"] is False

    with pytest.raises(ValueError, match="not a supported"):
        model_config_from_hf_json({"model_type": "gpt_bigcode",
                                   "vocab_size": 1, "hidden_size": 1,
                                   "intermediate_size": 1,
                                   "num_hidden_layers": 1,
                                   "num_attention_heads": 1})


def test_slurm_render_golden(tmp_path):
    """The sbatch branch's render (ref: submit_slurm_jobs.py:68-103): the
    script must carry the exact #SBATCH directives, the status.txt state
    transitions, and grep alternations built from the SAME pattern
    constants the local launcher classifies with."""
    sj = load_tool("submit_jobs")
    run = tmp_path / "llama-dp8"
    run.mkdir()
    (run / "config.json").write_text("{}")
    job = sj.discover_jobs(str(tmp_path))[0]

    script = sj.render_slurm(job, nodes=4, time_limit="03:30:00")
    assert script == str(run / "job.slurm")
    text = open(script).read()
    expected = sj.SLURM_TEMPLATE.format(
        name="llama-dp8", nodes=4, run_dir=str(run),
        time_limit="03:30:00", repo_root=sj.REPO_ROOT,
        oom_re="|".join(sj.OOM_PATTERNS),
        timeout_re="|".join(sj.TIMEOUT_PATTERNS))
    assert text == expected
    # structural invariants a template edit must not silently break
    assert "#SBATCH --job-name=llama-dp8" in text
    assert "#SBATCH --nodes=4" in text
    assert "#SBATCH --time=03:30:00" in text
    assert f"srun python -m picotron_tpu.train --config {run}/config.json" \
        in text
    for state in ("running", "completed", "oom", "timeout", "fail"):
        assert f"echo {state} > " in text
    assert "RESOURCE_EXHAUSTED|Out of memory|OutOfMemoryError" in text


def test_slurm_dry_run_renders_without_submitting(tmp_path, capsys,
                                                  monkeypatch):
    """--dry-run must render + print the script, call NO sbatch, and leave
    status.txt untouched (VERDICT r3: the sbatch branch had never executed,
    not even render-only)."""
    import subprocess as sp

    sj = load_tool("submit_jobs")
    run = tmp_path / "run_a"
    run.mkdir()
    (run / "config.json").write_text("{}")

    def boom(*a, **k):
        raise AssertionError("dry run must not invoke subprocess")

    monkeypatch.setattr(sp, "run", boom)
    monkeypatch.setattr(
        sys, "argv",
        ["submit_jobs", str(tmp_path), "--launcher", "slurm", "--dry-run"])
    sj.main()
    out = capsys.readouterr().out
    assert "rendered" in out and "srun python -m picotron_tpu.train" in out
    assert (run / "job.slurm").exists()
    assert (run / "status.txt").read_text().strip() == "init"


def test_watch_queue_flips_pending_to_running_and_catches_dead(tmp_path,
                                                               monkeypatch):
    """The squeue poller (ref: base_job.slurm:16-32): PENDING -> RUNNING
    when SLURM starts the job; a job that leaves the queue while still
    'pending' (killed before its script's first line) is marked fail
    instead of dangling forever."""
    import subprocess as sp

    sj = load_tool("submit_jobs")
    for name in ("run_a", "run_b"):
        d = tmp_path / name
        d.mkdir()
        (d / "config.json").write_text("{}")
    job_a, job_b = sj.discover_jobs(str(tmp_path))
    job_a.set_status("pending")
    job_b.set_status("pending")

    # poll 1: a PENDING, b RUNNING; poll 2: a gone (never started), b gone
    polls = iter([
        "1001 PENDING\n1002 RUNNING\n",
        "",
    ])

    class R:
        def __init__(self, out):
            self.stdout = out
            self.returncode = 0

    def fake_run(cmd, **kw):
        assert cmd[0] == "squeue"
        return R(next(polls))

    monkeypatch.setattr(sp, "run", fake_run)
    monkeypatch.setattr(sj.time, "sleep", lambda s: None)
    sj.watch_queue(str(tmp_path), {"run_a": "1001", "run_b": "1002"},
                   interval=0, max_polls=2)
    assert job_a.status == "fail"      # left queue while pending
    assert job_b.status == "running"   # started; epilogue owns the rest


def test_dry_run_requires_slurm_launcher(tmp_path, monkeypatch):
    sj = load_tool("submit_jobs")
    monkeypatch.setattr(
        sys, "argv", ["submit_jobs", str(tmp_path), "--dry-run"])
    with pytest.raises(SystemExit):
        sj.main()


def test_extract_metrics_harvests_extras_and_val_loss(tmp_path):
    """The harvester picks up trailing extras (moe_drop_frac) and dedicated
    eval lines from the de-facto log-line API."""
    import sys

    sys.path.insert(0, "tools")
    from extract_metrics import process_file

    log = tmp_path / "train.log"
    lines = []
    for s in range(1, 7):
        lines.append(
            f"[step {s:06d}] loss: 5.{s}000 | tokens/s: 1.5K | "
            f"tokens/s/chip: 750 | MFU: 45.00% | tokens: 10K | "
            f"mem: 1.0GB | moe_drop_frac: 0.0{s}00")
    lines.append("[eval  000004] val_loss: 5.4321 (8 batches)")
    lines.append("[eval  000006] val_loss: 5.2100 (8 batches)")
    log.write_text("\n".join(lines))
    out = process_file(str(log))
    assert abs(out["mean_moe_drop_frac"] - 0.05) < 1e-9  # steps 4..6
    assert out["final_val_loss"] == 5.21


def test_extract_metrics_extras_skip_stable_suffixed_fields(tmp_path):
    import sys

    sys.path.insert(0, "tools")
    from extract_metrics import process_file

    log = tmp_path / "train.log"
    log.write_text(
        "[step 000004] loss: 5.0000 | tokens/s: 1.5K | tokens/s/chip: 750 "
        "| MFU: 45.00% | tokens: 10K | mem: 1.0GB\n"
        "[step 000005] loss: 5.0000 | tokens/s: 1.5K | tokens/s/chip: 750 "
        "| MFU: 45.00% | tokens: 20K | mem: 1.0GB\n")
    out = process_file(str(log))
    assert "mean_tokens" not in out and "mean_mem" not in out


def test_extract_metrics_serve_columns(tmp_path):
    """A serving-only telemetry stream (no train steps) must still yield
    a harvest row: serve_* TTFT/TPOT columns from the serve_summary
    event."""
    import jax
    import numpy as np

    from picotron_tpu.config import ModelConfig, ServeConfig, resolve_preset
    from picotron_tpu.models.llama import init_params
    from picotron_tpu.serve import ServeEngine
    from picotron_tpu.telemetry import JsonlSink, Telemetry

    cfg = ModelConfig(dtype="float32", **{
        **resolve_preset("debug-tiny"), "max_position_embeddings": 64})
    params = init_params(cfg, jax.random.key(0))
    rng = np.random.default_rng(0)
    requests = [(list(map(int, rng.integers(0, cfg.vocab_size, size=n))), m)
                for n, m in ((5, 6), (9, 3), (3, 8))]
    run_dir = tmp_path / "serve_run"
    run_dir.mkdir()
    path = str(run_dir / "telemetry.jsonl")
    tel = Telemetry(sinks=[JsonlSink(path)])
    eng = ServeEngine(params, cfg, ServeConfig(
        decode_slots=3, block_size=4, num_blocks=24, prefill_chunk=4,
        max_model_len=32, decode_interval=3), telemetry=tel)
    eng.run(requests)
    tel.close()

    stats = load_tool("extract_metrics").process_telemetry(path)
    assert stats is not None
    assert stats["serve_requests"] == len(requests)
    assert stats["serve_output_tokens"] == 6 + 3 + 8
    assert stats["serve_ttft_p50_ms"] >= 0
    assert stats["serve_tpot_p50_ms"] >= 0
    assert "serve_decode_stall_ticks_max" in stats


def test_telemetry_report_cli_markdown_smoke(tmp_path, capsys):
    """tools/telemetry_report.py end-to-end over a tiny synthetic stream:
    the CLI path resolution (run dir -> telemetry.jsonl), the summarize
    pass, and the --markdown render contract the docs reference."""
    import json

    events = [
        {"ts": 1.0, "kind": "phase", "phase": "step", "step": 1,
         "category": "compute", "secs": 8.0},
        {"ts": 2.0, "kind": "phase", "phase": "save", "step": 1,
         "category": "ckpt_io", "secs": 2.0},
        {"ts": 3.0, "kind": "step", "step": 1, "loss": 3.25,
         "tokens_per_sec": 1500.0},
        {"ts": 4.0, "kind": "retry", "category": "retry_backoff",
         "secs": 0.5, "target": "checkpoint save", "attempt": 1},
    ]
    with open(tmp_path / "telemetry.jsonl", "w") as f:
        for e in events:
            f.write(json.dumps(e) + "\n")

    tr = load_tool("telemetry_report")
    assert tr.main([str(tmp_path), "--markdown"]) == 0
    out = capsys.readouterr().out
    assert "goodput 76.19%" in out  # 8 / (8 + 2 + 0.5)
    assert "| category | seconds | share |" in out
    assert "| compute | 8.000 | 76.2% |" in out
    assert "retry=1" in out
    # --json emits one machine-readable object
    assert tr.main([str(tmp_path / "telemetry.jsonl"), "--json"]) == 0
    row = json.loads(capsys.readouterr().out)
    assert row["steps"]["count"] == 1
    assert row["categories"]["ckpt_io"] == 2.0
    # an empty stream is an error, not a zero-filled report
    (tmp_path / "empty.jsonl").write_text("")
    assert tr.main([str(tmp_path / "empty.jsonl")]) == 1


def test_telemetry_report_comm_row(tmp_path, capsys):
    """--config adds the `comm` row: the ICI cost model's predicted comm
    time next to the measured sync-phase median, so calibration drift is
    visible per run."""
    import json

    events = [
        {"ts": 1.0, "kind": "phase", "phase": "step", "step": 1,
         "category": "compute", "secs": 0.5},
        {"ts": 1.5, "kind": "phase", "phase": "sync", "step": 1,
         "category": "logging", "secs": 0.02},
        {"ts": 2.0, "kind": "phase", "phase": "step", "step": 2,
         "category": "compute", "secs": 0.5},
        {"ts": 2.5, "kind": "phase", "phase": "sync", "step": 2,
         "category": "logging", "secs": 0.03},
    ]
    with open(tmp_path / "telemetry.jsonl", "w") as f:
        for e in events:
            f.write(json.dumps(e) + "\n")
    cfg_path = tmp_path / "config.json"
    cfg_path.write_text(json.dumps({
        "distributed": {"dp_size": 2, "tp_size": 2},
        "model": {"name": "debug-tiny"},
        "training": {"seq_length": 64, "micro_batch_size": 1,
                     "gradient_accumulation_steps": 2},
    }))

    tr = load_tool("telemetry_report")
    assert tr.main([str(tmp_path), "--config", str(cfg_path),
                    "--generation", "v5e", "--json"]) == 0
    row = json.loads(capsys.readouterr().out)
    comm = row["comm"]
    assert comm["generation"] == "v5e"
    assert comm["predicted_comm_ms"] > 0
    assert comm["measured_sync_p50_ms"] == 30.0
    assert "comm_drift_pct" in comm
    # text render carries the row too
    assert tr.main([str(tmp_path), "--config", str(cfg_path)]) == 0
    assert "comm [v5e]: predicted" in capsys.readouterr().out
    # without --config the row is absent (no silent v5e default)
    assert tr.main([str(tmp_path), "--json"]) == 0
    assert "comm" not in json.loads(capsys.readouterr().out)


def test_telemetry_report_comm_row_without_sync_records(tmp_path, capsys):
    """Regression: a stream with NO sync-phase records (an MPMD run, or a
    telemetry.jsonl cut before the first optimizer step) must render the
    comm row's measured side as 'n/a' — not crash, not print None."""
    import json

    events = [
        {"ts": 1.0, "kind": "phase", "phase": "step", "step": 1,
         "category": "compute", "secs": 0.5},
        {"ts": 2.0, "kind": "phase", "phase": "step", "step": 2,
         "category": "compute", "secs": 0.5},
    ]
    with open(tmp_path / "telemetry.jsonl", "w") as f:
        for e in events:
            f.write(json.dumps(e) + "\n")
    cfg_path = tmp_path / "config.json"
    cfg_path.write_text(json.dumps({
        "distributed": {"dp_size": 2, "tp_size": 2},
        "model": {"name": "debug-tiny"},
        "training": {"seq_length": 64, "micro_batch_size": 1,
                     "gradient_accumulation_steps": 2},
    }))

    tr = load_tool("telemetry_report")
    assert tr.main([str(tmp_path), "--config", str(cfg_path),
                    "--json"]) == 0
    comm = json.loads(capsys.readouterr().out)["comm"]
    assert comm["measured_sync_p50_ms"] is None
    assert "comm_drift_pct" not in comm
    for flags in ([], ["--markdown"]):
        assert tr.main([str(tmp_path), "--config", str(cfg_path),
                        *flags]) == 0
        out = capsys.readouterr().out
        assert "measured sync p50 n/a" in out
        assert "None ms" not in out


ROOT = os.path.abspath(os.path.join(TOOLS, ".."))


def _run_py(args, env_extra, cwd=ROOT, timeout=180):
    import subprocess

    env = dict(os.environ, **env_extra)
    env.pop("XLA_FLAGS", None)  # the conftest CPU forcing must not leak
    return subprocess.run([sys.executable, *args], capture_output=True,
                          text=True, env=env, cwd=cwd, timeout=timeout)


def test_bench_refuses_to_measure_without_a_chip():
    """bench.py is a measurement path: with no TPU it ends in ONE line and a
    non-zero code — whether JAX sits on the CPU (JAX_PLATFORMS=cpu in the
    environment is not consent; only --cpu is) or no backend comes up at
    all — never a traceback, never a JSON row."""
    for platforms, story in (("cpu", "no accelerator"),
                             ("cuda", "no JAX backend came up")):
        res = _run_py(["bench.py", "--steps", "1"],
                      {"JAX_PLATFORMS": platforms})
        assert res.returncode != 0, platforms
        assert story in res.stderr, res.stderr
        assert "Traceback" not in res.stderr
        assert "{" not in res.stdout


def test_chip_smoke_refuses_cpu_and_bare_directory(tmp_path):
    """chip_smoke.py has no CPU mode: on a CPU-only host it exits non-zero
    with one line and prints no result; alone in a directory (without the
    program) it does the same."""
    import shutil

    res = _run_py(["chip_smoke.py"], {"JAX_PLATFORMS": "cpu"})
    assert res.returncode != 0
    lines = [l for l in res.stderr.splitlines() if l.strip()]
    assert len(lines) == 1 and lines[0].startswith("chip_smoke: no "), \
        res.stderr
    assert '"ok"' not in res.stdout

    shutil.copy(os.path.join(ROOT, "chip_smoke.py"), tmp_path)
    res = _run_py(["chip_smoke.py"], {"JAX_PLATFORMS": "cpu"},
                  cwd=str(tmp_path))
    assert res.returncode != 0
    assert "cannot import the program" in res.stderr
    assert "Traceback" not in res.stderr and '"ok"' not in res.stdout


def test_compile_cache_helper_paths():
    """setup_compile_cache: JAX_COMPILATION_CACHE_DIR wins and nothing else
    is set in code; otherwise the fixed <checkout>/.jax_cache; a process
    pinned to the CPU platform gets no cache. One fresh interpreter, no
    backend initialized."""
    import json

    code = (
        "import json, os, jax\n"
        "from picotron_tpu.utils import setup_compile_cache\n"
        "out = {}\n"
        "out['env'] = setup_compile_cache()\n"
        "out['env_cfg'] = jax.config.jax_compilation_cache_dir\n"
        "del os.environ['JAX_COMPILATION_CACHE_DIR']\n"
        "out['fixed'] = setup_compile_cache()\n"
        "out['fixed_cfg'] = jax.config.jax_compilation_cache_dir\n"
        "jax.config.update('jax_platforms', 'cpu')\n"
        "out['cpu'] = setup_compile_cache()\n"
        "print(json.dumps(out))\n")
    env = {"JAX_COMPILATION_CACHE_DIR": "/x", "JAX_PLATFORMS": ""}
    res = _run_py(["-c", code], env)
    assert res.returncode == 0, res.stderr
    out = json.loads(res.stdout.strip().splitlines()[-1])
    assert out["env"] == "/x" and out["env_cfg"] == "/x"
    assert out["fixed"] == out["fixed_cfg"] == os.path.join(ROOT, ".jax_cache")
    assert out["cpu"] is None
    assert not os.path.exists("/x")


def test_bench_sweep_parent_stays_off_the_backend():
    """One process per chip: `bench.py --sweep` must not create a JAX
    backend before (or between) its children, and a row that produces no
    value fails the sweep. Drives bench.main in a fresh interpreter with
    subprocess.run replaced by a recorder, so it asserts on the parent's
    code path — no chip, no real children."""
    import json

    code = (
        "import json, subprocess, sys\n"
        "from jax._src import xla_bridge\n"
        "import bench\n"
        "seen = []\n"
        "def fake_run(cmd, **kw):\n"
        "    seen.append(xla_bridge.backends_are_initialized())\n"
        "    bad = '--optimizer-offload' in cmd\n"
        "    row = json.dumps({'metric': 'm', 'value': 0.5})\n"
        "    return subprocess.CompletedProcess(\n"
        "        cmd, 1 if bad else 0, '' if bad else row + '\\n',\n"
        "        'boom' if bad else '')\n"
        "subprocess.run = fake_run\n"
        "try:\n"
        "    bench.main(['--sweep', '--steps', '1', '--warmup', '1'])\n"
        "    rc = 0\n"
        "except SystemExit as e:\n"
        "    rc = e.code\n"
        "print(json.dumps({'seen': seen, 'rc': str(rc),\n"
        "                  'after': xla_bridge.backends_are_initialized()}))\n")
    res = _run_py(["-c", code], {"JAX_PLATFORMS": "cpu"})
    assert res.returncode == 0, res.stderr
    out = json.loads(res.stdout.strip().splitlines()[-1])
    assert len(out["seen"]) >= 2 * 8 and not any(out["seen"])
    assert out["after"] is False
    # the four offload rows "errored": the sweep says so and exits non-zero
    assert "4 of 8 row(s) produced no value" in out["rc"]
    rows = [json.loads(l) for l in res.stdout.strip().splitlines()[:-1]]
    assert sum("error" in r for r in rows) == 4
    assert sum(r.get("value") == 0.5 for r in rows) == 4


def test_shardcheck_cli_smoke(capsys):
    """tools/shardcheck.py end-to-end on the CPU backend: preset
    resolution, the full analyzer stack, and the JSON output contract
    (the acceptance-criteria entry point: `python tools/shardcheck.py
    --preset ...` runs green without a TPU)."""
    import json

    sc = load_tool("shardcheck")
    rc = sc.main(["--preset", "tiny-1chip", "--json"])
    out = capsys.readouterr().out.strip().splitlines()
    row = json.loads(out[-1])
    assert rc == 0
    assert row["config"] == "preset:tiny-1chip"
    assert row["ok"] is True and row["errors"] == 0
    assert row["info"]["donation"]["donated"] == \
        row["info"]["donation"]["state_leaves"]


def test_bench_decode_harness_smoke():
    """bench.run_decode end-to-end at debug-tiny scale on the CPU backend:
    the prefill/decode differencing, the JSON schema, and the
    place_for_decode plumbing must not bitrot between hardware runs (the
    real numbers come from `bench.py --decode` on the chip; PERF.md r5)."""
    import sys

    sys.path.insert(0, os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))))
    import bench

    # 24 tokens, best of 2: at 4 tokens and one repetition the decode part
    # (0.1 ms on this model) sat inside a loaded host's timing jitter and the
    # differencing refused, as it should, one run in some (PR 39's whole run)
    row = bench.run_decode("debug-tiny", 0, prompt_len=16, max_new=24,
                           batch=2, steps=2)
    assert row["unit"] == "decode_tokens_per_sec"
    assert row["value"] > 0
    assert row["prefill_tokens_per_sec"] > 0
    assert row["batch"] == 2 and row["max_new_tokens"] == 24


def test_bench_decode_tp_sharded_smoke():
    """bench.py --decode --tp 2 on the CPU mesh: the tp-sharded decode
    plumbing (place_for_decode through run_decode) and the metric naming —
    greedy token parity of the sharded decode itself is pinned by
    tests/test_generate.py; the 7B anchor is `--model Llama-2-7B
    --layers 4` on hardware (VERDICT r5 next #6)."""
    import sys

    sys.path.insert(0, os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))))
    import bench

    row = bench.run_decode("debug-tiny", 0, prompt_len=16, max_new=24,
                           batch=2, steps=2, tp=2)
    assert row["tp"] == 2
    assert row["metric"].endswith("-tp2")
    assert row["value"] > 0


def test_bench_bwd_grid_sweep_smoke():
    """--bwd-grid-sweep structural smoke on the CPU backend: every combo
    row carries the schema (block shape, pair timing, roofline fraction)
    and flags itself as the jnp fallback; the 16k numbers come from
    hardware (PERF.md)."""
    import sys

    sys.path.insert(0, os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))))
    import bench

    rows = bench.run_bwd_grid_sweep("debug-tiny", seq=128, batch=1,
                                    steps=1, blocks=[(64, 64), (128, 64)])
    assert len(rows) == 2
    for row in rows:
        assert row["is_tpu_kernel"] is False
        assert row["pair_ms"] > 0 and row["fwd_ms"] > 0
        assert row["unit"] == "pair_fraction_of_peak"
