"""The hand-run tools' own arithmetic, on the CPU: `loop_model.py` prices a
prefill dispatch by its rung and keeps to the slots, `knee_sweep.py` builds one
cell file a rate."""
import importlib.util
import json
import os
import sys

import numpy as np

HERE = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def tool(name):
    spec = importlib.util.spec_from_file_location(name, os.path.join(HERE, "tools", name + ".py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def walk(reqs, slots=32):
    lm = tool("loop_model")
    return lm.walk(reqs, {1: 0.01, 4: 0.07, 16: 0.27, 32: 0.55}, 0.1, 0.0,
                   np.random.default_rng(0), 256, 4, slots)


def test_rungs_are_the_engines():
    sys.path.insert(0, os.path.dirname(HERE))
    from picotron_tpu.serve.engine import prefill_rungs

    lm = tool("loop_model")
    for n in (1, 4, 8, 32, 64):
        assert tuple(lm.rungs(n)) == tuple(prefill_rungs(n))


def test_a_prefill_dispatch_costs_by_its_rung():
    one = walk([(0.0, 256, 1)])
    two = walk([(0.0, 256, 1), (0.0, 256, 1)])
    assert one["prefill_dispatches_rung_1"] == 1 and two["prefill_dispatches_rung_4"] == 1
    assert abs(one["ttft_p90_ms"] - 10) < 1e-6 and abs(two["ttft_p90_ms"] - 70) < 1e-6


def test_requests_wait_for_a_slot():
    # two slots, three requests at t = 0: the third is admitted when one leaves
    r = walk([(0.0, 256, 5)] * 3, slots=2)
    assert r["prefill_dispatches"] == 2 and r["decode_dispatches"] >= 2
    assert r["ttft_p90_ms"] > 2 * 70


def test_knee_sweep_writes_one_cell_a_rate(monkeypatch, tmp_path):
    ks = tool("knee_sweep")
    calls = []

    class Done:
        returncode, stdout, stderr = 0, "[benchmark] x\n{}", ""

    monkeypatch.setattr(ks.subprocess, "run", lambda cmd, **kw: calls.append((cmd, kw)) or Done())
    monkeypatch.setattr(ks, "ROOT", str(tmp_path))
    os.symlink(os.path.join(os.path.dirname(HERE), "BENCHMARK.json"), tmp_path / "BENCHMARK.json")
    os.makedirs(tmp_path / "picotron_tpu")
    with open(os.path.join(os.path.dirname(HERE), "BENCHMARK.json")) as f:
        cell = next(w["name"] for w in json.load(f)["workloads"] if "chat" in w["name"])
    monkeypatch.setattr(sys, "argv", ["knee_sweep.py", cell, "5", "shape_seed=25", "seeds=3,4",
                                      "control=int8", "2", "2.5"])
    ks.main()
    assert len(calls) == 4 and calls[0][0][calls[0][0].index("--seed") + 1] == "3"
    tree = calls[0][1]["cwd"]
    with open(os.path.join(tree, "benchmark", "workloads", cell + ".r2.5.json")) as f:
        w = json.load(f)
    assert w["traffic"]["rate_per_s"] == 2.5 and w["traffic"]["shape_seed"] == 25
    assert w["control"] == "int8"
    with open(os.path.join(tree, "BENCHMARK.json")) as f:
        bench = json.load(f)
    e2e = next(m for m in bench["end_to_end"] if cell in m.get("workloads", ()))
    assert {cell + ".r2", cell + ".r2.5"} <= set(e2e["workloads"])


def test_measure_sets_names_the_smallest_bound_the_two_rules_allow(monkeypatch, tmp_path, capsys):
    ms = tool("measure_sets")
    # two sets of six: spreads of about 1.1% with one far run a set, medians 1000 and 1003
    values = iter([1000, 1004, 996, 1008, 992, 1100, 1003, 1007, 999, 1011, 995, 900, 1001])

    class Done:
        returncode, stderr = 0, ""

        def __init__(self, trace):
            v = next(values)
            self.stdout = "[benchmark] ttft p90 = rank 1\n" + json.dumps(dict(
                correct=True, attempted=9, failed=0, device={},
                facts=dict(ttft_ms_p90=v, tpot_ms_p90=40 + v / 1000, attempted=9),
                metrics={"ttft_p90_ms" if not trace else "x.serve": dict(value=v, unit="ms"),
                         **({} if trace else {"setup_s": dict(value=25.0, unit="s")})}))

    monkeypatch.setattr(ms.subprocess, "run",
                        lambda cmd, **kw: Done(cmd[cmd.index("--trace") + 1] == "1"))
    monkeypatch.setattr(ms, "ROOT", str(tmp_path))
    monkeypatch.setattr(sys, "argv", ["measure_sets.py", "some.cell", "51"])
    ms.main()
    out = capsys.readouterr().out
    line = next(ln for ln in out.splitlines() if ln.startswith("ttft_p90_ms: mean trimmed"))
    # trimmed spreads 1.2% and 1.2% (quartiles of five by `statistics.quantiles`): half of 0.02 is under that, half of 0.03 over
    assert "smallest bound not too tight 0.03" in line and "not too loose: True" in line, line
    assert "fact:tpot_ms_p90" in out and "setup_s: mean trimmed" not in out
    with open(tmp_path / "chiprun_out" / "sets.some.cell.json") as f:
        saved = json.load(f)
    assert len(saved["sets"]) == 2 and len(saved["sets"][0]) == 6 and len(saved["traced"]) == 1
