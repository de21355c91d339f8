"""trace_reduce.py on a synthetic trace: busy union, idle share, self times,
gap attribution; and on a small trace recorded here through the profiler."""
import os
import sys

HERE = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, HERE)
import trace_reduce as tr  # noqa: E402

MS = 1e6  # ns


def synthetic():
    ops = [("%while.1 = (f32[12,64]{1,0:T(8,128)}, s32[]) while(%tuple.1), body=%b", 10 * MS, 40 * MS),  # parent: 10..50
           ("fusion.a", 10 * MS, 10 * MS),       #   child 10..20
           ("fusion.b", 25 * MS, 20 * MS),       #   child 25..45
           ("all-reduce.3", 60 * MS, 10 * MS),   # 60..70
           ("fusion.a", 90 * MS, 5 * MS)]        # 90..95
    host = [("bench.window", 0.0, 100 * MS),
            ("engine.step", 0.0, 52 * MS), ("wait.arrival", 52 * MS, 6 * MS),
            ("engine.step", 58 * MS, 14 * MS), ("wait.arrival", 72 * MS, 17 * MS),
            ("engine.step", 89 * MS, 11 * MS)]
    return [("/device:TPU:0", [("XLA Ops", ops), ("Steps", [("0", 0.0, 100 * MS)])]),
            ("/host:CPU", [("python3", host)])]


def test_union_and_complement():
    assert tr.union([(5, 7), (1, 3), (2, 4), (7, 8)]) == [(1, 4), (5, 8)]
    assert tr.complement([(1, 4), (5, 8)], 0, 10) == [(0, 1), (4, 5), (8, 10)]


def test_reduce_synthetic():
    red = tr.reduce(synthetic(), span_names=("engine.step", "wait.arrival"))
    assert abs(red["window_s"] - 0.100) < 1e-12          # the harness's window span
    assert abs(red["busy_s"] - 0.055) < 1e-12            # 40 + 10 + 5 ms; "Steps" is no op
    ops = red["op_seconds"]
    assert abs(ops["while.1"] - 0.010) < 1e-12           # 40 less children's 30
    assert abs(ops["fusion.a"] - 0.015) < 1e-12 and red["op_calls"]["fusion.a"] == 2
    assert abs(ops["all-reduce.3"] - 0.010) < 1e-12
    idle = dict(red["idle_by_span"])
    # gaps: 0-10 (step), 50-60 (2 ms step, 6 ms wait, 2 ms step -> wait), 70-90
    # (17 ms wait), 95-100 (step)
    assert abs(idle["wait.arrival"] - 0.030) < 1e-12
    assert abs(idle["engine.step"] - 0.015) < 1e-12
    assert red["longest_gaps"][0] == ("wait.arrival", 0.020)
    bd = tr.breakdown(red)
    assert bd["device_ops"][0][0] == "fusion.b" and len(bd["idle_gaps"]) == 2
    assert ["while.1 f32[12,64]", 0.010] in [[n, round(s, 9)] for n, s in bd["device_ops"]]


def test_short_name():
    assert tr.short_name("%closed_call.79 = (bf16[1,12,4096,128]{3,2,1,0}, f32[1]) custom-call(%a)") == (
        "closed_call.79", "bf16[1,12,4096,128]")
    assert tr.short_name("%copy-done.3 = f32[8]{0} copy-done(%x)") == ("copy-done.3", "f32[8]")
    assert tr.short_name("dot_general.1") == ("dot_general.1", "")


def test_window_falls_back_to_device_extent_without_the_span():
    planes = [p for p in synthetic() if p[0].startswith("/device")]
    red = tr.reduce(planes)
    assert abs(red["window_s"] - 0.085) < 1e-12 and red["per_device"][0]["window_from"] == "device_events"
    assert red["idle_by_span"][0][0] == "(no harness span)"


def test_no_device_plane_gives_nothing():
    assert tr.reduce([("/host:CPU", [("python3", [("x", 0.0, 1.0)])])]) is None


def test_recorded_trace_loads(tmp_path):
    os.environ.setdefault("JAX_PLATFORMS", "cpu")
    import jax
    import jax.numpy as jnp

    f = jax.jit(lambda x: (x @ x).sum())
    x = jnp.ones((64, 64))
    f(x).block_until_ready()
    jax.profiler.start_trace(str(tmp_path))
    with jax.profiler.TraceAnnotation("bench.window"):
        f(x).block_until_ready()
    jax.profiler.stop_trace()
    planes = tr.load_xplane(str(tmp_path))
    assert [n for n, s, e in tr.host_spans(planes, ["bench.window"])] == ["bench.window"]
