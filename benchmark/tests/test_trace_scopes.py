"""trace_scopes.py: the wire reading against a hand-encoded xplane and against
`jax.profiler.ProfileData` on a recorded trace; programs, scope self times
and annotations on synthetic planes; the four readers on a small CPU trace of
a tiny `ServeEngine` (counts and structure only: nothing here is a time of
a device)."""
import glob
import os
import struct
import sys
import types

HERE = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, HERE)
sys.path.insert(0, os.path.dirname(HERE))
import trace_scopes as ts  # noqa: E402

from run import load_module  # noqa: E402

MS = 1e6  # ns


# ---- a hand-encoded xplane -------------------------------------------------


def vi(x):
    x &= (1 << 64) - 1
    out = b""
    while True:
        out += bytes([(x & 0x7F) | (0x80 if x > 0x7F else 0)])
        x >>= 7
        if not x:
            return out


def fld(num, value):
    if isinstance(value, int):
        return vi(num << 3) + vi(value)
    if isinstance(value, float):
        return vi(num << 3 | 1) + struct.pack("<d", value)
    if isinstance(value, str):
        value = value.encode()
    return vi(num << 3 | 2) + vi(len(value)) + value


def xstat(mid, **kw):
    (kind, v), = kw.items()
    return fld(1, mid) + fld({"f": 2, "u": 3, "i": 4, "s": 5, "ref": 7}[kind], v)


def xplane(name, stat_names, metas, lines):
    b = fld(2, name)
    for sid, sname in stat_names.items():
        b += fld(5, fld(1, sid) + fld(2, fld(1, sid) + fld(2, sname)))
    for mid, (mname, stats) in metas.items():
        b += fld(4, fld(1, mid) + fld(2, fld(1, mid) + fld(2, mname) + b"".join(fld(5, s) for s in stats)))
    for lname, t0, events in lines:
        ev = b"".join(fld(4, fld(1, mid) + fld(2, off) + fld(3, dur) + b"".join(fld(4, s) for s in st))
                      for mid, off, dur, st in events)
        b += fld(3, fld(2, lname) + fld(3, t0) + ev)
    return fld(1, b)


def encoded():
    dev = xplane(
        "/device:TPU:0", {1: "tf_op", 2: "flops", 3: "jit(train_step)/optimizer/mul:"},
        {1: ("%fusion.1 = f32[8] fusion(%a)", [xstat(1, s="jit(train_step)/while/body/jvp(head_ce)/dot_general:"), xstat(2, u=99)]),
         2: ("%while.2 = () while(%t)", [xstat(1, s="jit(train_step)/while:")]),
         3: ("%fusion.3 = f32[8] fusion(%b)", [xstat(1, ref=3)]),
         4: ("jit_train_step(123)", [])},
        [("XLA Modules", 1000, [(4, 0, int(50 * MS * 1000), [])]),
         ("XLA Ops", 1000, [(2, 0, int(40 * MS * 1000), []),            # 0..40 ms, parent
                            (1, int(5 * MS * 1000), int(10 * MS * 1000), []),   # child 5..15
                            (3, int(41 * MS * 1000), int(4 * MS * 1000), [])])])  # 41..45
    host = xplane(
        "/host:CPU", {1: "tokens", 2: "ids", 3: "neg"},
        {1: ("bench.window", []), 2: ("serve.prefill.dispatch", [])},
        [("python", 0, [(1, 0, int(60 * MS * 1000), []),
                        (2, int(2 * MS * 1000), int(3 * MS * 1000),
                         [xstat(1, i=100), xstat(2, s="1000 1001"), xstat(3, i=-5)])])])
    return dev + host


def test_wire_reading_of_a_hand_encoded_trace():
    planes = ts.parse(encoded())
    assert [p for p, _ in planes] == ["/device:TPU:0", "/host:CPU"]
    ops = dict(planes[0][1])["XLA Ops"]
    assert ops[1][:3] == ("%fusion.1 = f32[8] fusion(%a)", 1000 + 5 * MS, 10 * MS)
    assert ops[1][3] == {"tf_op": "jit(train_step)/while/body/jvp(head_ce)/dot_general:", "flops": 99}
    assert ops[2][3]["tf_op"] == "jit(train_step)/optimizer/mul:"  # a referenced string
    assert ts.window(planes) == (0.0, 60 * MS)
    (ann,) = ts.annotations(planes, ["serve.prefill.dispatch"])
    assert ann == ("serve.prefill.dispatch", 2 * MS, 5 * MS, {"tokens": 100, "ids": "1000 1001", "neg": -5})


def test_programs_and_scope_seconds():
    planes = ts.parse(encoded())
    assert ts.program_name("jit_serve_prefill(13755724650115383957)") == "serve_prefill"
    assert ts.programs(planes, *ts.window(planes)) == {0: {"train_step": [50 * MS]}}
    assert ts.programs(planes, 0.0, 10 * MS) == {0: {}}  # not inside that window
    words = ts.scope_seconds(planes)[0]
    assert abs(words["head_ce"] - 0.010) < 1e-12      # under a transform
    assert abs(words["optimizer"] - 0.004) < 1e-12
    assert abs(words["while"] - 0.040) < 1e-12        # the parent's 30 ms + the child under it
    assert abs(words["train_step"] - 0.044) < 1e-12   # self times add up to the busy time
    assert "attention" not in words
    assert ts.scope_words("jit(train_step)/transpose(jvp(mlp))/dot_general:") >= {"mlp", "train_step"}


class Ctx(types.SimpleNamespace):
    def log(self, *a):
        self.logged.append(" ".join(map(str, a)))


def test_readers_on_a_cpu_trace_of_a_tiny_engine(tmp_path):
    os.environ.setdefault("JAX_PLATFORMS", "cpu")
    import jax
    from jax.profiler import ProfileData

    from picotron_tpu.config import ModelConfig, ServeConfig, resolve_preset
    from picotron_tpu.models.llama import init_params
    from picotron_tpu.serve import ServeEngine

    mcfg = ModelConfig(dtype="float32", **{
        **resolve_preset("debug-tiny"), "max_position_embeddings": 64})
    eng = ServeEngine(init_params(mcfg, jax.random.key(0)), mcfg,
                      ServeConfig(decode_slots=2, block_size=4, num_blocks=16,
                                  prefill_chunk=4, max_model_len=32, decode_interval=2))
    eng.submit(list(range(1, 6)), 3)
    while eng.sched.has_work():
        eng.step(0.0)
    jax.profiler.start_trace(str(tmp_path))
    with jax.profiler.TraceAnnotation("bench.window"):
        eng.submit(list(range(1, 6)), 4, req_id=7)
        eng.submit(list(range(1, 8)), 4, req_id=8)
        while eng.sched.has_work():
            eng.step(0.0)
    jax.profiler.stop_trace()
    eng.close()

    planes = ts.load(str(tmp_path))
    assert ts.load(str(tmp_path)) is planes  # read once per process
    # the same events as ProfileData gives, stats of the annotations included
    path = sorted(glob.glob(os.path.join(str(tmp_path), "**", "*.xplane.pb"), recursive=True))[-1]
    want = {p.name: [(ln.name, [(e.name, e.start_ns, e.duration_ns) for e in ln.events])
                     for ln in p.lines] for p in ProfileData.from_file(path).planes}
    for pname, lines in planes:
        assert [ln for ln, _ in lines] == [ln for ln, _ in want[pname]]
        for (_, got), (_, exp) in zip(lines, want[pname]):
            assert [e[0] for e in got] == [e[0] for e in exp]
            assert all(abs(g[1] - e[1]) < 1.0 and abs(g[2] - e[2]) < 1.0 for g, e in zip(got, exp))
    win = ts.window(planes)
    disp = ts.annotations(planes, ["serve.prefill.dispatch"], *win)
    assert sum(c["tokens"] for *_, c in disp) == 12 and all(c["capacity"] == 8 for *_, c in disp)

    # the readers: CPU traces have no device plane, so the device readers
    # report nothing and do not raise; the span readers read the counts
    ctx = Ctx(trace_dir=str(tmp_path), logged=[],
              trace=dict(first_device=0, per_device={0: dict(busy_s=1.0, window_s=1.0)}))
    facts = dict(decode_interval=2, traced_steps=3)
    fill = load_module("readers", "span_ratio").read(
        dict(span="serve.prefill.dispatch", num="tokens", den="capacity"), facts, ctx)
    assert abs(fill - 100.0 * 12 / (8 * len(disp))) < 1e-9
    host = load_module("readers", "span_self_ms").read(
        dict(parent="serve.step", minus=["serve.prefill.wait", "serve.decode.wait"],
             idle_gaps_by=["serve.admit", "serve.decode.emit"]), facts, ctx)
    steps = ts.annotations(planes, ["serve.step"], *win)
    assert 0.0 < host <= max(hi - lo for _, lo, hi, _ in steps) / 1e6
    assert any("idle gaps" in line for line in ctx.logged)
    assert load_module("readers", "program_ms").read(dict(program="serve_prefill"), facts, ctx) is None
    assert load_module("readers", "scope_ms").read(
        dict(scope="head_ce", device="max", unit="ms_per_step"), facts, ctx) is None
    # a program without the spans (the parent commit's): nothing, no error
    assert load_module("readers", "span_ratio").read(
        dict(span="serve.nothing", num="tokens", den="capacity"), facts, ctx) is None
    assert load_module("readers", "span_self_ms").read(
        dict(parent="serve.nothing", minus=[]), facts, ctx) is None
    # and no trace at all
    empty = Ctx(trace_dir=str(tmp_path / "none"), logged=[], trace=ctx.trace)
    for r, p in (("program_ms", dict(program="x")), ("scope_ms", dict(scope="x")),
                 ("span_ratio", dict(span="x", num="a", den="b")),
                 ("span_self_ms", dict(parent="x", minus=[]))):
        assert load_module("readers", r).read(p, facts, empty) is None


def test_device_readers_on_synthetic_planes(tmp_path, monkeypatch):
    planes = ts.parse(encoded())
    monkeypatch.setattr(ts, "load", lambda d: planes)
    ctx = Ctx(trace_dir="x", logged=[],
              trace=dict(first_device=0, per_device={0: dict(busy_s=0.044, window_s=0.060)}))
    facts = dict(traced_steps=2, decode_interval=4)
    assert load_module("readers", "program_ms").read(dict(program="train_step"), facts, ctx) == 50.0
    assert load_module("readers", "program_ms").read(
        dict(program="train_step", per_fact="decode_interval"), facts, ctx) == 12.5
    assert "sum 0.0500 s of 0.0440 s busy" in ctx.logged[0] and len(ctx.logged) == 1  # once a run
    load_module("readers", "program_ms").read(
        dict(program="train_step", log_scopes=["head_ce", "mlp"]), facts, ctx)
    assert ctx.logged[-1].endswith("head_ce 0.0100, mlp 0.0000")
    read = load_module("readers", "scope_ms").read
    assert abs(read(dict(scope="head_ce", device="max", unit="ms_per_step",
                         also_log=["optimizer"]), facts, ctx) - 5.0) < 1e-9
    assert ctx.logged[-1].endswith("optimizer 0.0040")
    assert abs(read(dict(scope="optimizer", unit="window_share"), facts, ctx) - 100 * 0.004 / 0.060) < 1e-9
    assert read(dict(scope="pp_boundary", device="max", unit="window_share"), facts, ctx) is None
