"""chat_lognormal: deterministic per seed, honours its clips, offers the same
work under every seed."""
import importlib.util
import os

HERE = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
spec = importlib.util.spec_from_file_location(
    "chat_lognormal", os.path.join(HERE, "traffic", "chat_lognormal.py"))
gen = importlib.util.module_from_spec(spec)
spec.loader.exec_module(gen)

P = {"rate_per_s": 5.0, "shape_seed": 3,
     "prompt_tokens": {"median": 512, "sigma": 0.8, "min": 32, "max": 3072},
     "output_tokens": {"median": 128, "sigma": 0.7, "min": 16, "max": 512}}


def test_deterministic_and_large_seed():
    a = gen.make(P, 2**31 + 77, 20.0, 1000)
    b = gen.make(P, 2**31 + 77, 20.0, 1000)
    assert a == b and len(a) > 50


def test_clips_and_window():
    reqs = gen.make(P, 1, 40.0, 1000)
    assert all(0 <= t < 40.0 for t, _, _ in reqs)
    assert [t for t, _, _ in reqs] == sorted(t for t, _, _ in reqs)
    assert all(32 <= len(p) <= 3072 and 16 <= n <= 512 for _, p, n in reqs)
    assert all(0 <= tok < 1000 for _, p, _ in reqs for tok in p)
    assert any(len(p) == 3072 or len(p) == 32 for _, p, _ in gen.make(P, 1, 60.0, 10))


def test_seeds_offer_the_same_work():
    a, b = gen.make(P, 1, 30.0, 1000), gen.make(P, 2, 30.0, 1000)
    assert [(t, len(p), n) for t, p, n in a] == [(t, len(p), n) for t, p, n in b]
    assert a[0][1] != b[0][1]  # other tokens
