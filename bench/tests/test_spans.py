"""Engine spans and named programs in the trace reduction (bench/spans.py)
and the readers built on them, on a hand-made trace written as an
``.xplane.pb`` (bench/tests/xspace.py) and read back through
``jax.profiler.ProfileData``; and on the small chip trace that
bench/tests/record_trace.py records into bench/testdata/, where present.

    JAX_PLATFORMS=cpu PYTHONPATH=src:. python -m pytest -q bench/tests
"""

from pathlib import Path

import pytest

from bench import run, spans, trace
from bench.tests import xspace

PB = Path(__file__).resolve().parents[1] / "testdata" / "tiny_chat.xplane.pb"

# host spans, nanoseconds: two engine steps inside the harness's, with a
# wait for an arrival between them; window [1000, 3500)
BENCH_SPANS = [("bench.step", 1000, 1000, None), ("bench.wait", 2000, 500,
                                                   None),
               ("bench.step", 2500, 1000, None)]
ENGINE_SPANS = [
    ("engine.step", 1010, 980, {"active": 1}),
    ("engine.admit", 1020, 280, None),
    ("engine.prefill", 1030, 20, {"rows": 1, "tokens": 512, "hit": 0,
                                  "rids": "3", "kind": "first"}),
    ("engine.sync", 1100, 100, {"what": "prefill"}),
    ("engine.prefill_sync", 1200, 90, None),
    ("engine.plan", 1300, 50, {"cow": 0}),
    ("engine.decode", 1350, 20, {"steps": 8, "rows": 2, "rids": "1 3"}),
    ("engine.sync", 1370, 530, {"what": "decode"}),
    ("engine.emit", 1900, 80, None),
    ("engine.step", 2510, 980, {"active": 2}),
    ("engine.prefill", 2512, 3, {"rows": 1, "tokens": 256, "hit": 0,
                                 "rids": "4", "kind": "continue"}),
    ("engine.prefill", 2515, 3, {"rows": 1, "tokens": 100, "hit": 0,
                                 "rids": "4", "kind": "draft"}),
    ("engine.plan", 2520, 70, {"cow": 0}),
    ("engine.decode", 2590, 5, {"steps": 4, "rows": 2, "rids": "1 3"}),
    ("engine.sync", 2600, 800, {"what": "decode"}),
]
MODULES = [("jit_paged_prefill(11)", 1040, 160, None),
           ("jit_sample_tokens(12)", 1200, 10, None),
           ("jit_decode_chunk(13)", 1360, 540, None),
           ("jit_decode_chunk(13)", 2600, 800, None)]
OPS = [("%fusion.1 = bf16[4,512,4096]{2,1,0} fusion(%p)", 1040, 160, None),
       ("%argmax.2 = s32[4]{0} reduce(%l)", 1200, 10, None),
       ("%axllm_matmul_pallas.3 = f32[4,12800]{1,0} custom-call(%x)", 1360,
        540, None),
       ("%copy.4 = bf16[4]{0} copy(%y)", 2600, 800, None)]


def write(path, engine=True, modules=True):
    host = BENCH_SPANS + (ENGINE_SPANS if engine else [])
    dev = {"XLA Ops": OPS}
    if modules:
        dev["XLA Modules"] = MODULES
    xspace.write(path, [("/host:CPU", {"python": host}),
                        ("/device:TPU:0", dev)])
    return path


def test_old_reduction_is_blind_to_engine_spans_and_modules(tmp_path):
    full = trace.reduce(write(tmp_path / "a.xplane.pb"))
    bare = trace.reduce(write(tmp_path / "b.xplane.pb", False, False))
    assert full == bare
    assert full["window_s"] == pytest.approx(2500e-9)
    assert full["busy_s"] == pytest.approx((170 + 540 + 800) * 1e-9)


def test_programs_spans_and_gaps(tmp_path):
    red = spans.reduce(write(tmp_path / "a.xplane.pb"))
    assert red["programs_s"] == pytest.approx(
        {"paged_prefill": 160e-9, "sample_tokens": 10e-9,
         "decode_chunk": 1340e-9})
    assert len(spans.of(red, "engine.step")) == 2
    # each gap named by the innermost span the host was in
    assert red["idle_gaps"] == [["bench.wait", pytest.approx(700e-9)],
                                ["engine.prefill_sync",
                                 pytest.approx(150e-9)],
                                ["engine.step", pytest.approx(100e-9)],
                                ["engine.admit", pytest.approx(40e-9)]]
    assert spans.host_steps_ms(red) == pytest.approx([260e-6, 180e-6])


def test_readers_against_hand_computed_values(tmp_path, monkeypatch):
    write(tmp_path / "a.xplane.pb")
    monkeypatch.setattr(spans, "TRACE_DIR", tmp_path)

    def read(name):
        return run.load_reader(name)({})

    assert read("decode_step_ms.itl") == pytest.approx(1340e-9 / 12 * 1e3)
    # the draft prefill's tokens are not the target's
    assert read("prefill_us_per_token.itl") == pytest.approx(
        160e-9 / (512 + 256) * 1e6)
    assert read("host_step_ms.itl") == pytest.approx(
        180e-6 + 0.9 * (260e-6 - 180e-6))


def test_readers_find_nothing_without_engine_spans(tmp_path, monkeypatch):
    # a program that opens no spans, with unnamed programs
    bare = [(n.replace("decode_chunk", "fn"), s, d, st)
            for n, s, d, st in MODULES]
    xspace.write(tmp_path / "a.xplane.pb",
                 [("/host:CPU", {"python": BENCH_SPANS}),
                  ("/device:TPU:0", {"XLA Ops": OPS, "XLA Modules": bare})])
    monkeypatch.setattr(spans, "TRACE_DIR", tmp_path)
    for name in ("decode_step_ms.itl", "prefill_us_per_token.itl",
                 "host_step_ms.itl"):
        assert run.load_reader(name)({}) is None
    monkeypatch.setattr(spans, "TRACE_DIR", tmp_path / "none")
    assert run.load_reader("decode_step_ms.itl")({}) is None


@pytest.mark.skipif(not PB.exists(), reason="no recorded trace")
def test_recorded_trace_names_programs_and_engine_gaps():
    red = spans.reduce(PB)
    assert red["programs_s"] and "fn" not in red["programs_s"]
    assert {"decode_chunk", "paged_prefill"} <= set(red["programs_s"])
    assert spans.of(red, "engine.decode") and spans.host_steps_ms(red)
    # every idle gap inside a harness step falls in an engine span
    assert all(name != "bench.step" for name, _ in red["idle_gaps"])
