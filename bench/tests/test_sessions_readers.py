"""The sessions cell's per-layer readers on a hand-made trace: what they
read from the engine's ``keys`` and ``prefix`` span stats, and that they
return None, and never raise, on a program whose spans lack them.

    JAX_PLATFORMS=cpu PYTHONPATH=src:. python -m pytest -q bench/tests
"""

import pytest

from bench import cell, run, spans, work
from bench.tests import xspace
from bench.tests.test_spans import BENCH_SPANS, MODULES, OPS

# one step of a session turn: its new tokens against a 6144-position
# cached prefix, then a decode chunk of two rows
ENGINE_SPANS = [
    ("engine.step", 1010, 980, {"active": 2}),
    ("engine.prefill", 1030, 20, {"rows": 1, "tokens": 128, "hit": 6144,
                                  "prefix": 6144, "rids": "3",
                                  "kind": "first"}),
    ("engine.decode", 1350, 20, {"steps": 8, "rows": 2, "keys": 98000,
                                 "rids": "1 3"}),
    ("engine.step", 2510, 980, {"active": 2}),
    ("engine.prefill", 2512, 3, {"rows": 1, "tokens": 64, "hit": 0,
                                 "prefix": 6272, "rids": "3",
                                 "kind": "continue"}),
    ("engine.prefill", 2515, 3, {"rows": 1, "tokens": 100, "hit": 0,
                                 "prefix": 0, "rids": "3", "kind": "draft"}),
    ("engine.decode", 2590, 5, {"steps": 4, "rows": 2, "keys": 49000,
                                "rids": "1 3"}),
]
CTX = {"trace": {"kernel_s": {"decode_attention": 0.5}},
       "conf": cell.load_config("glm4-9b-int8"),
       "peaks": {"bf16_flops": 197e12, "int8_ops": 393e12,
                 "hbm_bytes_per_s": 819e9}}


def _write(tmp_path, monkeypatch, engine_spans):
    xspace.write(tmp_path / "a.xplane.pb",
                 [("/host:CPU", {"python": BENCH_SPANS + engine_spans}),
                  ("/device:TPU:0", {"XLA Ops": OPS,
                                     "XLA Modules": MODULES})])
    monkeypatch.setattr(spans, "TRACE_DIR", tmp_path)


def test_readers_against_hand_computed_values(tmp_path, monkeypatch):
    _write(tmp_path, monkeypatch, ENGINE_SPANS)
    keys = 98000 + 49000
    nbytes = keys * work.kv_bytes_per_token(CTX["conf"])
    assert run.load_reader("decode_attention_roofline.sessions")(CTX) == \
        pytest.approx(100 * nbytes / 819e9 / 0.5)
    # the draft's prefill is not the target's
    assert run.load_reader("prefix_hit_share.sessions")({}) == \
        pytest.approx(100 * 6144 / (6144 + 128 + 64))
    for name in ("decode_step_ms", "prefill_us_per_token"):
        assert run.load_reader(f"{name}.sessions")({}) == \
            run.load_reader(f"{name}.itl")({})


def test_readers_find_nothing_without_keys(tmp_path, monkeypatch):
    # the parent program's spans: no keys, no prefix
    bare = [(n, s, d, st and {k: v for k, v in st.items()
                              if k not in ("keys", "prefix")})
            for n, s, d, st in ENGINE_SPANS]
    _write(tmp_path, monkeypatch, bare)
    assert run.load_reader("decode_attention_roofline.sessions")(CTX) is None
    assert run.load_reader("prefix_hit_share.sessions")({}) is not None
    monkeypatch.setattr(spans, "TRACE_DIR", tmp_path / "none")
    for name in ("decode_attention_roofline", "prefix_hit_share",
                 "decode_step_ms", "prefill_us_per_token"):
        assert run.load_reader(f"{name}.sessions")(CTX) is None


def test_weight_roofline_is_the_chat_cells_reader():
    from types import SimpleNamespace
    log = SimpleNamespace(prefill_pos=[(6144, 6272)], prefill_rows=1,
                          decode_ctx=list(range(6300, 6316)),
                          decode_steps=8, prefill_waves=1)
    ctx = dict(CTX, work=log, trace={"kernel_s": {"weight_matmul": 0.4}})
    want = run.load_reader("weight_matmul_roofline")(ctx)
    assert want > 0
    assert run.load_reader("weight_matmul_roofline.sessions")(ctx) == want
