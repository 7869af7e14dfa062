"""Trace reduction: busy union, idle attribution to harness spans, and the
kernel-name table, on hand-made intervals and event texts, and on a small
trace recorded on a TPU v5e by bench/tests/record_trace.py wherever one
has been recorded into bench/testdata/.

    JAX_PLATFORMS=cpu PYTHONPATH=src:. python -m pytest -q bench/tests
"""

import json
from pathlib import Path

import pytest

from bench import trace

DATA = Path(__file__).resolve().parents[1] / "testdata"
PB = DATA / "tiny_chat.xplane.pb"


def test_union_merges_overlaps_and_touching_intervals():
    assert trace.union([(5, 7), (0, 2), (1, 3), (3, 4)]) == [[0, 4], [5, 7]]


def test_gaps_are_the_window_minus_the_busy_union():
    busy = [[2, 4], [6, 7]]
    assert trace.gaps_of(busy, 0, 10) == [(0, 2), (4, 6), (7, 10)]
    assert trace.gaps_of(busy, 3, 6) == [(4, 6)]


def test_idle_inside_engine_spans_only():
    gaps = [(0, 2), (4, 6), (7, 10)]
    engine = [[1, 5], [9, 12]]
    assert trace.overlap(gaps, engine) == 1 + 1 + 1


def test_span_at_picks_the_innermost_span():
    spans = [("bench.window", 0, 100), ("bench.step", 10, 20)]
    assert trace.span_at(spans, 15) == "bench.step"
    assert trace.span_at(spans, 50) == "bench.window"
    assert trace.span_at(spans, 150) == "outside"


def test_op_names_come_from_the_instruction_text():
    ev = ("%axllm_matmul_pallas.69 = f32[8,12800]{1,0} custom-call(bf16[8,"
          "4096]{1,0} %pad.70), custom_call_target=\"tpu_custom_call\"")
    assert trace.base_name(ev) == "axllm_matmul_pallas"
    assert trace.base_name("%copy.63 = bf16[4]{0} copy(%x)") == "copy"

def test_kernel_table_maps_the_weight_path_and_attention():
    table = trace.kernel_table()
    mm = ("%axllm_matmul_pallas.69 = f32[8,12800]{1,0} custom-call(bf16[8,"
          "4096]{1,0} %pad.70, s8[4096,12800]{1,0} %dynamic-slice)")
    codes = ("%dynamic-slice_bitcast_fusion.25 = s8[4096,12800]{1,0:T(8,128)"
             "(4,1)} fusion(s8[40,4096,12800]{2,1,0} %get-tuple-element.1557)")
    kv = ("%dynamic-slice_bitcast_fusion.21 = bf16[148,128,8,128]{3,2,1,0} "
          "fusion(bf16[40,148,128,8,128]{4,3,2,1,0} %get-tuple-element.1564)")
    att = ("%paged_decode_attention_pallas.8 = f32[4,32,1024]{2,1,0} "
           "custom-call(s32[4]{0} %broadcast_add_fusion.4)")
    assert table["weight_matmul"].search(mm)
    # the per-layer copy of int8 codes feeding the kernel is weight path
    assert table["weight_matmul"].search(codes)
    assert not table["weight_matmul"].search(kv)
    assert not table["weight_matmul"].search(att)
    assert table["decode_attention"].search(att)
    assert not table["decode_attention"].search(mm)


@pytest.mark.skipif(not PB.exists(), reason="no recorded trace")
def test_recorded_trace_reduces_as_the_run_reported():
    red = trace.reduce(PB)
    want = json.loads((DATA / "tiny_chat.json").read_text())
    assert red["window_s"] == pytest.approx(want["device"]["window_s"])
    assert red["busy_s"] == pytest.approx(want["device"]["busy_s"])
    assert 0 < red["busy_s"] <= red["window_s"]
    assert 0 <= red["engine_idle_s"] <= red["window_s"] - red["busy_s"]
    # every kernel of the served path is found by the table
    assert red["kernel_s"]["weight_matmul"] > 0
    assert red["kernel_s"]["decode_attention"] > 0
    assert red["kernel_s"]["weight_matmul"] <= red["busy_s"]
    # the longest gaps are attributed to what the harness was doing
    assert red["idle_gaps"] and all(
        name.startswith("bench.") or name == "outside"
        for name, _ in red["idle_gaps"])
    assert red["idle_gaps"] == sorted(red["idle_gaps"], key=lambda g: -g[1])
