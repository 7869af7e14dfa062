"""Work counts and peaks: operations and bytes per token from the
configurations' sizes, checked against hand arithmetic."""

import types

import pytest

from bench import cell, harness, work


def test_granite_counts_by_hand():
    m = cell.load_config("granite-3-8b-int8")
    d, ff, L = 4096, 12800, 40
    per_layer = d * 4096 + 2 * d * 1024 + 4096 * d + 3 * d * ff
    assert per_layer == 199_229_440
    assert work.layer_params(m) == L * per_layer
    assert work.head_params(m) == d * 49408
    # 2 x weights per token, head over the real vocabulary
    assert work.weight_flops(m, 1, 1) == 2 * L * per_layer + 2 * d * 49155
    assert work.kv_bytes_per_token(m) == 8 * 128 * 2 * 2 * L == 163840
    assert work.attn_flops_per_key(m) == 4 * 128 * 32 * L
    f, b = work.decode_attention(m, [1000])
    assert f == 1000 * 4 * 128 * 32 * L
    assert b == 1000 * 163840 + 2 * 2 * 32 * 128 * L


def test_glm_counts_by_hand():
    m = cell.load_config("glm4-9b-int8")
    d, ff, L = 4096, 13696, 40
    per_layer = d * 4096 + 2 * d * 256 + 4096 * d + 3 * d * ff
    assert work.layer_params(m) == L * per_layer
    assert work.kv_bytes_per_token(m) == 2 * 128 * 2 * 2 * L == 40960
    assert work.head_params(m) == d * 151552
    # one decode step of one token: codes + scales once, activations
    once = L * per_layer + d * 151552 + work.scale_bytes(m)
    act = work.layer_act_bytes(m) + 2 * d + 4 * 151552
    assert work.weight_bytes(m, 1, 1, 1) == once + act


def test_prefill_attention_counts_each_position_once():
    m = cell.load_config("glm4-9b-int8")
    k = work.attn_flops_per_key(m)
    assert work.prefill_attention_flops(m, [(0, 3)]) == (1 + 2 + 3) * k
    assert work.prefill_attention_flops(m, [(2, 4)]) == (3 + 4) * k


def test_unknown_device_kind_raises(monkeypatch):
    import jax
    fake = types.SimpleNamespace(platform="tpu", device_kind="TPU v99")
    monkeypatch.setattr(jax, "devices", lambda: [fake])
    with pytest.raises(harness.NoDevice, match="no entry"):
        harness.check_device(1)


def test_no_tpu_raises():
    with pytest.raises(harness.NoDevice, match="no TPU"):
        harness.check_device(1)
