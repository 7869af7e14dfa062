"""Fused projections: qconcat exactness (fp/int8/packed-int4, every
granularity), the fused-vs-unfused kernel path (ref + Pallas interpret),
and model-level fuse_params equivalence for all four families."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs.base import ModelConfig
from repro.core.axllm_linear import concat_weights, deploy_quantize
from repro.core.quantization import (QuantConfig, dequantize, qconcat,
                                     quantize)
from repro.kernels import ops
from repro.models.model import get_model, make_batch


def _rand(rng, shape, dtype=jnp.float32):
    return jnp.asarray(rng.standard_normal(shape), dtype)


# ---------------------------------------------------------------------------
# qconcat
# ---------------------------------------------------------------------------

QCFGS = [
    QuantConfig(8, "affine", "per_channel"),
    QuantConfig(8, "affine", "per_tensor"),
    QuantConfig(8, "affine", "per_group", group_size=64),
    QuantConfig(8, "codebook", "per_channel"),
    QuantConfig(4, "affine", "per_channel", pack=True),
    QuantConfig(4, "codebook", "per_channel", pack=True),
    QuantConfig(4, "affine", "per_channel", pack=False),
]


@pytest.mark.parametrize("qcfg", QCFGS,
                         ids=lambda c: f"{c.bits}b-{c.mode}-{c.granularity}"
                         f"{'-packed' if c.pack and c.bits == 4 else ''}")
def test_qconcat_dequant_exact(qcfg):
    """dequantize(qconcat(a, b, c)) == concat(dequantize each) exactly:
    scales travel with their columns, no requantization happens."""
    rng = np.random.default_rng(0)
    k = 128
    parts = [quantize(_rand(rng, (k, n)), qcfg) for n in (64, 32, 32)]
    fused = qconcat(parts)
    assert fused.shape == (k, 128)
    want = jnp.concatenate([dequantize(p) for p in parts], axis=-1)
    np.testing.assert_array_equal(np.asarray(dequantize(fused)),
                                  np.asarray(want))


def test_qconcat_per_tensor_becomes_per_channel():
    rng = np.random.default_rng(1)
    qcfg = QuantConfig(8, "affine", "per_tensor")
    a = quantize(_rand(rng, (64, 32)), qcfg)
    b = quantize(_rand(rng, (64, 16)) * 5.0, qcfg)   # different scale
    fused = qconcat([a, b])
    assert fused.granularity == "per_channel"
    want = jnp.concatenate([dequantize(a), dequantize(b)], axis=-1)
    np.testing.assert_array_equal(np.asarray(dequantize(fused)),
                                  np.asarray(want))


def test_qconcat_stacked_leading_dims():
    """Stacked-layer weights ([L, K, N], the scan layout) concat exactly."""
    rng = np.random.default_rng(2)
    qcfg = QuantConfig(8, "affine", "per_channel")
    a = quantize(_rand(rng, (3, 64, 32)), qcfg)
    b = quantize(_rand(rng, (3, 64, 16)), qcfg)
    fused = qconcat([a, b])
    assert fused.shape == (3, 64, 48)
    want = jnp.concatenate([dequantize(a), dequantize(b)], axis=-1)
    np.testing.assert_array_equal(np.asarray(dequantize(fused)),
                                  np.asarray(want))


def test_qconcat_rejects_mismatches():
    rng = np.random.default_rng(3)
    a8 = quantize(_rand(rng, (64, 32)),
                  QuantConfig(8, "affine", "per_channel"))
    a4 = quantize(_rand(rng, (64, 32)),
                  QuantConfig(4, "affine", "per_channel"))
    ag = quantize(_rand(rng, (64, 32)),
                  QuantConfig(8, "affine", "per_group", group_size=32))
    ak = quantize(_rand(rng, (128, 32)),
                  QuantConfig(8, "affine", "per_channel"))
    with pytest.raises(ValueError, match="mismatch"):
        qconcat([a8, a4])
    with pytest.raises(ValueError, match="per_group"):
        qconcat([a8, ag])
    with pytest.raises(ValueError, match="K/leading"):
        qconcat([a8, ak])
    with pytest.raises(TypeError, match="quantize first"):
        concat_weights([a8, _rand(rng, (64, 32))])


def test_concat_weights_mixed_error_branch():
    """concat_weights on a QTensor/dense mix raises TypeError in either
    order (fuse after deploy_quantize, never across the boundary); the
    all-dense path still concatenates plain arrays."""
    rng = np.random.default_rng(4)
    dense_a, dense_b = _rand(rng, (64, 32)), _rand(rng, (64, 16))
    qt = quantize(dense_a, QuantConfig(8, "affine", "per_channel"))
    for mix in ([qt, dense_b], [dense_b, qt], [dense_a, qt, dense_b]):
        with pytest.raises(TypeError, match="mix of QTensor and dense"):
            concat_weights(mix)
    fused = concat_weights([dense_a, dense_b])
    assert fused.shape == (64, 48)
    np.testing.assert_array_equal(
        np.asarray(fused), np.asarray(jnp.concatenate([dense_a, dense_b],
                                                      axis=-1)))


# ---------------------------------------------------------------------------
# Fused matmul: one [K, N1+N2+N3] launch == three separate launches
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("impl", ["ref", "pallas_interpret"])
@pytest.mark.parametrize("qcfg", [
    QuantConfig(8, "affine", "per_channel"),
    QuantConfig(4, "affine", "per_channel", pack=True),
], ids=["int8", "int4-packed"])
def test_fused_matmul_matches_separate(impl, qcfg):
    rng = np.random.default_rng(4)
    k = 256
    x = _rand(rng, (8, k))
    parts = [quantize(_rand(rng, (k, n)), qcfg) for n in (128, 64, 64)]
    fused = qconcat(parts)
    ys = [ops.axllm_matmul(x, p, impl=impl) for p in parts]
    y_fused = ops.axllm_matmul(x, fused, impl=impl)
    np.testing.assert_allclose(np.asarray(y_fused),
                               np.asarray(jnp.concatenate(ys, -1)),
                               rtol=2e-5, atol=2e-4)


def test_fused_dense_matmul_matches_separate():
    rng = np.random.default_rng(5)
    x = _rand(rng, (8, 64))
    ws = [_rand(rng, (64, n)) for n in (32, 16, 16)]
    y_fused = jnp.dot(x, concat_weights(ws))
    want = jnp.concatenate([jnp.dot(x, w) for w in ws], -1)
    # one dot against three may sum in another order: float32 reassociation
    np.testing.assert_allclose(np.asarray(y_fused), np.asarray(want),
                               rtol=1e-5, atol=1e-5)


# ---------------------------------------------------------------------------
# Model-level: fuse_params preserves outputs per family
# ---------------------------------------------------------------------------

from tests.test_decode_steps import FAMILIES  # noqa: E402  (shared configs)


@pytest.mark.parametrize("family", sorted(FAMILIES))
@pytest.mark.parametrize("quantized", [False, True],
                         ids=["fp", "axllm-int8"])
def test_fuse_params_forward_equivalence(family, quantized):
    cfg = FAMILIES[family]
    api = get_model(cfg)
    params = api.init(jax.random.PRNGKey(0))
    if quantized:
        params = deploy_quantize(params, QuantConfig(
            bits=8, mode="affine", granularity="per_channel"))
    fused = api.fuse_params(params)
    batch = make_batch(cfg, 0, 2, 8)
    y0 = api.forward(params, batch)
    y1 = api.forward(fused, batch)
    np.testing.assert_allclose(np.asarray(y1, np.float32),
                               np.asarray(y0, np.float32),
                               rtol=2e-4, atol=2e-4)


def test_fuse_params_moe_shared_experts():
    """MoE: attention + shared-expert MLP fuse; routed experts keep their
    einsum layout untouched."""
    cfg = ModelConfig(name="m", family="moe", n_layers=2, d_model=64,
                      n_heads=4, n_kv_heads=2, d_ff=128, vocab_size=256,
                      head_dim=16, vocab_pad_multiple=64, n_experts=8,
                      top_k=2, n_shared_experts=1, expert_pad_to=8,
                      capacity_factor=8.0, dtype="float32", remat=False)
    api = get_model(cfg)
    params = api.init(jax.random.PRNGKey(0))
    fused = api.fuse_params(params)
    ffn = fused["layers"]["ffn"]
    assert "gate_up" in ffn["shared"] and "expert_gate" in ffn
    batch = make_batch(cfg, 0, 2, 8)
    np.testing.assert_allclose(np.asarray(api.forward(fused, batch)),
                               np.asarray(api.forward(params, batch)),
                               rtol=2e-4, atol=2e-4)


def test_fuse_params_qkv_bias_and_qk_norm():
    """qwen2-style qkv_bias and chameleon-style qk_norm ride through the
    fused projection."""
    import dataclasses
    cfg = dataclasses.replace(FAMILIES["dense"], qkv_bias=True,
                              qk_norm=True)
    api = get_model(cfg)
    params = api.init(jax.random.PRNGKey(1))
    # give the biases non-zero values so the test has teeth
    params = jax.tree_util.tree_map(
        lambda a: a + 0.1 if a.ndim == 1 else a, params)
    fused = api.fuse_params(params)
    attn = jax.tree_util.tree_map(lambda a: a[0], fused["layers"]["attn"])
    assert "wqkv" in attn and "wqkv_bias" in attn and "wq" not in attn
    batch = make_batch(cfg, 0, 2, 8)
    np.testing.assert_allclose(np.asarray(api.forward(fused, batch)),
                               np.asarray(api.forward(params, batch)),
                               rtol=2e-4, atol=2e-4)


def test_fused_engine_decode_matches_unfused():
    """End-to-end: a fused+quantized+chunked engine serves the same tokens
    as the unfused per-token engine."""
    from repro.serve.engine import ServeEngine
    cfg = FAMILIES["dense"]
    params = get_model(cfg).init(jax.random.PRNGKey(0))
    prompts = [np.arange(8), np.arange(12) + 3, np.arange(31) + 7]
    ref = ServeEngine(cfg, params, n_slots=2, max_len=64, quantize=True,
                      decode_chunk=1).generate(prompts, max_new=6)
    got = ServeEngine(cfg, params, n_slots=2, max_len=64, quantize=True,
                      decode_chunk=8, fuse_qkv=True).generate(
                          prompts, max_new=6)
    assert got == ref
