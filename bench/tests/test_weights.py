"""Weights drawn on the device: layer by layer inside one program equals
the reference's own per-layer draw, the codes are what the program's
quantizer makes of the same draw, and the packed tree means the same
numbers to the program as to the reference."""

import jax
import jax.numpy as jnp
import numpy as np

from bench import cell, reference, weights
from bench.tests.tiny import tiny


def test_stacked_draw_equals_per_layer_draw():
    _, conf, _ = tiny("glm4-9b-int8.sessions")
    key = weights.base_key(2 ** 35 + 3)
    w = jax.jit(lambda k: weights.draw_all(k, conf))(key)
    m = reference.frozen(conf)
    for i in range(conf["num_hidden_layers"]):
        one = jax.jit(lambda k: weights.draw_layer(k, m))(
            weights.layer_key(key, i))
        for name in weights.MATRICES:
            np.testing.assert_array_equal(w["layers"][name][0][i],
                                          one[name][0])
            np.testing.assert_array_equal(w["layers"][name][1][i],
                                          one[name][1])
        np.testing.assert_array_equal(w["layers"]["wk_bias"][i],
                                      one["wk_bias"])


def test_codes_are_the_programs_int8_quantization():
    from repro.core.quantization import QuantConfig, quantize
    key = jax.random.PRNGKey(7)
    codes, absmax = weights.codes_of(key, 256, 384, 8)
    w = jax.random.normal(key, (256, 384), jnp.float32) * np.float32(
        1 / 16)
    qt = quantize(w, QuantConfig(bits=8, mode="affine",
                                 granularity="per_channel"))
    np.testing.assert_array_equal(np.asarray(qt.codes), np.asarray(codes))
    np.testing.assert_allclose(np.asarray(qt.scale), np.asarray(absmax))


def test_packed_tree_dequantizes_to_the_reference_weights():
    from repro.core.quantization import dequantize
    _, conf, _ = tiny("granite-3-8b-int8.chat")
    key = weights.base_key(5)
    w = weights.draw_all(key, conf)
    p = cell.pack_params(w, conf)
    ref = reference.layer_weights(key, 1, reference.frozen(conf), 8)
    for blk, names in (("attn", ("wq", "wk", "wv", "wo")),
                       ("ffn", ("gate", "up", "down"))):
        for n in names:
            got = dequantize(p["layers"][blk][n])[1]
            np.testing.assert_allclose(np.asarray(got), np.asarray(ref[n]),
                                       rtol=1e-6, atol=1e-7)
    head = dequantize(p["embed"]["lm_head"])
    codes, absmax = weights.draw_head(key, conf)
    np.testing.assert_allclose(np.asarray(head),
                               np.asarray(weights.dequant(codes, absmax)),
                               rtol=1e-6, atol=1e-7)
