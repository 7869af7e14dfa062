"""Pallas kernel validation: interpret-mode sweeps vs the pure-jnp oracles
(shape x dtype x quant-mode grids per kernel)."""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core.quantization import QuantConfig, quantize
from repro.kernels import ops, ref


def _rand(rng, shape, dtype=jnp.float32):
    return jnp.asarray(rng.standard_normal(shape), dtype)


# ---------------------------------------------------------------------------
# axllm_matmul
# ---------------------------------------------------------------------------

MATMUL_SHAPES = [(8, 512, 256), (100, 512, 256), (128, 1024, 512),
                 (256, 512, 1024), (1, 512, 256)]
QUANT_CONFIGS = [
    QuantConfig(8, "affine", "per_channel"),
    QuantConfig(8, "affine", "per_group", group_size=128),
    QuantConfig(8, "affine", "per_tensor"),
    QuantConfig(8, "codebook", "per_channel"),
    QuantConfig(4, "codebook", "per_channel", pack=True),
    QuantConfig(4, "affine", "per_channel", pack=True),
    QuantConfig(4, "affine", "per_channel", pack=False),
]


@pytest.mark.parametrize("shape", MATMUL_SHAPES)
def test_axllm_matmul_shapes(shape):
    m, k, n = shape
    rng = np.random.default_rng(0)
    x = _rand(rng, (m, k))
    qt = quantize(_rand(rng, (k, n)), QUANT_CONFIGS[0])
    y_ref = ops.axllm_matmul(x, qt, impl="ref")
    y_pal = ops.axllm_matmul(x, qt, impl="pallas_interpret")
    np.testing.assert_allclose(np.asarray(y_pal), np.asarray(y_ref),
                               rtol=2e-5, atol=2e-4)


@pytest.mark.parametrize("qcfg", QUANT_CONFIGS,
                         ids=lambda c: f"{c.bits}b-{c.mode}-{c.granularity}"
                         f"{'-packed' if c.pack and c.bits == 4 else ''}")
def test_axllm_matmul_quant_modes(qcfg):
    rng = np.random.default_rng(1)
    x = _rand(rng, (64, 512))
    qt = quantize(_rand(rng, (512, 256)), qcfg)
    y_ref = ops.axllm_matmul(x, qt, impl="ref")
    y_pal = ops.axllm_matmul(x, qt, impl="pallas_interpret")
    np.testing.assert_allclose(np.asarray(y_pal), np.asarray(y_ref),
                               rtol=2e-5, atol=2e-4)


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_axllm_matmul_dtypes(dtype):
    rng = np.random.default_rng(2)
    x = _rand(rng, (32, 512), dtype)
    qt = quantize(_rand(rng, (512, 256)), QUANT_CONFIGS[0])
    y_ref = ops.axllm_matmul(x, qt, impl="ref")
    y_pal = ops.axllm_matmul(x, qt, impl="pallas_interpret")
    assert y_pal.dtype == dtype
    np.testing.assert_allclose(np.asarray(y_pal, np.float32),
                               np.asarray(y_ref, np.float32),
                               rtol=2e-2, atol=2e-1)


def test_axllm_matmul_leading_batch_dims():
    rng = np.random.default_rng(3)
    x = _rand(rng, (2, 16, 512))
    qt = quantize(_rand(rng, (512, 256)), QUANT_CONFIGS[0])
    y = ops.axllm_matmul(x, qt, impl="pallas_interpret")
    assert y.shape == (2, 16, 256)


def test_lora_matmul_matches_ref():
    rng = np.random.default_rng(4)
    x = _rand(rng, (16, 512))
    qt = quantize(_rand(rng, (512, 256)), QUANT_CONFIGS[0])
    a = _rand(rng, (512, 8))
    b = _rand(rng, (8, 256))
    y1 = ops.lora_matmul(x, qt, a, b, 2.0, impl="ref")
    y2 = ops.lora_matmul(x, qt, a, b, 2.0, impl="pallas_interpret")
    np.testing.assert_allclose(np.asarray(y2), np.asarray(y1),
                               rtol=2e-5, atol=2e-4)


# the four code layouts of the serving path, read in place from a stack of
# layers: the same tiles in the same order, so the same bits
STACKED_CONFIGS = [
    QuantConfig(8, "affine", "per_channel"),
    QuantConfig(8, "affine", "per_group", group_size=128),
    QuantConfig(4, "affine", "per_channel", pack=True),
    QuantConfig(4, "codebook", "per_channel", pack=True),
]


@pytest.mark.parametrize("layer", [0, 2], ids=["first", "last"])
@pytest.mark.parametrize("qcfg", STACKED_CONFIGS,
                         ids=lambda c: f"{c.bits}b-{c.mode}-{c.granularity}")
def test_axllm_matmul_reads_layer_in_place(qcfg, layer):
    from repro.core.quantization import layer_slice, resolve_codebook
    from repro.kernels.axllm_matmul import axllm_matmul_pallas
    rng = np.random.default_rng(5)
    m, k, n = 8, 512, 256
    x = _rand(rng, (m, k))
    stack = quantize(_rand(rng, (3, k, n)), qcfg)          # [L, K, N] codes
    one = layer_slice(dataclasses.replace(
        stack, scale=stack.scale[layer], layer=layer))     # codes[layer]
    scale = ops._kernel_scale(one)
    kw = dict(bits=qcfg.bits, packed=stack.packed,
              group_size=qcfg.group_size,
              blocks=ops.pick_blocks(m, k, n, qcfg.group_size,
                                     qcfg.granularity == "per_group")[:3],
              interpret=True)
    cb = resolve_codebook(stack)
    want = axllm_matmul_pallas(x, one.codes, scale, cb, **kw)
    got = axllm_matmul_pallas(x, stack.codes, scale, cb,
                              jnp.asarray(layer, jnp.int32), **kw)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


# ---------------------------------------------------------------------------
# Decode-shape block table (pad decision lives in the table, not the call)
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("m", [8, 16, 24, 32, 40, 48, 56, 64, 72, 96, 120])
def test_pick_blocks_no_pad_fast_path(m):
    """Every multiple of 8 in the decode range dispatches without
    re-padding M (the old table rounded up to the next power of two)."""
    bm, bk, bn, pad_m = ops.pick_blocks(m, 512, 256)
    assert pad_m == 0 and m % bm == 0


def test_pick_blocks_widens_bn_for_skinny_m():
    bm, _, bn, _ = ops.pick_blocks(8, 1024, 1024)
    assert bm == 8 and bn == 512          # decode shape: wide N tiles
    bm, _, bn, _ = ops.pick_blocks(256, 1024, 1024)
    assert bm == 256 and bn == 256        # prefill shape: one row block


def test_pick_blocks_divisor_safe():
    """Shapes the old min(512, k) rule would crash on (k % bk != 0): every
    block divides its dim where the kernel takes no ragged block (the
    reuse kernel, packed codes); the weight kernel's blocks are lane-legal
    (a multiple of 128 or the whole dim), its ragged tails masked."""
    for m, k, n in [(8, 384, 320), (16, 768, 640), (2, 96, 48)]:
        bm, bk, bn, pad_m = ops.pick_blocks(m, k, n, ragged=False)
        assert k % bk == 0 and n % bn == 0 and (m + pad_m) % bm == 0
        bm, bk, bn, pad_m = ops.pick_blocks(m, k, n)
        assert (m + pad_m) % bm == 0
        for dim, b in ((k, bk), (n, bn)):
            assert b == dim or b % 128 == 0


# Granite-3.0-8B (d4096, 32/8 heads, d_ff 12800) keeps the blocks it was
# measured with; GLM-4-9B's d_ff 13696 = 107·128 takes the same 512-lane
# decode tiles with a ragged last block, where it fell to 128 lanes
@pytest.mark.parametrize("shape,blocks", [
    ((512, 4096, 12800), (512, 512, 256, 0)),
    ((8, 4096, 12800), (8, 512, 512, 0)),
    ((8, 12800, 4096), (8, 512, 512, 0)),
    ((8, 4096, 1024), (8, 512, 512, 0)),
    ((128, 4096, 4096), (128, 512, 256, 0)),
    ((8, 4096, 49408), (8, 512, 256, 0)),
    ((8, 4096, 13696), (8, 512, 512, 0)),
    ((8, 13696, 4096), (8, 512, 512, 0)),
    ((128, 4096, 13696), (128, 512, 256, 0)),
    ((128, 13696, 4096), (128, 512, 256, 0)),
], ids=["granite-prefill-ffn", "granite-gate", "granite-down", "granite-kv",
        "granite-prefill-wq", "granite-head", "glm-gate", "glm-down",
        "glm-prefill-gate", "glm-prefill-down"])
def test_pick_blocks_model_shapes(shape, blocks):
    assert ops.pick_blocks(*shape) == blocks


@pytest.mark.parametrize("m", [3, 8, 128])
@pytest.mark.parametrize("k,n", [(384, 640), (640, 384), (640, 640)])
@pytest.mark.parametrize("stacked", [False, True], ids=["2d", "stacked"])
def test_axllm_matmul_ragged_blocks(m, k, n, stacked):
    """Dims that are odd multiples of 128 (GLM-4's d_ff 13696 = 107·128,
    scaled down) run on 512-lane tiles with a ragged last block on K and
    N: x's columns past K are zeroed and the columns past N dropped, so
    the result matches the jnp oracle, for 2-D and stacked codes."""
    rng = np.random.default_rng(9)
    x = _rand(rng, (m, k))
    w = _rand(rng, (3, k, n) if stacked else (k, n))
    qt = quantize(w, QUANT_CONFIGS[0])
    if stacked:
        qt = dataclasses.replace(qt, scale=qt.scale[2], layer=2)
    bm, bk, bn, _ = ops.pick_blocks(m, k, n)
    assert k % bk or n % bn                 # a ragged last block
    y_ref = ops.axllm_matmul(x, qt, impl="ref")
    y_pal = ops.axllm_matmul(x, qt, impl="pallas_interpret")
    assert np.isfinite(np.asarray(y_pal)).all()
    np.testing.assert_allclose(np.asarray(y_pal), np.asarray(y_ref),
                               rtol=2e-5, atol=2e-4)


@pytest.mark.parametrize("row_parallel", [False, True],
                         ids=["column", "row"])
def test_axllm_matmul_ragged_blocks_per_shard(row_parallel):
    """Under a (1, 2) mesh each shard's kernel gets a 640-wide local dim
    (a ragged 512-lane tail) and reads its share of a stacked weight."""
    from repro.dist import sharding as shd
    from repro.launch.mesh import make_mesh
    rng = np.random.default_rng(10)
    k, n = (1280, 384) if row_parallel else (384, 1280)
    x = _rand(rng, (8, k))
    qt = quantize(_rand(rng, (2, k, n)), QUANT_CONFIGS[0])
    qt = dataclasses.replace(qt, scale=qt.scale[1], layer=1)
    y_ref = ops.axllm_matmul(x, qt, impl="ref")
    mesh = make_mesh((1, 2), ("data", "model"),
                     devices=jax.devices()[:2])
    with shd.activate(mesh, shd.SERVE_RULES):
        y_pal = jax.jit(lambda x, qt: ops.axllm_matmul(
            x, qt, impl="pallas_interpret", row_parallel=row_parallel))(x, qt)
    np.testing.assert_allclose(np.asarray(y_pal), np.asarray(y_ref),
                               rtol=2e-5, atol=2e-4)


def test_pick_blocks_per_group_alignment():
    bm, bk, bn, _ = ops.pick_blocks(8, 640, 256, group_size=128,
                                    per_group=True)
    assert bk % 128 == 0 and 640 % bk == 0


@pytest.mark.parametrize("k,gs", [(384, 128), (1536, 512), (96, 32)])
def test_pick_blocks_per_group_k_not_multiple_of_gs_bk(k, gs):
    """k a non-power-of-two multiple of the group size (e.g. 3 groups):
    the group-aligned bk must still divide k exactly — the naive
    (bk // gs) * gs of a power-of-two bk does not."""
    bm, bk, bn, pad_m = ops.pick_blocks(16, k, 256, group_size=gs,
                                        per_group=True)
    assert bk % gs == 0 and k % bk == 0
    assert pad_m == 0


def test_pick_blocks_skinny_m8_with_per_group():
    """The skinny-decode fast path and per-group alignment compose: m=8
    keeps the no-pad bm=8 row and the widened bn, while bk snaps to the
    group grid."""
    bm, bk, bn, pad_m = ops.pick_blocks(8, 512, 1024, group_size=128,
                                        per_group=True)
    assert bm == 8 and pad_m == 0
    assert bn == 512                        # skinny launch widens N tiles
    assert bk % 128 == 0 and 512 % bk == 0
    # odd skinny m with per_group still pads up to the bm=8 row
    bm, bk, bn, pad_m = ops.pick_blocks(9, 512, 1024, group_size=128,
                                        per_group=True)
    assert bm == 8 and pad_m == 7 and bk % 128 == 0


@pytest.mark.parametrize("m", [8, 24, 48])
def test_axllm_matmul_no_pad_shapes_interpret(m):
    """The no-pad decode shapes produce correct results end to end."""
    rng = np.random.default_rng(7)
    x = _rand(rng, (m, 512))
    qt = quantize(_rand(rng, (512, 256)), QUANT_CONFIGS[0])
    y_ref = ops.axllm_matmul(x, qt, impl="ref")
    y_pal = ops.axllm_matmul(x, qt, impl="pallas_interpret")
    np.testing.assert_allclose(np.asarray(y_pal), np.asarray(y_ref),
                               rtol=2e-5, atol=2e-4)


def test_axllm_matmul_wide_bn_skinny_m_interpret():
    """Skinny m widens bn to 512 — exercise that tile shape end to end,
    not just the table entry."""
    rng = np.random.default_rng(8)
    m, k, n = 8, 256, 512
    assert ops.pick_blocks(m, k, n)[:3] == (8, 256, 512)
    x = _rand(rng, (m, k))
    qt = quantize(_rand(rng, (k, n)), QUANT_CONFIGS[0])
    y_ref = ops.axllm_matmul(x, qt, impl="ref")
    y_pal = ops.axllm_matmul(x, qt, impl="pallas_interpret")
    np.testing.assert_allclose(np.asarray(y_pal), np.asarray(y_ref),
                               rtol=2e-5, atol=2e-4)


# ---------------------------------------------------------------------------
# flash attention
# ---------------------------------------------------------------------------

ATTN_CASES = [
    # (B, Sq, Sk, H, Hk, d, causal)
    (2, 256, 256, 4, 4, 64, True),
    (2, 256, 512, 8, 2, 64, True),      # GQA + longer KV
    (1, 512, 512, 4, 1, 128, True),     # MQA
    (2, 256, 256, 4, 4, 64, False),
]


@pytest.mark.parametrize("case", ATTN_CASES)
def test_flash_attention_vs_oracle(case):
    b, sq, sk, h, hk, d, causal = case
    rng = np.random.default_rng(5)
    q = _rand(rng, (b, sq, h, d))
    k = _rand(rng, (b, sk, hk, d))
    v = _rand(rng, (b, sk, hk, d))
    o_ref = ref.attention_ref(q, k, v, causal=causal)
    o_pal = ops.flash_attention(q, k, v, causal=causal,
                                impl="pallas_interpret")
    np.testing.assert_allclose(np.asarray(o_pal), np.asarray(o_ref),
                               rtol=2e-5, atol=2e-5)


def test_chunked_oracle_matches_dense():
    rng = np.random.default_rng(6)
    q = _rand(rng, (2, 200, 4, 32))
    k = _rand(rng, (2, 300, 2, 32))
    v = _rand(rng, (2, 300, 2, 32))
    for causal in (True, False):
        o1 = ref.attention_ref(q, k, v, causal=causal)
        o2 = ref.chunked_attention_ref(q, k, v, causal=causal, chunk=128)
        np.testing.assert_allclose(np.asarray(o2), np.asarray(o1),
                                   rtol=2e-5, atol=2e-5)


# ---------------------------------------------------------------------------
# decode attention
# ---------------------------------------------------------------------------

def _kv_quant(x):
    s = jnp.maximum(jnp.max(jnp.abs(x), -1, keepdims=True), 1e-8) / 127.0
    return (jnp.clip(jnp.round(x / s), -127, 127).astype(jnp.int8),
            s.astype(jnp.float32))


@pytest.mark.parametrize("case", [(2, 1024, 8, 2, 64), (1, 2048, 4, 4, 128),
                                  (4, 512, 4, 1, 64)])
def test_decode_attention_vs_oracle(case):
    b, s, h, hk, d = case
    rng = np.random.default_rng(7)
    q = _rand(rng, (b, h, d))
    kc = _rand(rng, (b, s, hk, d))
    vc = _rand(rng, (b, s, hk, d))
    length = jnp.asarray(rng.integers(1, s + 1, size=b), jnp.int32)
    o_ref = ref.decode_attention_ref(q, kc, vc, length)
    o_pal = ops.decode_attention(q, kc, vc, length, impl="pallas_interpret")
    np.testing.assert_allclose(np.asarray(o_pal), np.asarray(o_ref),
                               rtol=2e-5, atol=2e-5)


def test_decode_attention_int8_kv():
    rng = np.random.default_rng(8)
    b, s, h, hk, d = 2, 1024, 8, 2, 64
    q = _rand(rng, (b, h, d))
    kc = _rand(rng, (b, s, hk, d))
    vc = _rand(rng, (b, s, hk, d))
    kq, ks = _kv_quant(kc)
    vq, vs = _kv_quant(vc)
    length = jnp.asarray([700, 1024], jnp.int32)
    o_ref = ref.decode_attention_ref(q, kq, vq, length, k_scale=ks,
                                     v_scale=vs)
    o_pal = ops.decode_attention(q, kq, vq, length, k_scale=ks, v_scale=vs,
                                 impl="pallas_interpret")
    np.testing.assert_allclose(np.asarray(o_pal), np.asarray(o_ref),
                               rtol=2e-5, atol=2e-5)
    # int8-KV error vs exact stays small
    o_exact = ref.decode_attention_ref(q, kc, vc, length)
    rel = np.abs(np.asarray(o_ref) - np.asarray(o_exact)).max() \
        / np.abs(np.asarray(o_exact)).max()
    assert rel < 0.05


def test_decode_attention_int8_kv_length_zero_rows():
    """length == 0 rows (empty slots riding through a batched decode) must
    come back as exact zeros on both paths — a fully masked softmax must
    not renormalize into a uniform average of garbage."""
    rng = np.random.default_rng(9)
    b, s, h, hk, d = 3, 512, 8, 2, 64
    q = _rand(rng, (b, h, d))
    kq, ks = _kv_quant(_rand(rng, (b, s, hk, d)))
    vq, vs = _kv_quant(_rand(rng, (b, s, hk, d)))
    length = jnp.asarray([0, 130, 0], jnp.int32)
    o_ref = ref.decode_attention_ref(q, kq, vq, length, k_scale=ks,
                                     v_scale=vs)
    o_pal = ops.decode_attention(q, kq, vq, length, k_scale=ks, v_scale=vs,
                                 impl="pallas_interpret")
    np.testing.assert_allclose(np.asarray(o_pal), np.asarray(o_ref),
                               rtol=2e-5, atol=2e-5)
    assert np.allclose(np.asarray(o_ref)[[0, 2]], 0.0)
    assert np.allclose(np.asarray(o_pal)[[0, 2]], 0.0)


def test_decode_attention_non_divisible_cache_length():
    """S=768 with the default 512 block used to raise; the kernel now
    falls back to the largest power-of-two divisor block."""
    rng = np.random.default_rng(10)
    b, s, h, hk, d = 2, 768, 4, 2, 64
    q = _rand(rng, (b, h, d))
    kc = _rand(rng, (b, s, hk, d))
    vc = _rand(rng, (b, s, hk, d))
    length = jnp.asarray([700, 768], jnp.int32)
    o_ref = ref.decode_attention_ref(q, kc, vc, length)
    o_pal = ops.decode_attention(q, kc, vc, length, impl="pallas_interpret")
    np.testing.assert_allclose(np.asarray(o_pal), np.asarray(o_ref),
                               rtol=2e-5, atol=2e-5)


# ---------------------------------------------------------------------------
# quantize kernel
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("shape", [(512, 512), (1024, 256), (128, 1024)])
def test_quantize_kernel_vs_oracle(shape):
    rng = np.random.default_rng(9)
    w = _rand(rng, shape)
    c1, s1 = ops.quantize_channels(w, impl="ref")
    c2, s2 = ops.quantize_channels(w, impl="pallas_interpret")
    np.testing.assert_array_equal(np.asarray(c1), np.asarray(c2))
    np.testing.assert_allclose(np.asarray(s1), np.asarray(s2), rtol=1e-6)
