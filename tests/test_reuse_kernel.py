"""Reuse (LUT) matmul validation: the paper's Result-Cache arithmetic
on device (kernels/reuse_matmul.py + kernels/ops.reuse_matmul).

Three contracts:

1. Bit-exactness. In the integer/dyadic regime (integer activations,
   scale = qmax * 2^-e) every product and partial sum is exactly
   representable in f32, so the reuse path — gather-from-LUT instead of
   multiply — must reproduce the exact int64 matmul BIT-FOR-BIT, in both
   the jnp oracle and the Pallas kernel (interpret mode). Codebook modes
   with an integer table get the same treatment; NF4 (irrational table
   values) is association-sensitive and compared at tolerance against
   the multiply path.

2. Measured reuse. The kernel counts the multiplies it cannot avoid
   (distinct alphabet cells per (k-row, bn-wide column segment)); that
   count must equal ``core.reuse.segment_unique_counts`` on the same
   codes with the same fold — the number the simulator and Fig. 8
   analytics predict. One number, three independent implementations.

3. Alphabet pinning (regression for the PR-1 double-fold bug class):
   ``core.reuse.rc_alphabet`` is the single source of the (levels,
   fold_sign) contract; these tests pin its values and its agreement
   with ``fold_codes`` so the simulator and kernel cannot drift apart —
   including the packed-int4 trap where raw code *bytes* look like
   valid uint8 cells.
"""

import hypothesis.strategies as st
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from hypothesis import given, settings

from repro.core import reuse as R
from repro.core.quantization import (QTensor, QuantConfig, nf4_codebook,
                                     pack_int4, quantize)
from repro.kernels import ops
from repro.launch.mesh import make_mesh

M, K, N = 64, 512, 256


def _qtensor(codes, scale, bits, mode, packed=False, granularity=None,
             group_size=128):
    c = pack_int4(jnp.asarray(codes)) if packed else jnp.asarray(codes)
    gran = granularity or ("per_group" if np.asarray(scale).shape[0] > 1
                           else "per_channel")
    return QTensor(codes=c, scale=jnp.asarray(scale), codebook=None,
                   bits=bits, mode=mode, granularity=gran,
                   group_size=group_size, packed=packed, shape=codes.shape)


def _int_x(seed, m=M):
    rng = np.random.default_rng(seed)
    return rng, jnp.asarray(rng.integers(-8, 9, size=(m, K)), jnp.float32)


REUSE_PATHS = ("reuse_ref", "reuse_interpret")


# ---------------------------------------------------------------------------
# 1. bit-exact golden tests (integer/dyadic regime)
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("impl", REUSE_PATHS)
def test_affine_int8_bit_exact(impl):
    rng, x = _int_x(0)
    codes = rng.integers(-127, 128, size=(K, N)).astype(np.int8)
    qt = _qtensor(codes, np.full((1, N), 127.0 * 2.0 ** -3, np.float32),
                  8, "affine")
    exact = ((np.asarray(x, np.int64) @ codes.astype(np.int64))
             * 2.0 ** -3).astype(np.float32)
    y, _ = ops.reuse_matmul(x, qt, impl=impl)
    np.testing.assert_array_equal(np.asarray(y), exact)


@pytest.mark.parametrize("impl", REUSE_PATHS)
@pytest.mark.parametrize("packed", [False, True])
def test_affine_int4_bit_exact(impl, packed):
    rng, x = _int_x(1)
    codes = rng.integers(-7, 8, size=(K, N)).astype(np.int8)
    qt = _qtensor(codes, np.full((1, N), 7.0 * 2.0 ** -2, np.float32),
                  4, "affine", packed=packed)
    exact = ((np.asarray(x, np.int64) @ codes.astype(np.int64))
             * 2.0 ** -2).astype(np.float32)
    y, _ = ops.reuse_matmul(x, qt, impl=impl)
    np.testing.assert_array_equal(np.asarray(y), exact)


@pytest.mark.parametrize("impl", REUSE_PATHS)
def test_affine_per_group_bit_exact(impl):
    rng, x = _int_x(2)
    codes = rng.integers(-127, 128, size=(K, N)).astype(np.int8)
    exps = rng.integers(-4, 1, size=(K // 128, N))
    scale = (127.0 * 2.0 ** exps).astype(np.float32)
    qt = _qtensor(codes, scale, 8, "affine", granularity="per_group")
    xi = np.asarray(x, np.int64)
    exact = np.zeros((M, N), np.float64)
    for g in range(K // 128):
        part = xi[:, g * 128:(g + 1) * 128] @ \
            codes[g * 128:(g + 1) * 128].astype(np.int64)
        exact += part * (2.0 ** exps[g])[None, :]
    y, _ = ops.reuse_matmul(x, qt, impl=impl)
    np.testing.assert_array_equal(np.asarray(y), exact.astype(np.float32))


@pytest.mark.parametrize("impl", REUSE_PATHS)
def test_codebook_int8_tracks_float_reference(impl):
    """The identity-8 table is normalized (code/127), so products are
    rounded and the reuse decomposition (per-level gather-sums, then
    scale) reorders the additions vs the multiply path's single dot —
    bitwise equality is not a well-defined contract here (unlike the
    dyadic affine regime). Compare against the float64 ground truth at
    f32 tolerance instead."""
    rng, x = _int_x(3)
    codes = rng.integers(-128, 128, size=(K, N)).astype(np.int8)
    qt = _qtensor(codes, np.full((1, N), 2.0 ** -4, np.float32),
                  8, "codebook")
    truth = (np.asarray(x, np.float64)
             @ (codes.astype(np.float64) / 127.0) * 2.0 ** -4)
    y, _ = ops.reuse_matmul(x, qt, impl=impl)
    np.testing.assert_allclose(np.asarray(y), truth, rtol=2e-5, atol=2e-4)


@pytest.mark.parametrize("impl", REUSE_PATHS)
def test_codebook_nf4_matches_multiply_path(impl):
    """NF4 table values are not integers, so (x*cb)*s vs x*(cb*s) may
    differ in the last ulp — compare against the multiply-path oracle at
    f32 tolerance instead of bitwise."""
    rng = np.random.default_rng(4)
    x = jnp.asarray(rng.standard_normal((M, K)), jnp.float32)
    qt = quantize(jnp.asarray(rng.standard_normal((K, N)), jnp.float32),
                  QuantConfig(4, "codebook", "per_channel"))
    y_mul = ops.axllm_matmul(x, qt, impl="ref")
    y_reu, _ = ops.reuse_matmul(x, qt, impl=impl)
    np.testing.assert_allclose(np.asarray(y_reu), np.asarray(y_mul),
                               rtol=2e-5, atol=2e-4)


@pytest.mark.parametrize("qcfg", [
    QuantConfig(8, "affine", "per_channel"),
    QuantConfig(8, "affine", "per_group", group_size=128),
    QuantConfig(8, "affine", "per_tensor"),
    QuantConfig(8, "codebook", "per_channel"),
    QuantConfig(4, "codebook", "per_channel", pack=True),
    QuantConfig(4, "affine", "per_channel", pack=True),
], ids=lambda c: f"{c.bits}b-{c.mode}-{c.granularity}")
def test_reuse_matches_multiply_all_quant_modes(qcfg):
    """Every deployable quant config: reuse oracle and interpret-mode
    kernel agree with the multiply path on real quantized weights."""
    rng = np.random.default_rng(5)
    x = jnp.asarray(rng.standard_normal((32, K)), jnp.float32)
    qt = quantize(jnp.asarray(rng.standard_normal((K, N)), jnp.float32),
                  qcfg)
    y_mul = np.asarray(ops.axllm_matmul(x, qt, impl="ref"))
    for impl in REUSE_PATHS:
        y, _ = ops.reuse_matmul(x, qt, impl=impl)
        np.testing.assert_allclose(np.asarray(y), y_mul,
                                   rtol=2e-5, atol=2e-4, err_msg=impl)


def test_reuse_skinny_decode_shapes():
    """m = 1 (single-token decode) pads to the block table's bm."""
    rng = np.random.default_rng(6)
    qt = quantize(jnp.asarray(rng.standard_normal((K, N)), jnp.float32),
                  QuantConfig(8, "affine", "per_channel"))
    for m in (1, 3, 8):
        x = jnp.asarray(rng.standard_normal((m, K)), jnp.float32)
        y_mul = np.asarray(ops.axllm_matmul(x, qt, impl="ref"))
        y, _ = ops.reuse_matmul(x, qt, impl="reuse_interpret")
        assert y.shape == (m, N)
        np.testing.assert_allclose(np.asarray(y), y_mul,
                                   rtol=2e-5, atol=2e-4)


def test_reuse_leading_batch_dims_and_dtype():
    rng = np.random.default_rng(7)
    x = jnp.asarray(rng.standard_normal((2, 4, K)), jnp.bfloat16)
    qt = quantize(jnp.asarray(rng.standard_normal((K, N)), jnp.float32),
                  QuantConfig(8, "affine", "per_channel"))
    y = ops.axllm_matmul(x, qt, impl="reuse_ref")
    assert y.shape == (2, 4, N) and y.dtype == jnp.bfloat16


# ---------------------------------------------------------------------------
# 2. measured multiply count == analytics prediction
# ---------------------------------------------------------------------------

@st.composite
def quant_codes(draw):
    bits = draw(st.sampled_from([4, 8]))
    mode = draw(st.sampled_from(["affine", "codebook"]))
    seed = draw(st.integers(0, 2 ** 31 - 1))
    rng = np.random.default_rng(seed)
    lo, hi = (-7, 8) if bits == 4 else (-127, 128)
    if mode == "codebook":
        lo, hi = (-8, 8) if bits == 4 else (-128, 128)
    codes = rng.integers(lo, hi, size=(K, N)).astype(np.int8)
    return bits, mode, codes


@given(quant_codes())
@settings(deadline=None, max_examples=12)
def test_kernel_mult_count_matches_segment_unique_counts(case):
    bits, mode, codes = case
    qt = _qtensor(codes, np.full((1, N), 1.0, np.float32), bits, mode)
    x = jnp.ones((4, K), jnp.float32)
    levels, fold = R.rc_alphabet(bits, mode)
    _, _, bn, _ = ops.pick_blocks(4, K, N)
    expect = int(R.segment_unique_counts(codes, bn, fold_sign=fold).sum())
    _, m_ref = ops.reuse_matmul(x, qt, impl="reuse_ref", with_stats=True)
    _, m_ker = ops.reuse_matmul(x, qt, impl="reuse_interpret",
                                with_stats=True)
    assert int(m_ref) == expect
    assert int(m_ker) == expect


def test_mult_count_packed_equals_unpacked():
    """Nibble packing is storage, not semantics: the kernel must count
    the same distinct cells either way."""
    rng = np.random.default_rng(8)
    codes = rng.integers(-7, 8, size=(K, N)).astype(np.int8)
    scale = np.full((1, N), 7.0, np.float32)
    x = jnp.ones((4, K), jnp.float32)
    counts = []
    for packed in (False, True):
        qt = _qtensor(codes, scale, 4, "affine", packed=packed)
        _, m = ops.reuse_matmul(x, qt, impl="reuse_interpret",
                                with_stats=True)
        counts.append(int(m))
    assert counts[0] == counts[1]


def test_with_stats_false_is_jit_safe():
    """The serving default must stay traceable: stats off -> no host
    callback, usable inside the jitted decode hot path."""
    rng = np.random.default_rng(9)
    qt = quantize(jnp.asarray(rng.standard_normal((K, N)), jnp.float32),
                  QuantConfig(8, "affine", "per_channel"))

    @jax.jit
    def f(a):
        y, mults = ops.reuse_matmul(a, qt, impl="reuse_ref")
        assert mults is None
        return y

    x = jnp.asarray(rng.standard_normal((4, K)), jnp.float32)
    np.testing.assert_allclose(
        np.asarray(f(x)), np.asarray(ops.axllm_matmul(x, qt, impl="ref")),
        rtol=2e-5, atol=2e-4)


# ---------------------------------------------------------------------------
# 3. alphabet pinning (simulator <-> kernel contract)
# ---------------------------------------------------------------------------

def test_rc_alphabet_pinned_values():
    lv8, fold8 = R.rc_alphabet(8, "affine")
    assert fold8 is True and lv8.dtype == np.float32
    np.testing.assert_array_equal(lv8, np.arange(128, dtype=np.float32))
    lv4, fold4 = R.rc_alphabet(4, "affine")
    assert fold4 is True
    np.testing.assert_array_equal(lv4, np.arange(8, dtype=np.float32))
    nf4, foldn = R.rc_alphabet(4, "codebook")
    assert foldn is False and len(nf4) == 16
    np.testing.assert_array_equal(nf4, np.asarray(nf4_codebook(),
                                                  np.float32))
    id8, foldi = R.rc_alphabet(8, "codebook")
    assert foldi is False and len(id8) == 256
    with pytest.raises(ValueError):
        R.rc_alphabet(8, "nonsense")


def test_codebook_counts_use_unfolded_cells():
    """Codebook mode indexes the explicit 2^bits table — folding there
    would conflate codes c and -c whose table entries are distinct rows
    (and the identity-8 table's -128 entry has no positive mirror at
    all). Pin that the measured count equals the UNFOLDED analytics and
    differs from the folded one, so an accidental re-fold (the PR-1 bug
    class) trips this test."""
    rng = np.random.default_rng(20)
    codes = rng.integers(-8, 8, size=(K, N)).astype(np.int8)
    qt = _qtensor(codes, np.full((1, N), 1.0, np.float32), 4, "codebook")
    levels, fold = R.rc_alphabet(4, "codebook")
    assert fold is False
    _, _, bn, _ = ops.pick_blocks(4, K, N)
    unfolded = int(R.segment_unique_counts(codes, bn,
                                           fold_sign=False).sum())
    folded = int(R.segment_unique_counts(codes, bn, fold_sign=True).sum())
    assert folded < unfolded  # ±c pairs collapse under a fold
    x = jnp.ones((4, K), jnp.float32)
    _, mults = ops.reuse_matmul(x, qt, impl="reuse_interpret",
                                with_stats=True)
    assert int(mults) == unfolded != folded


@pytest.mark.parametrize("bits,mode", [(8, "affine"), (4, "affine"),
                                       (8, "codebook"), (4, "codebook")])
def test_kernel_cell_mapping_matches_fold_codes(bits, mode):
    """The kernel indexes its LUT as |c| (folded) or c + L/2 (unfolded);
    fold_codes uses |c| or c + 128. Both must induce the same partition
    of codes into cells — same distinct-count everywhere — or measured
    and predicted reuse drift apart."""
    levels, fold = R.rc_alphabet(bits, mode)
    n_levels = len(levels)
    if mode == "affine":
        lo, hi = -(n_levels - 1), n_levels
    else:
        lo, hi = -(n_levels // 2), n_levels // 2
    codes = np.arange(lo, hi, dtype=np.int32)
    kernel_cells = np.abs(codes) if fold else codes + (n_levels >> 1)
    lib_cells = R.fold_codes(codes.reshape(1, -1), fold_sign=fold).ravel()
    assert kernel_cells.min() >= 0
    assert kernel_cells.max() < n_levels
    # same partition: two codes share a kernel cell iff they share a
    # fold_codes cell (injective re-labeling)
    pairs = {}
    for kc, lc in zip(kernel_cells, lib_cells):
        assert pairs.setdefault(kc, lc) == lc
    assert len(set(pairs.values())) == len(pairs)


def test_fold_codes_rejects_packed_bytes():
    """Raw packed-int4 storage bytes must not silently count as cells
    (the kernel_bench provenance bug this PR fixed)."""
    rng = np.random.default_rng(10)
    codes = rng.integers(-7, 8, size=(64, 64)).astype(np.int8)
    packed = np.asarray(pack_int4(jnp.asarray(codes)))
    assert packed.dtype == np.uint8
    with pytest.raises(ValueError, match="packed"):
        R.fold_codes(packed, fold_sign=False)
    qt = _qtensor(codes, np.full((1, 64), 7.0, np.float32), 4, "affine",
                  packed=True)
    # the QTensor path decodes first and matches the unpacked counts
    np.testing.assert_array_equal(
        R.fold_codes(qt, fold_sign=True),
        R.fold_codes(codes, fold_sign=True))


# ---------------------------------------------------------------------------
# dispatch
# ---------------------------------------------------------------------------

def test_axllm_matmul_reuse_impl_dispatch():
    """axllm_matmul(impl='reuse*') routes through the reuse path and
    matches its own multiply path."""
    rng = np.random.default_rng(11)
    x = jnp.asarray(rng.standard_normal((8, K)), jnp.float32)
    qt = quantize(jnp.asarray(rng.standard_normal((K, N)), jnp.float32),
                  QuantConfig(8, "affine", "per_channel"))
    y_mul = np.asarray(ops.axllm_matmul(x, qt, impl="ref"))
    for impl in ("reuse", "reuse_ref", "reuse_interpret"):
        y = np.asarray(ops.axllm_matmul(x, qt, impl=impl))
        np.testing.assert_allclose(y, y_mul, rtol=2e-5, atol=2e-4,
                                   err_msg=impl)


def test_reuse_impl_flows_through_linear_and_lora():
    from repro.core.axllm_linear import linear
    rng = np.random.default_rng(12)
    x = jnp.asarray(rng.standard_normal((8, K)), jnp.float32)
    qt = quantize(jnp.asarray(rng.standard_normal((K, N)), jnp.float32),
                  QuantConfig(8, "affine", "per_channel"))
    y_mul = np.asarray(linear(x, qt, impl="auto"))
    y_reu = np.asarray(linear(x, qt, impl="reuse"))
    np.testing.assert_allclose(y_reu, y_mul, rtol=2e-5, atol=2e-4)
    a = jnp.asarray(rng.standard_normal((K, 8)) * 0.05, jnp.float32)
    b = jnp.asarray(rng.standard_normal((8, N)) * 0.05, jnp.float32)
    y_l_mul = np.asarray(ops.lora_matmul(x, qt, a, b, 2.0, impl="auto"))
    y_l_reu = np.asarray(ops.lora_matmul(x, qt, a, b, 2.0, impl="reuse"))
    np.testing.assert_allclose(y_l_reu, y_l_mul, rtol=2e-5, atol=2e-4)


def test_attention_ops_normalize_reuse_impl():
    """Reuse is a matmul concept; attention ops must treat impl='reuse'
    as their base dispatch instead of failing on an unknown string."""
    rng = np.random.default_rng(13)
    q = jnp.asarray(rng.standard_normal((2, 8, 4, 16)), jnp.float32)
    k = jnp.asarray(rng.standard_normal((2, 8, 2, 16)), jnp.float32)
    v = jnp.asarray(rng.standard_normal((2, 8, 2, 16)), jnp.float32)
    y_auto = np.asarray(ops.flash_attention(q, k, v, impl="auto"))
    y_reuse = np.asarray(ops.flash_attention(q, k, v, impl="reuse"))
    np.testing.assert_array_equal(y_reuse, y_auto)


# ---------------------------------------------------------------------------
# end-to-end: serve decode token-identity (acceptance criterion)
# ---------------------------------------------------------------------------

def _tiny_cfg():
    from repro.configs.base import ModelConfig
    return ModelConfig(name="reuse-e2e", family="dense", n_layers=2,
                       d_model=64, n_heads=4, n_kv_heads=2, d_ff=128,
                       vocab_size=256, head_dim=16, vocab_pad_multiple=64,
                       dtype="float32")


@pytest.mark.parametrize("quant,bits,mode,fuse", [
    (False, None, "affine", False),     # fp32 weights, reuse impl inert
    (True, 8, "affine", False),
    (True, 8, "affine", True),          # fused wqkv/gate_up
    (True, 4, "affine", False),         # packed int4
    (True, 4, "codebook", False),       # NF4
    (True, 4, "codebook", True),
], ids=["fp32", "int8", "int8-fused", "int4", "nf4", "nf4-fused"])
def test_engine_reuse_decode_token_identity(quant, bits, mode, fuse):
    """The acceptance bar: an engine dispatching every projection through
    the reuse path decodes the exact same tokens as the multiply path."""
    from repro.models.model import get_model
    from repro.serve.engine import ServeEngine
    cfg = _tiny_cfg()
    params = get_model(cfg).init(jax.random.PRNGKey(0))
    rng = np.random.default_rng(14)
    prompts = [rng.integers(0, cfg.vocab_size, size=pl).astype(np.int32)
               for pl in (5, 9, 3)]
    outs = {}
    for impl in ("auto", "reuse"):
        eng = ServeEngine(cfg, params, n_slots=2, max_len=64,
                          quantize=quant, quant_bits=bits, quant_mode=mode,
                          fuse_qkv=fuse, impl=impl)
        outs[impl] = eng.generate(prompts, max_new=8)
    for a, b in zip(outs["auto"], outs["reuse"]):
        assert a == b


@pytest.mark.slow
def test_engine_reuse_interpret_smoke():
    """One decode step through the actual kernel body (interpret mode) —
    slow, so marked out of the tier-1 default run."""
    from repro.models.model import get_model
    from repro.serve.engine import ServeEngine
    cfg = _tiny_cfg()
    params = get_model(cfg).init(jax.random.PRNGKey(0))
    prompts = [np.asarray([5, 7, 11], np.int32)]
    out_mul = ServeEngine(cfg, params, n_slots=1, max_len=16,
                          quantize=True, impl="auto").generate(
        prompts, max_new=2)
    out_int = ServeEngine(cfg, params, n_slots=1, max_len=16,
                          quantize=True, impl="reuse_interpret").generate(
        prompts, max_new=2)
    assert out_mul == out_int


# ---------------------------------------------------------------------------
# 5. ring collectives x reuse path (tensor-parallel serving, PR 7)
# ---------------------------------------------------------------------------

@pytest.mark.multi_device
@pytest.mark.parametrize("gran", ["per_channel", "per_group"])
def test_ring_allgather_matmul_matches_reuse_bit_exact(
        eight_cpu_devices, gran):
    """ring_allgather_matmul on a column-sharded QTensor must equal
    ops.reuse_matmul on the gathered operand BIT-FOR-BIT in the dyadic
    regime: the ring splits K into per-device blocks, each block runs the
    same reuse arithmetic, and the f32 block sums stay exact (partial
    sums < 2^24 * 2^-e), so the changed association cannot round."""
    from functools import partial

    from jax import shard_map
    from jax.sharding import PartitionSpec as P

    from repro.dist.collective_matmul import ring_allgather_matmul

    mesh = make_mesh((4,), ("model",), devices=eight_cpu_devices[:4])
    rng, x = _int_x(3)
    codes = rng.integers(-127, 128, size=(K, N)).astype(np.int8)
    if gran == "per_group":
        g = 128
        scale = np.full((K // g, 1, N), 127.0 * 2.0 ** -3, np.float32)
    else:
        scale = np.full((1, N), 127.0 * 2.0 ** -3, np.float32)
    qt = _qtensor(codes, scale, 8, "affine", granularity=gran)
    y_ref, _ = ops.reuse_matmul(x, qt, impl="reuse_ref")

    # shard_map moves the raw leaves; the local QTensor shard (full K
    # rows, N/4 columns) is rebuilt inside the body
    scale_spec = P(None, None, "model") if gran == "per_group" \
        else P(None, "model")

    @partial(shard_map, mesh=mesh,
             in_specs=(P(None, "model"), P(None, "model"), scale_spec),
             out_specs=P(None, "model"))
    def ring(x_l, codes_l, scale_l):
        w_l = QTensor(codes=codes_l, scale=scale_l, codebook=None,
                      bits=8, mode="affine", granularity=gran,
                      group_size=128, packed=False,
                      shape=(K, codes_l.shape[-1]))
        return ring_allgather_matmul(x_l, w_l, "model", impl="reuse_ref")

    y = ring(x, qt.codes, qt.scale)
    np.testing.assert_array_equal(np.asarray(y), np.asarray(y_ref))


@pytest.mark.multi_device
def test_ring_reducescatter_matmul_matches_reuse_bit_exact(
        eight_cpu_devices):
    """The row-parallel half: x column-sharded, W row-sharded, output
    reduce-scattered over N — still bit-exact vs the gathered reuse
    matmul in the dyadic regime (per-shard partials are exact dyadics)."""
    from functools import partial

    from jax import shard_map
    from jax.sharding import PartitionSpec as P

    from repro.dist.collective_matmul import ring_matmul_reducescatter

    mesh = make_mesh((4,), ("model",), devices=eight_cpu_devices[:4])
    rng, x = _int_x(4)
    codes = rng.integers(-127, 128, size=(K, N)).astype(np.int8)
    qt = _qtensor(codes, np.full((1, N), 127.0 * 2.0 ** -3, np.float32),
                  8, "affine")
    y_ref, _ = ops.reuse_matmul(x, qt, impl="reuse_ref")

    @partial(shard_map, mesh=mesh,
             in_specs=(P(None, "model"), P("model", None), P(None, None)),
             out_specs=P(None, "model"))
    def ring(x_l, codes_l, scale_l):
        w_l = QTensor(codes=codes_l, scale=scale_l, codebook=None,
                      bits=8, mode="affine", granularity="per_channel",
                      group_size=128, packed=False,
                      shape=codes_l.shape)
        return ring_matmul_reducescatter(x_l, w_l, "model",
                                         impl="reuse_ref")

    y = ring(x, qt.codes, qt.scale)
    np.testing.assert_array_equal(np.asarray(y), np.asarray(y_ref))
