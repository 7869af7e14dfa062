"""Mosaic compiles of the serving path's Pallas kernels at repro-100m widths.

Each case lowers a kernel for one chip of a *described* TPU v5e (no chip
attached: the TPU compiler is installed and compiles for the description)
and compiles it, so a block shape or VMEM budget the chip's compiler would
refuse fails here, at no chip time. Nothing runs; results are checked by
the interpret-mode tests and by chip_smoke.py on the chip.

The topology is described inside a module fixture, never at import: only
one process at a time may load the TPU library, and every test worker
imports this file.
"""

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.configs.repro_100m import CONFIG
from repro.kernels import ops

D, DFF = CONFIG.d_model, CONFIG.d_ff                      # 768, 2048
H, HK, HD = CONFIG.n_heads, CONFIG.n_kv_heads, CONFIG.head_dim   # 12, 4, 64
B, S, BLOCK = 4, 256, 16          # decode slots, max_len, KV block tokens
NB, MB = 2 * B * (S // BLOCK) + 2, S // BLOCK


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


def _compile(one_chip, fn, *shapes):
    args = [jax.ShapeDtypeStruct(s, dt, sharding=one_chip)
            for s, dt in shapes]
    text = jax.jit(fn).lower(*args).compile().as_text()
    assert "tpu_custom_call" in text


def _decode(k_scale=False, paged=False):
    from repro.kernels import decode_attention as da
    from repro.kernels import paged_decode_attention as pda

    if paged:
        return lambda q, k, v, bt, ln, *sc: pda.paged_decode_attention_pallas(
            q, k, v, bt, ln, k_scale=sc[0] if sc else None,
            v_scale=sc[1] if sc else None)
    return lambda q, k, v, ln, *sc: da.decode_attention_pallas(
        q, k, v, ln, k_scale=sc[0] if sc else None,
        v_scale=sc[1] if sc else None)


@pytest.mark.parametrize("kv", ["bf16", "int8"])
def test_decode_attention_compiles(one_chip, kv):
    dt = jnp.int8 if kv == "int8" else jnp.bfloat16
    shapes = [((B, H, HD), jnp.bfloat16), ((B, S, HK, HD), dt),
              ((B, S, HK, HD), dt), ((B,), jnp.int32)]
    if kv == "int8":
        shapes += [((B, S, HK, 1), jnp.float32)] * 2
    _compile(one_chip, _decode(), *shapes)


@pytest.mark.parametrize("kv", ["bf16", "int8"])
def test_paged_decode_attention_compiles(one_chip, kv):
    dt = jnp.int8 if kv == "int8" else jnp.bfloat16
    shapes = [((B, H, HD), jnp.bfloat16), ((NB, BLOCK, HK, HD), dt),
              ((NB, BLOCK, HK, HD), dt), ((B, MB), jnp.int32),
              ((B,), jnp.int32)]
    if kv == "int8":
        shapes += [((NB, BLOCK, HK, 1), jnp.float32)] * 2
    _compile(one_chip, _decode(paged=True), *shapes)


# (m, K, N): decode m = slots, prefill m = 4 x 32; one tensor-parallel
# shard of a column-parallel projection (N / 4 = 192 lanes)
MATMULS = [(B, D, D), (B, D, HK * HD), (B, D, DFF), (B, DFF, D),
           (128, DFF, D), (B, D, D // 4)]


def _blocks(m, k, n):
    return ops.pick_blocks(m, k, n)[:3]


@pytest.mark.parametrize("m,k,n", MATMULS)
def test_axllm_matmul_int8_compiles(one_chip, m, k, n):
    from repro.kernels.axllm_matmul import axllm_matmul_pallas
    mp = m + ops.pick_blocks(m, k, n)[3]
    _compile(one_chip, lambda x, c, s: axllm_matmul_pallas(
        x, c, s, blocks=_blocks(m, k, n)),
        ((mp, k), jnp.bfloat16), ((k, n), jnp.int8), ((1, n), jnp.float32))


@pytest.mark.parametrize("m,k,n", MATMULS[:1] + MATMULS[3:5])
def test_axllm_matmul_nf4_packed_compiles(one_chip, m, k, n):
    from repro.kernels.axllm_matmul import axllm_matmul_pallas
    mp = m + ops.pick_blocks(m, k, n)[3]
    _compile(one_chip, lambda x, c, s, cb: axllm_matmul_pallas(
        x, c, s, cb, bits=4, packed=True, blocks=_blocks(m, k, n)),
        ((mp, k), jnp.bfloat16), ((k, n // 2), jnp.uint8),
        ((1, n), jnp.float32), ((16,), jnp.float32))


@pytest.mark.parametrize("count", [False, True], ids=["serve", "counting"])
@pytest.mark.parametrize("m,k,n", [(B, D, D), (128, D, D)])
def test_reuse_matmul_compiles(one_chip, m, k, n, count):
    from repro.kernels.reuse_matmul import reuse_matmul_pallas
    mp = m + ops.pick_blocks(m, k, n)[3]
    _compile(one_chip, lambda x, c, s, lv: reuse_matmul_pallas(
        x, c, s, lv, blocks=_blocks(m, k, n), count=count),
        ((mp, k), jnp.bfloat16), ((k, n), jnp.int8), ((1, n), jnp.float32),
        ((128,), jnp.float32))


@pytest.mark.parametrize("s", [32, S])
def test_flash_attention_compiles(one_chip, s):
    from repro.kernels.flash_attention import flash_attention_pallas
    _compile(one_chip, flash_attention_pallas,
             ((4, s, H, HD), jnp.bfloat16), ((4, s, HK, HD), jnp.bfloat16),
             ((4, s, HK, HD), jnp.bfloat16))
