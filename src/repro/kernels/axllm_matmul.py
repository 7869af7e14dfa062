"""Pallas TPU kernel: fused AxLLM dequant-matmul.

TPU mapping of the paper's Result Cache (DESIGN.md §2): weights live in HBM as
q-bit codes; the 2^q-entry codebook (the RC) is resident in SMEM for the whole
kernel invocation and every weight tile is decoded *in VMEM* right before
the MXU contraction — never re-fetched from HBM. Affine codes are integers,
exact in bf16, so they meet bf16 activations on the MXU as they are and the
per-channel scale is applied once to the f32 accumulator.
The HBM traffic is `bytes(int8 codes) = N·M` instead of `2·N·M` (bf16) or
`4·N·M` (f32); for int4-codebook mode it is `N·M/2` plus a 16-float table.

Layout & tiling
  x     [M, K]   activations (bf16/f32), blocked (bm, bk)
  codes [K, N]   int8 (or uint8-packed int4), blocked (bk, bn)
  scale per-channel [1, N] f32, blocked (1, bn)       (affine / codebook)
        per-group  [K/g, N] f32, blocked (bk/g, bn)   (per_group affine)
  out   [M, N]   f32 accumulation across the K grid dimension.

Grid = (M/bm, N/bn, K/bk), K innermost ("arbitrary" semantics) so the f32
accumulator tile persists in VMEM scratch across K steps. MXU-aligned block
defaults (bm, bk, bn) = (128, 512, 256); VMEM footprint ≈ x 128·512·4 +
codes 512·256 + acc 128·256·4 ≈ 0.5 MB — far under the ~16 MB v5e budget,
leaving room for double buffering.
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

DEFAULT_BLOCKS = (128, 512, 256)  # (bm, bk, bn)

# Decode-shape M blocks, preferred order. Serving batches are small
# (m = n_slots·decode tokens, typically 1..64); picking the largest entry
# that divides m exactly gives a no-pad fast path for m ∈ {8..64} instead
# of rounding every call up to the 128-row tile. Skinny-m launches pair
# with a widened bn (ops.pick_blocks) to keep the MXU busy.
SKINNY_BM = (64, 32, 16, 8)


def _unpack_nibbles(packed):
    """uint8 [bk, bn/2] -> int32 [bk, bn] codes in [-8, 7], in *split*
    column order: the low nibbles (the tile's even columns) then the high
    nibbles (its odd columns). A lane concatenation instead of an
    interleave keeps the unpack within what Mosaic lowers; the wrappers put
    scales into that order with :func:`split_columns` and restore the
    output with :func:`merge_columns`."""
    p = packed.astype(jnp.int32)
    lo = p & 0xF
    hi = (p >> 4) & 0xF
    lo = jnp.where(lo >= 8, lo - 16, lo)
    hi = jnp.where(hi >= 8, hi - 16, hi)
    return jnp.concatenate([lo, hi], axis=1)


def split_columns(a, bn: int):
    """[..., N] natural column order -> per-bn-tile split order (even
    columns of each tile, then its odd columns) — see _unpack_nibbles."""
    *lead, n = a.shape
    return a.reshape(*lead, n // bn, bn // 2, 2).swapaxes(-1, -2) \
        .reshape(*lead, n)


def merge_columns(a, bn: int):
    """Inverse of :func:`split_columns`."""
    *lead, n = a.shape
    return a.reshape(*lead, n // bn, 2, bn // 2).swapaxes(-1, -2) \
        .reshape(*lead, n)


def _codebook_values(codes, cb_ref, n_levels: int):
    """codebook[codes + n_levels/2] as f32, reading the table from SMEM:
    one compare-select per level (the RC lookup without a gather)."""
    offset = n_levels // 2

    def level(i, w):
        return jnp.where(codes == i - offset, cb_ref[i], w)

    return jax.lax.fori_loop(0, n_levels, level,
                             jnp.zeros(codes.shape, jnp.float32),
                             unroll=n_levels <= 16)


def _dot(x, w):
    """x [bm, bk] @ w [bk, bn] -> f32. Integer-valued weights go to the MXU
    in the activation dtype (int8/int4 codes are exact in bf16); anything
    else contracts in f32 at full precision."""
    if x.dtype == jnp.bfloat16 and w.dtype == jnp.int32:
        return jax.lax.dot(x, w.astype(jnp.bfloat16),
                           preferred_element_type=jnp.float32)
    return jax.lax.dot(x.astype(jnp.float32), w.astype(jnp.float32),
                       precision=jax.lax.Precision.HIGHEST,
                       preferred_element_type=jnp.float32)


def _axllm_kernel(x_ref, codes_ref, scale_ref, *rest, bits: int,
                  packed: bool, codebook: bool, per_group: bool,
                  group_size: int, n_k: int):
    if codebook:
        cb_ref, out_ref, acc_ref = rest
    else:
        out_ref, acc_ref = rest
    k = pl.program_id(2)

    @pl.when(k == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    codes = codes_ref[...]
    codes = _unpack_nibbles(codes) if packed else codes.astype(jnp.int32)
    w = _codebook_values(codes, cb_ref, 1 << bits) if codebook else codes
    if per_group:
        # scale [bk/g, bn] varies along K: dequantize before the contraction
        bk, bn = w.shape
        w = (w.astype(jnp.float32).reshape(bk // group_size, group_size, bn)
             * scale_ref[...][:, None, :]).reshape(bk, bn)
    acc_ref[...] += _dot(x_ref[...], w)

    @pl.when(k == n_k - 1)
    def _flush():
        # per-channel scales are constant along K: applied once, here
        out_ref[...] = acc_ref[...] if per_group \
            else acc_ref[...] * scale_ref[...]


@functools.partial(jax.jit, static_argnames=(
    "bits", "packed", "group_size", "blocks", "interpret"))
def axllm_matmul_pallas(x: jax.Array, codes: jax.Array, scale: jax.Array,
                        codebook: Optional[jax.Array] = None, *,
                        bits: int = 8, packed: bool = False,
                        group_size: int = 128,
                        blocks=DEFAULT_BLOCKS,
                        interpret: bool = False) -> jax.Array:
    """y[M, N] = x[M, K] @ deq(codes[K, N]); see module docstring.

    `scale` must be [1, N] (per_channel/per_tensor broadcast) or [K/g, N]
    (per_group). `codes` is [K, N] int8, or [K, N//2] uint8 when packed.
    """
    m, kdim = x.shape
    n = scale.shape[-1]
    bm, bk, bn = blocks
    bm = min(bm, m)
    bk = min(bk, kdim)
    bn = min(bn, n)
    if m % bm or kdim % bk or n % bn:
        raise ValueError(f"shape ({m},{kdim},{n}) not divisible by blocks "
                         f"({bm},{bk},{bn})")
    n_k = kdim // bk
    per_group = scale.shape[0] > 1
    if per_group and bk % group_size:
        raise ValueError("per_group requires group_size | bk")

    if packed:
        # the kernel sees each tile's columns in split order (even, odd)
        scale = split_columns(scale, bn)
    x_spec = pl.BlockSpec((bm, bk), lambda i, j, k: (i, k))
    if packed:
        codes_spec = pl.BlockSpec((bk, bn // 2), lambda i, j, k: (k, j))
    else:
        codes_spec = pl.BlockSpec((bk, bn), lambda i, j, k: (k, j))
    if per_group:
        scale_spec = pl.BlockSpec((bk // group_size, bn),
                                  lambda i, j, k: (k, j))
    else:
        scale_spec = pl.BlockSpec((1, bn), lambda i, j, k: (0, j))
    out_spec = pl.BlockSpec((bm, bn), lambda i, j, k: (i, j))

    in_specs = [x_spec, codes_spec, scale_spec]
    args = [x, codes, scale]
    if codebook is not None:
        in_specs.append(pl.BlockSpec(memory_space=pltpu.SMEM))
        args.append(codebook.astype(jnp.float32))

    kernel = functools.partial(
        _axllm_kernel, bits=bits, packed=packed,
        codebook=codebook is not None, per_group=per_group,
        group_size=group_size, n_k=n_k)

    y = pl.pallas_call(
        kernel,
        grid=(m // bm, n // bn, n_k),
        in_specs=in_specs,
        out_specs=out_spec,
        out_shape=jax.ShapeDtypeStruct((m, n), jnp.float32),
        scratch_shapes=[pltpu.VMEM((bm, bn), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret,
    )(*args)
    return merge_columns(y, bn) if packed else y
