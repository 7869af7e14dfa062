"""Pallas TPU kernel: AxLLM reuse (LUT) matmul — the paper's core, on device.

Where :mod:`repro.kernels.axllm_matmul` dequantizes every weight code and
multiplies (one MAC per element), this kernel implements the Result-Cache
semantics of paper §III.b: once per activation tile it materializes the
product of every activation element with the *code alphabet* — a
``levels``-entry table per (row, k) pair, SqueezeLLM/FineQuant-style — and
then *gathers* table entries for every repeated code instead of multiplying
again. For q-bit weights a row segment can contain at most ``2**q`` distinct
values, so the table build costs ``bm x bk x L`` multiplies and everything
past the first occurrence of a code is an add-only reuse.

Alphabet (shared contract with core/reuse.rc_alphabet — regression-pinned):
  affine    levels = [0 .. qmax] magnitudes, sign-folded: code ``c`` reads
            cell ``|c|`` and the sign rides on the gather (the paper's
            128-cell RC for 8-bit, 8 cells for int4). The per-channel
            ``scale/qmax`` factor is applied after the per-group reduction.
  codebook  levels = the explicit 2**bits table (NF4 / identity), unfolded:
            cell ``c + 2**(bits-1)``. NF4 is not sign-symmetric, so no fold.

TPU mapping: the gather is a signed one-hot contraction on the MXU, one
alphabet cell at a time — for cell ``l`` the LUT column ``x * levels[l]``
(``bm x bk`` multiplies, the only place activation values are multiplied)
meets the [bk, bn] selector ``sign * (cell == l)`` whose 0/±1 entries are the
"adds" of the reuse path. Summed over the L cells this is the
``[bm, bk·L] @ [bk·L, bn]`` gather-sum, built from 2-D tiles only, so VMEM
holds no [bk, bn, L] one-hot and the blocks stay lane-aligned whatever L is.
With ``count=True`` the kernel also *measures* its reuse: a second output
accumulates, once per (j, k) tile, the number of distinct alphabet cells per
k-row within the bn-wide column segment — i.e. the multiplies a Result Cache
would actually execute. The wrapper scales this by the logical M to report
the achieved multiply count, directly comparable against
``core.reuse.segment_unique_counts`` / ``simulator.simulate_matrix``
predictions (kernel_bench's predicted-vs-achieved row).

Grid = (M/bm, N/bn, K/bk). With ``count=True`` all dims are "arbitrary": the
multiply-count output is a single revisited (1, 1) block accumulated across
grid steps, which requires the sequential traversal order. The alphabet
lives in SMEM; packed int4 tiles are decoded in split column order (see
``axllm_matmul._unpack_nibbles``).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.axllm_matmul import (_unpack_nibbles, merge_columns,
                                        split_columns)


def _reuse_kernel(x_ref, codes_ref, scale_ref, levels_ref, out_ref, *rest,
                  packed: bool, fold_sign: bool, n_levels: int, groups: int,
                  n_k: int, count: bool):
    if count:
        mults_ref, acc_ref = rest
    else:
        (acc_ref,) = rest
    i, j, k = pl.program_id(0), pl.program_id(1), pl.program_id(2)

    @pl.when(k == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    codes = codes_ref[...]
    codes = _unpack_nibbles(codes) if packed else codes.astype(jnp.int32)
    if fold_sign:
        cells = jnp.abs(codes)                          # [bk, bn] in [0, L)
        sign = jnp.where(codes < 0, -1.0, 1.0).astype(jnp.float32)
    else:
        cells = codes + (n_levels >> 1)
        sign = jnp.ones(codes.shape, jnp.float32)
    x = x_ref[...].astype(jnp.float32)                  # [bm, bk]
    bk = x.shape[1]
    g = bk // groups
    for gi in range(groups):                            # scale groups
        xg, cg, sg = (x[:, gi * g:(gi + 1) * g], cells[gi * g:(gi + 1) * g],
                      sign[gi * g:(gi + 1) * g])

        def cell(l, part):
            # LUT column l (the multiplies) gathered by its signed selector
            sel = jnp.where(cg == l, sg, 0.0)
            return part + jax.lax.dot(
                xg * levels_ref[l], sel, precision=jax.lax.Precision.HIGHEST,
                preferred_element_type=jnp.float32)

        part = jax.lax.fori_loop(0, n_levels, cell,
                                 jnp.zeros(acc_ref.shape, jnp.float32))
        acc_ref[...] += part * scale_ref[gi:gi + 1, :]

    if count:
        @pl.when((i == 0) & (j == 0) & (k == 0))
        def _init_count():
            mults_ref[...] = jnp.zeros_like(mults_ref)

        # measured reuse: distinct cells per k-row within this bn segment
        # are the multiplies the RC executes; everything else was a table
        # hit. The count is activation-row-independent: tally it at i == 0.
        @pl.when(i == 0)
        def _count():
            def present(l, n):                       # n: [1, 1] int32
                hit = jnp.max(jnp.where(cells == l, 1, 0), axis=1,
                              keepdims=True)             # [bk, 1]
                return n + jnp.sum(hit, axis=0, keepdims=True)

            mults_ref[...] += jax.lax.fori_loop(
                0, n_levels, present, jnp.zeros((1, 1), jnp.int32))

    @pl.when(k == n_k - 1)
    def _flush():
        out_ref[...] = acc_ref[...]


@functools.partial(jax.jit, static_argnames=(
    "packed", "fold_sign", "group_size", "blocks", "count", "interpret"))
def reuse_matmul_pallas(x: jax.Array, codes: jax.Array, scale: jax.Array,
                        levels: jax.Array, *, packed: bool = False,
                        fold_sign: bool = True, group_size: int = 128,
                        blocks=(8, 128, 256), count: bool = True,
                        interpret: bool = False):
    """(y[M, N], mults[1, 1]) = reuse-matmul; see module docstring.

    ``scale`` is [1, N] (per_channel, with /qmax folded for affine) or
    [K/g, N] (per_group). ``levels`` is the [L] f32 alphabet value table
    from ``core.reuse.rc_alphabet``. ``mults`` is the per-activation-row
    multiply count: the sum over (k-row, bn-segment) of distinct alphabet
    cells — multiply by M for the total the lane array would execute.
    ``count=False`` skips the tally and returns ``mults=None``.
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
    groups = bk // group_size if per_group else 1
    if packed:
        scale = split_columns(scale, bn)

    x_spec = pl.BlockSpec((bm, bk), lambda i, j, k: (i, k))
    if packed:
        codes_spec = pl.BlockSpec((bk, bn // 2), lambda i, j, k: (k, j))
    else:
        codes_spec = pl.BlockSpec((bk, bn), lambda i, j, k: (k, j))
    if per_group:
        scale_spec = pl.BlockSpec((groups, bn), lambda i, j, k: (k, j))
    else:
        scale_spec = pl.BlockSpec((1, bn), lambda i, j, k: (0, j))
    levels_spec = pl.BlockSpec(memory_space=pltpu.SMEM)
    out_specs = [pl.BlockSpec((bm, bn), lambda i, j, k: (i, j))]
    out_shape = [jax.ShapeDtypeStruct((m, n), jnp.float32)]
    if count:
        out_specs.append(pl.BlockSpec((1, 1), lambda i, j, k: (0, 0)))
        out_shape.append(jax.ShapeDtypeStruct((1, 1), jnp.int32))

    kernel = functools.partial(
        _reuse_kernel, packed=packed, fold_sign=fold_sign,
        n_levels=levels.shape[0], groups=groups, n_k=n_k, count=count)

    outs = pl.pallas_call(
        kernel,
        grid=(m // bm, n // bn, n_k),
        in_specs=[x_spec, codes_spec, scale_spec, levels_spec],
        out_specs=out_specs,
        out_shape=out_shape,
        scratch_shapes=[pltpu.VMEM((bm, bn), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",) * 3 if count
            else ("parallel", "parallel", "arbitrary")),
        interpret=interpret,
    )(x, codes, scale, levels.astype(jnp.float32))
    y = merge_columns(outs[0], bn) if packed else outs[0]
    return y, (outs[1] if count else None)
