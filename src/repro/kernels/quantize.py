"""Pallas TPU kernel: per-channel absmax quantization.

Used at deploy time (weight conversion) and for KV-cache quantization bursts.
Grid over column strips; each strip reduces |w| over the full K dimension in
VMEM, then rounds. K x bn x 4B must fit VMEM (checked; ops.py falls back to
the jnp oracle for oversized K, where XLA streams the reduction).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

MAX_K_VMEM = 8192


def _quant_kernel(w_ref, codes_ref, scale_ref, *, qmax: int):
    w = w_ref[...].astype(jnp.float32)                   # [K, bn]
    absmax = jnp.max(jnp.abs(w), axis=0, keepdims=True)  # [1, bn]
    scale = jnp.maximum(absmax, 1e-8) / qmax
    codes = jnp.clip(jnp.round(w / scale), -qmax, qmax)
    codes_ref[...] = codes.astype(jnp.int8)
    scale_ref[...] = scale


@functools.partial(jax.jit, static_argnames=("bits", "block_n", "interpret"))
def quantize_pallas(w, *, bits: int = 8, block_n: int = 256,
                    interpret: bool = False):
    """w: [K, N] -> (codes int8 [K, N], scale f32 [1, N])."""
    k, n = w.shape
    if k > MAX_K_VMEM:
        raise ValueError(f"K={k} exceeds single-pass VMEM budget; use ref")
    bn = min(block_n, n)
    if n % bn:
        raise ValueError(f"N={n} not divisible by block {bn}")
    qmax = (1 << (bits - 1)) - 1
    codes, scale = pl.pallas_call(
        functools.partial(_quant_kernel, qmax=qmax),
        grid=(n // bn,),
        in_specs=[pl.BlockSpec((k, bn), lambda j: (0, j))],
        out_specs=[pl.BlockSpec((k, bn), lambda j: (0, j)),
                   pl.BlockSpec((1, bn), lambda j: (0, j))],
        out_shape=[jax.ShapeDtypeStruct((k, n), jnp.int8),
                   jax.ShapeDtypeStruct((1, n), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel",)),
        interpret=interpret,
    )(w)
    return codes, scale
