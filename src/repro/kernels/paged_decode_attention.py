"""Pallas TPU kernel: single-token decode attention against a block-paged
KV pool (flash-decode through a block table).

The serving engine stores KV in fixed-size blocks inside a shared pool
``[n_blocks, block, Hk, d]`` with per-slot block tables — the KV-side
analogue of the paper's Result Cache: identical prompt prefixes map to the
*same* physical blocks, so their KV is computed once and reused by every
request that shares them (see repro/serve/paged_cache.py). This kernel is
the dense flash-decode kernel of ``decode_attention.py`` generalized to
gather its KV tiles through that indirection, and shares its tile body.

Grid: (B, n_blocks_per_seq). The block table and the per-row valid lengths
ride in as scalar-prefetch operands, so each KV tile's DMA source address is
computed from ``block_tables[b, ib]`` *before* the kernel body runs
(pltpu.PrefetchScalarGridSpec) — the gather costs no extra pass over HBM.
Each step loads one [block, Hk·d] tile (the pool viewed as
[n_blocks, block, Hk·d]) and serves all H query heads of the row from it;
online-softmax state lives in VMEM scratch across the block dimension.
int8-KV per-(position, head) scales stream through the same index map.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.decode_attention import (attend_tile, expand_queries,
                                            flush_state, init_state,
                                            own_heads, scratch_shapes)


def _paged_decode_kernel(len_ref, bt_ref, q_ref, k_ref, v_ref, *rest,
                         scale: float, bs: int, n_b: int, rep: int,
                         quantized: bool):
    if quantized:
        ks_ref, vs_ref, o_ref, m_ref, l_ref, acc_ref = rest
    else:
        ks_ref = vs_ref = None
        o_ref, m_ref, l_ref, acc_ref = rest
    b, ib = pl.program_id(0), pl.program_id(1)
    length = len_ref[b]

    @pl.when(ib == 0)
    def _init():
        init_state(m_ref, l_ref, acc_ref)

    # block ib holds positions [ib*bs, (ib+1)*bs) of the row's logical
    # sequence, wherever the block table placed them in the pool; blocks
    # wholly past the row's length are skipped
    @pl.when(ib * bs < length)
    def _tile():
        attend_tile(q_ref, k_ref, v_ref, ks_ref, vs_ref, m_ref, l_ref,
                    acc_ref, length=length, kpos0=ib * bs, scale=scale,
                    rep=rep)

    @pl.when(ib == n_b - 1)
    def _flush():
        flush_state(o_ref, l_ref, acc_ref)


@functools.partial(jax.jit, static_argnames=("interpret",))
def paged_decode_attention_pallas(q, k_pool, v_pool, block_tables, length, *,
                                  k_scale=None, v_scale=None,
                                  interpret: bool = False):
    """q: [B, H, d]; pools: [NB, bs, Hk, d]; block_tables: [B, MB] int32
    (pool block id of each row's ib-th logical block); length: [B].
    Returns [B, H, d]. Entries of the table beyond a row's valid length may
    point anywhere in the pool (conventionally block 0, the trash block) —
    the length mask keeps them out of the softmax.
    """
    b, h, d = q.shape
    nb, bs, hk = k_pool.shape[0], k_pool.shape[1], k_pool.shape[2]
    mb = block_tables.shape[1]
    quantized = k_scale is not None

    def kv_index(bi, ib, len_ref, bt_ref):
        return (bt_ref[bi, ib], 0, 0)

    kv_spec = pl.BlockSpec((1, bs, hk * d), kv_index)
    in_specs = [pl.BlockSpec((1, h, hk * d),
                             lambda bi, ib, len_ref, bt_ref: (bi, 0, 0)),
                kv_spec, kv_spec]
    args = [expand_queries(q, hk), k_pool.reshape(nb, bs, hk * d),
            v_pool.reshape(nb, bs, hk * d)]
    if quantized:
        in_specs += [pl.BlockSpec((1, bs, hk), kv_index)] * 2
        args += [k_scale.reshape(nb, bs, hk), v_scale.reshape(nb, bs, hk)]

    out = pl.pallas_call(
        functools.partial(_paged_decode_kernel, scale=1.0 / (d ** 0.5),
                          bs=bs, n_b=mb, rep=h // hk, quantized=quantized),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,          # lengths + block table in SMEM
            grid=(b, mb),
            in_specs=in_specs,
            out_specs=pl.BlockSpec(
                (1, h, hk * d), lambda bi, ib, len_ref, bt_ref: (bi, 0, 0)),
            scratch_shapes=scratch_shapes(h, hk * d)),
        out_shape=jax.ShapeDtypeStruct((b, h, hk * d), jnp.float32),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary")),
        interpret=interpret,
    )(length.astype(jnp.int32), block_tables.astype(jnp.int32), *args)
    return own_heads(out, hk).astype(q.dtype)
