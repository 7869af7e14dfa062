"""Pallas TPU kernel: single-token decode attention against a block-paged
KV pool (flash-decode through a block table).

The serving engine stores KV in fixed-size blocks inside a shared pool
``[n_layers, n_blocks, block, Hk, d]`` with per-slot block tables — the KV-side
analogue of the paper's Result Cache: identical prompt prefixes map to the
*same* physical blocks, so their KV is computed once and reused by every
request that shares them (see repro/serve/paged_cache.py). This kernel is
the dense flash-decode kernel of ``decode_attention.py`` generalized to
gather its KV tiles through that indirection, and shares its online
softmax.

Grid: (B, n_blocks_per_seq). The block table, the per-row valid lengths
and the layer ride in as scalar-prefetch operands, so each KV tile's DMA
source address is computed from ``(layer, block_tables[b, ib])`` *before*
the kernel body runs (pltpu.PrefetchScalarGridSpec) — the gather costs no
extra pass over HBM, and the kernel reads one layer of the decode step's
stacked pool in place. Each step loads one block as the pool stores it,
``[block, Hk, d]``, views it in VMEM as ``block·Hk`` rows of d (row r:
position r // Hk, KV head r % Hk), and serves all H query heads of the
row from it: each head scores every row and masks the rows of other KV
heads. No layout of the pool is assumed beyond its shape, so XLA never
relays a pool for the call. Online-softmax state lives in VMEM scratch
across the block dimension. int8-KV per-(position, head) scales stream
through the same index map, as ``[Hk, block]`` tiles (the order they are
stored in).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.decode_attention import (NEG_INF, flush_state,
                                            fold_scores, init_state,
                                            scratch_shapes)

_HIGHEST = jax.lax.Precision.HIGHEST


def _row_scales(scale_tile, h: int, rep: int):
    """[Hk, bs] per-(position, KV head) scales -> [H, bs·Hk]: entry
    (h, r) is the scale of position r // Hk in query head h's KV head
    (0/1 contractions, exact at HIGHEST); rows of other KV heads are
    masked by the caller."""
    hk, bs = scale_tile.shape
    own = (jax.lax.broadcasted_iota(jnp.int32, (h, hk), 0) // rep
           == jax.lax.broadcasted_iota(jnp.int32, (h, hk), 1))
    spread = (jax.lax.broadcasted_iota(jnp.int32, (bs, bs * hk), 1) // hk
              == jax.lax.broadcasted_iota(jnp.int32, (bs, bs * hk), 0))
    per_head = jax.lax.dot(own.astype(jnp.float32),
                           scale_tile.astype(jnp.float32),
                           precision=_HIGHEST,
                           preferred_element_type=jnp.float32)
    return jax.lax.dot(per_head, spread.astype(jnp.float32),
                       precision=_HIGHEST,
                       preferred_element_type=jnp.float32)


def _paged_decode_kernel(len_ref, bt_ref, layer_ref, q_ref, k_ref, v_ref,
                         *rest, scale: float, bs: int, hk: int, n_b: int,
                         rep: int, quantized: bool):
    del layer_ref                     # read by the KV index map only
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
        q = q_ref[0].astype(jnp.float32)                   # [H, d]
        k = k_ref[...].astype(jnp.float32).reshape(bs * hk, -1)  # rows
        v = v_ref[...].astype(jnp.float32).reshape(bs * hk, -1)
        h, n = q.shape[0], k.shape[0]
        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                precision=_HIGHEST,
                                preferred_element_type=jnp.float32) * scale
        if quantized:
            s = s * _row_scales(ks_ref[...], h, rep)
        r = jax.lax.broadcasted_iota(jnp.int32, (h, n), 1)
        head = jax.lax.broadcasted_iota(jnp.int32, (h, n), 0) // rep
        valid = (r % hk == head) & (ib * bs + r // hk < length)
        s = jnp.where(valid, s, NEG_INF)
        fold_scores(s, valid, v,
                    _row_scales(vs_ref[...], h, rep) if quantized else None,
                    m_ref, l_ref, acc_ref)

    @pl.when(ib == n_b - 1)
    def _flush():
        flush_state(o_ref, l_ref, acc_ref)


@functools.partial(jax.jit, static_argnames=("interpret",))
def paged_decode_attention_pallas(q, k_pool, v_pool, block_tables, length,
                                  layer=None, *, k_scale=None, v_scale=None,
                                  interpret: bool = False):
    """q: [B, H, d]; pools: [NB, bs, Hk, d], or with ``layer`` (an int32
    scalar) the stack [L, NB, bs, Hk, d] of every layer's pool, read in
    place at that layer; block_tables: [B, MB] int32 (pool block id of
    each row's ib-th logical block); length: [B]. Scales, when given,
    have the pools' shape with a last dim of 1. Returns [B, H, d].
    Entries of the table beyond a row's valid length may point anywhere
    in the pool (conventionally block 0, the trash block) — the length
    mask keeps them out of the softmax.
    """
    if layer is None:
        k_pool, v_pool, layer = k_pool[None], v_pool[None], 0
        if k_scale is not None:
            k_scale, v_scale = k_scale[None], v_scale[None]
    b, h, d = q.shape
    bs, hk = k_pool.shape[2:4]
    mb = block_tables.shape[1]
    quantized = k_scale is not None

    # index maps take the prefetched lengths, table and layer refs last
    def kv_index(bi, ib, len_ref, bt_ref, layer_ref):
        return (layer_ref[0], bt_ref[bi, ib], 0, 0, 0)

    def row_index(bi, ib, len_ref, bt_ref, layer_ref):
        return (bi, 0, 0)

    kv_spec = pl.BlockSpec((None, None, bs, hk, d), kv_index)
    in_specs = [pl.BlockSpec((1, h, d), row_index), kv_spec, kv_spec]
    args = [q, k_pool, v_pool]
    if quantized:
        # [L, NB, bs, Hk, 1] scales as [L, NB, Hk, bs]: the order in which
        # the device stores them, with positions in the lanes
        in_specs += [pl.BlockSpec((None, None, hk, bs),
                                  lambda *a: kv_index(*a)[:4])] * 2
        args += [jnp.swapaxes(sc[..., 0], -1, -2) for sc in (k_scale,
                                                             v_scale)]

    out = pl.pallas_call(
        functools.partial(_paged_decode_kernel, scale=1.0 / (d ** 0.5),
                          bs=bs, hk=hk, n_b=mb, rep=h // hk,
                          quantized=quantized),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=3,   # lengths, block table, layer in SMEM
            grid=(b, mb),
            in_specs=in_specs,
            out_specs=pl.BlockSpec((1, h, d), row_index),
            scratch_shapes=scratch_shapes(h, d)),
        out_shape=jax.ShapeDtypeStruct((b, h, d), jnp.float32),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary")),
        interpret=interpret,
    )(length.astype(jnp.int32), block_tables.astype(jnp.int32),
      jnp.asarray(layer, jnp.int32).reshape(1), *args)
    return out.astype(q.dtype)
