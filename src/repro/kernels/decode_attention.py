"""Pallas TPU kernel: single-token decode attention (flash-decode) with
optional int8-quantized KV cache.

Decode is the memory-roofline cell: per step the whole KV cache streams
HBM->VMEM once while doing O(S·d) FLOPs. Quantizing the cache to int8 halves
those bytes — the KV-side counterpart of the AxLLM weight-code traffic
reduction (DESIGN.md §2) and a §Perf lever for decode_32k. Dequantization is
fused: codes and per-(position, head) scales stream in, f32 math in VMEM.

Grid: (B, S/bs) with the online-softmax state of all H query heads in VMEM
scratch across the S dimension. Each step loads one [bs, Hk·d] KV tile — the
cache viewed as [B, S, Hk·d], whose last two block dims are lane/sublane
aligned for any head count — and serves every query head of the row from it,
so each tile crosses HBM once per row rather than once per query head.

GQA without slicing heads out of the tile: the wrapper lays the queries out
block-diagonally ([H, Hk·d], row h holds q_h in the lanes of its KV head
h // rep and zeros elsewhere), so one [H, Hk·d] x [bs, Hk·d]^T contraction
gives exactly each head's scores. The accumulator keeps all Hk lane groups
per row and the wrapper picks each head's own group at the end. The valid
length rides in as a scalar-prefetch operand (SMEM).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30
_HIGHEST = jax.lax.Precision.HIGHEST


def expand_queries(q: jax.Array, hk: int) -> jax.Array:
    """[B, H, d] -> block-diagonal [B, H, Hk·d]: row h keeps q_h in the
    lanes of KV head h // (H / Hk) and zeros in the others."""
    b, h, d = q.shape
    own = jax.nn.one_hot(jnp.arange(h) // (h // hk), hk, dtype=q.dtype)
    return (q[:, :, None, :] * own[None, :, :, None]).reshape(b, h, hk * d)


def own_heads(out: jax.Array, hk: int) -> jax.Array:
    """[B, H, Hk·d] -> [B, H, d]: each query head's own KV-head lanes."""
    b, h, hkd = out.shape
    heads = jnp.arange(h)
    return out.reshape(b, h, hk, hkd // hk)[:, heads, heads // (h // hk)]


def _row_scales(scale_tile, h: int, rep: int):
    """[bs, Hk] per-(position, KV head) scales -> [H, bs]: row h reads
    its own KV head's column (a 0/1 contraction, exact at HIGHEST)."""
    hk = scale_tile.shape[1]
    own = (jax.lax.broadcasted_iota(jnp.int32, (h, hk), 0) // rep
           == jax.lax.broadcasted_iota(jnp.int32, (h, hk), 1))
    return jax.lax.dot_general(
        own.astype(jnp.float32), scale_tile.astype(jnp.float32),
        (((1,), (1,)), ((), ())), precision=_HIGHEST,
        preferred_element_type=jnp.float32)


def attend_tile(q_ref, k_ref, v_ref, ks_ref, vs_ref, m_ref, l_ref, acc_ref,
                *, length, kpos0, scale: float, rep: int):
    """Fold one KV tile into the online-softmax state of every query head.

    q_ref: [1, H, Hk·d] block-diagonal queries; k_ref/v_ref: [1, bs, Hk·d]
    (int8 codes when ks_ref/vs_ref [1, bs, Hk] are given); m_ref/l_ref:
    [H, 128] running max / denominator; acc_ref: [H, Hk·d]. Keys at
    positions kpos0 + [0, bs) at or past ``length`` are masked."""
    q = q_ref[0].astype(jnp.float32)                       # [H, Hk·d]
    k = k_ref[0].astype(jnp.float32)                       # [bs, Hk·d]
    v = v_ref[0].astype(jnp.float32)
    h, bs = q.shape[0], k.shape[0]
    s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                            precision=_HIGHEST,
                            preferred_element_type=jnp.float32) * scale
    if ks_ref is not None:
        s = s * _row_scales(ks_ref[0], h, rep)
    kpos = kpos0 + jax.lax.broadcasted_iota(jnp.int32, (h, bs), 1)
    valid = kpos < length
    s = jnp.where(valid, s, NEG_INF)

    m_prev = m_ref[:, :1]                                  # [H, 1]
    l_prev = l_ref[:, :1]
    m_new = jnp.maximum(m_prev, s.max(axis=-1, keepdims=True))
    p = jnp.exp(s - m_new) * valid.astype(jnp.float32)
    corr = jnp.exp(m_prev - m_new)
    l_new = l_prev * corr + p.sum(axis=-1, keepdims=True)
    pv = p if vs_ref is None else p * _row_scales(vs_ref[0], h, rep)
    acc_ref[...] = acc_ref[...] * corr + jax.lax.dot(
        pv, v, precision=_HIGHEST, preferred_element_type=jnp.float32)
    m_ref[...] = jnp.broadcast_to(m_new, m_ref.shape)
    l_ref[...] = jnp.broadcast_to(l_new, l_ref.shape)


def init_state(m_ref, l_ref, acc_ref):
    m_ref[...] = jnp.full_like(m_ref, NEG_INF)
    l_ref[...] = jnp.zeros_like(l_ref)
    acc_ref[...] = jnp.zeros_like(acc_ref)


def flush_state(o_ref, l_ref, acc_ref):
    # length-0 rows: l == 0 and acc == 0, so the output is exactly zero
    o_ref[0] = (acc_ref[...] /
                jnp.maximum(l_ref[:, :1], 1e-30)).astype(o_ref.dtype)


def scratch_shapes(h: int, hkd: int):
    return [pltpu.VMEM((h, 128), jnp.float32),
            pltpu.VMEM((h, 128), jnp.float32),
            pltpu.VMEM((h, hkd), jnp.float32)]


def _decode_kernel(len_ref, q_ref, k_ref, v_ref, *rest, scale: float,
                   bs: int, n_s: int, rep: int, quantized: bool):
    if quantized:
        ks_ref, vs_ref, o_ref, m_ref, l_ref, acc_ref = rest
    else:
        ks_ref = vs_ref = None
        o_ref, m_ref, l_ref, acc_ref = rest
    b, ik = pl.program_id(0), pl.program_id(1)
    length = len_ref[b]

    @pl.when(ik == 0)
    def _init():
        init_state(m_ref, l_ref, acc_ref)

    @pl.when(ik * bs < length)         # tiles past the row's length: skip
    def _tile():
        attend_tile(q_ref, k_ref, v_ref, ks_ref, vs_ref, m_ref, l_ref,
                    acc_ref, length=length, kpos0=ik * bs, scale=scale,
                    rep=rep)

    @pl.when(ik == n_s - 1)
    def _flush():
        flush_state(o_ref, l_ref, acc_ref)


@functools.partial(jax.jit, static_argnames=("block_s", "interpret"))
def decode_attention_pallas(q, k_cache, v_cache, length, *, k_scale=None,
                            v_scale=None, block_s: int = 512,
                            interpret: bool = False):
    """q: [B, H, d]; caches: [B, S, Hk, d]; length: [B] -> [B, H, d]."""
    b, h, d = q.shape
    s, hk = k_cache.shape[1], k_cache.shape[2]
    quantized = k_scale is not None
    bs = min(block_s, s)
    if s % bs:
        # non-power-of-two cache lengths (e.g. S=768 with block 512): fall
        # back to the largest power-of-two block that divides S instead of
        # refusing the launch — worst case one block spanning all of S
        from repro.kernels.ops import _divisor_block
        bs = _divisor_block(s, bs)
    n_s = s // bs

    def kv_index(bi, ik, len_ref):
        # tiles past the row's length re-name the last live tile, so the
        # pipeline issues no DMA for them (their compute is skipped)
        last = jnp.maximum(len_ref[bi] - 1, 0) // bs
        return (bi, jnp.minimum(ik, last), 0)

    kv_spec = pl.BlockSpec((1, bs, hk * d), kv_index)
    in_specs = [pl.BlockSpec((1, h, hk * d), lambda bi, ik, len_ref:
                             (bi, 0, 0)), kv_spec, kv_spec]
    args = [expand_queries(q, hk), k_cache.reshape(b, s, hk * d),
            v_cache.reshape(b, s, hk * d)]
    if quantized:
        in_specs += [pl.BlockSpec((1, bs, hk), kv_index)] * 2
        args += [k_scale.reshape(b, s, hk), v_scale.reshape(b, s, hk)]

    out = pl.pallas_call(
        functools.partial(_decode_kernel, scale=1.0 / (d ** 0.5), bs=bs,
                          n_s=n_s, rep=h // hk, quantized=quantized),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,           # valid lengths in SMEM
            grid=(b, n_s),
            in_specs=in_specs,
            out_specs=pl.BlockSpec((1, h, hk * d),
                                   lambda bi, ik, len_ref: (bi, 0, 0)),
            scratch_shapes=scratch_shapes(h, hk * d)),
        out_shape=jax.ShapeDtypeStruct((b, h, hk * d), jnp.float32),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary")),
        interpret=interpret,
    )(length.astype(jnp.int32), *args)
    return own_heads(out, hk).astype(q.dtype)
