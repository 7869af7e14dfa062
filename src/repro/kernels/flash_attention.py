"""Pallas TPU kernel: causal flash attention with GQA.

Standard online-softmax tiling adapted to the TPU memory hierarchy: Q/K/V
tiles stream HBM->VMEM per BlockSpec; the running max/denominator/accumulator
live in VMEM scratch across the innermost KV grid dimension, so the S_q x S_k
score matrix never exists in HBM — the requirement for the 32k-prefill cells.

Grid: (B*H, Sq/bq, Sk/bk), KV innermost ("arbitrary"). GQA is handled in the
K/V index maps (query head h reads kv head h // (H/Hk)). Causally dead blocks
are masked to zero inside the kernel (a production TPU kernel would prune
them via a block-sparse index map; the masked form is kept for clarity and is
what the interpret-mode tests validate — the pruned variant is a recorded
§Perf candidate).

Masking note: fully-masked tiles make every score -1e30; the probability tile
is multiplied by the 0/1 validity mask, so the m == -1e30 corner cannot leak
exp(0) = 1 into the accumulator.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30


def _flash_kernel(q_ref, k_ref, v_ref, o_ref, m_ref, l_ref, acc_ref, *,
                  scale: float, causal: bool, sq: int, sk: int,
                  bq: int, bk: int, n_k: int):
    ik = pl.program_id(2)

    @pl.when(ik == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    q = q_ref[0].astype(jnp.float32)                    # [bq, d]
    k = k_ref[0].astype(jnp.float32)                    # [bk, d]
    v = v_ref[0].astype(jnp.float32)                    # [bk, d]

    s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                            preferred_element_type=jnp.float32) * scale

    iq = pl.program_id(1)
    qpos = iq * bq + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 0) \
        + (sk - sq)
    kpos = ik * bk + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 1)
    valid = kpos < sk
    if causal:
        valid = valid & (qpos >= kpos)
    vmask = valid.astype(jnp.float32)
    s = jnp.where(valid, s, NEG_INF)

    m_prev = m_ref[:, :1]                               # [bq, 1]
    l_prev = l_ref[:, :1]
    m_new = jnp.maximum(m_prev, s.max(axis=-1, keepdims=True))
    p = jnp.exp(s - m_new) * vmask                      # masked tiles -> 0
    corr = jnp.exp(m_prev - m_new)
    l_new = l_prev * corr + p.sum(axis=-1, keepdims=True)
    acc_ref[...] = acc_ref[...] * corr + jax.lax.dot(
        p, v, preferred_element_type=jnp.float32)
    m_ref[...] = jnp.broadcast_to(m_new, m_ref.shape)
    l_ref[...] = jnp.broadcast_to(l_new, l_ref.shape)

    @pl.when(ik == n_k - 1)
    def _flush():
        l = l_ref[:, :1]
        o_ref[0] = (acc_ref[...] / jnp.maximum(l, 1e-30)).astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("causal", "block_q", "block_k",
                                             "interpret"))
def flash_attention_pallas(q, k, v, *, causal: bool = True,
                           block_q: int = 256, block_k: int = 256,
                           interpret: bool = False):
    """q: [B, Sq, H, d]; k, v: [B, Sk, Hk, d] -> [B, Sq, H, d]."""
    b, sq, h, d = q.shape
    sk, hk = k.shape[1], k.shape[2]
    rep = h // hk
    scale = 1.0 / (d ** 0.5)

    bq = min(block_q, sq)
    bk = min(block_k, sk)
    if sq % bq or sk % bk:
        raise ValueError(f"seq ({sq},{sk}) not divisible by blocks ({bq},{bk})")
    n_q, n_k = sq // bq, sk // bk

    qf = q.transpose(0, 2, 1, 3).reshape(b * h, sq, d)
    kf = k.transpose(0, 2, 1, 3).reshape(b * hk, sk, d)
    vf = v.transpose(0, 2, 1, 3).reshape(b * hk, sk, d)

    def kv_index(bh, iq, ik):
        return ((bh // h) * hk + (bh % h) // rep, ik, 0)

    out = pl.pallas_call(
        functools.partial(_flash_kernel, scale=scale, causal=causal,
                          sq=sq, sk=sk, bq=bq, bk=bk, n_k=n_k),
        grid=(b * h, n_q, n_k),
        in_specs=[
            pl.BlockSpec((1, bq, d), lambda bh, iq, ik: (bh, iq, 0)),
            pl.BlockSpec((1, bk, d), kv_index),
            pl.BlockSpec((1, bk, d), kv_index),
        ],
        out_specs=pl.BlockSpec((1, bq, d), lambda bh, iq, ik: (bh, iq, 0)),
        out_shape=jax.ShapeDtypeStruct((b * h, sq, d), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((bq, 128), jnp.float32),   # running max (replicated)
            pltpu.VMEM((bq, 128), jnp.float32),   # running denom
            pltpu.VMEM((bq, d), jnp.float32),     # output accumulator
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret,
    )(qf, kf, vf)
    return out.reshape(b, h, sq, d).transpose(0, 2, 1, 3)
