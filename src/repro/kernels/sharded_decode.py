"""Sequence-sharded decode attention (shard_map): fused cache-update +
flash-decode with cross-shard softmax combine.

Why: long-context decode shards the KV cache's *sequence* dim over "model"
(kv_heads are too few to shard — glm4 has 2). Under plain pjit, the
per-token cache update is a scatter into a sharded dim at a traced index, and
GSPMD's fallback is to ALL-GATHER the cache (measured: 537 MB/layer/token on
glm4-9b:decode_32k — the dominant collective, §Perf hillclimb). This module
makes the distributed structure explicit:

  * every "model" shard owns seq rows [lo, hi); the new token's K/V is
    written LOCALLY by the owning shard (a where-masked scatter — zero
    communication);
  * each shard computes a partial flash-decode (m, l, acc) over its rows;
  * the combine is the flash-decode reduction: m* = pmax(m),
    l* = psum(l·e^{m-m*}), acc* = psum(acc·e^{m-m*}) — communication is
    O(B·H·d) per layer instead of O(B·S·Hk·d).

Works for bf16 and int8-quantized caches (scales ride along).
"""

from __future__ import annotations

import functools
from typing import Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
from jax import shard_map
from jax.sharding import Mesh, PartitionSpec as P

NEG_INF = -1e30


def _local_update(cache, new_val, pos, lo, s_local):
    """Write new_val [B, Hk, d] at seq position pos[b]-lo when owned."""
    b = cache.shape[0]
    local_pos = pos - lo
    in_range = (local_pos >= 0) & (local_pos < s_local)
    idx = jnp.clip(local_pos, 0, s_local - 1)
    bidx = jnp.arange(b)
    old = cache[bidx, idx]                                   # [B, Hk, d]
    val = jnp.where(in_range[:, None, None], new_val.astype(cache.dtype),
                    old)
    return cache.at[bidx, idx].set(val)


def _partial_attend(q, k, v, k_scale, v_scale, lo, length, scale):
    """Local flash-decode over this shard's rows.

    q: [B, H, d]; k/v: [B, S_loc, Hk, d]; returns (m, l, acc) partials."""
    b, h, d = q.shape
    s_loc, hk = k.shape[1], k.shape[2]
    rep = h // hk
    kf = k.astype(jnp.float32)
    vf = v.astype(jnp.float32)
    if k_scale is not None:
        kf = kf * k_scale
        vf = vf * v_scale
    # [B, S, Hk, d] -> [B, S, H, d]
    kf = jnp.repeat(kf, rep, axis=2)
    vf = jnp.repeat(vf, rep, axis=2)
    s = jnp.einsum("bhd,bshd->bhs", q.astype(jnp.float32), kf) * scale
    kpos = lo + jnp.arange(s_loc)
    valid = kpos[None, None, :] < length[:, None, None]
    s = jnp.where(valid, s, NEG_INF)
    m = s.max(axis=-1)                                       # [B, H]
    p = jnp.exp(s - m[..., None]) * valid.astype(jnp.float32)
    l = p.sum(axis=-1)
    acc = jnp.einsum("bhs,bshd->bhd", p, vf)
    return m, l, acc


def decode_attention_seqsharded(q, k_cache, v_cache, new_k, new_v, pos,
                                length, mesh: Mesh,
                                seq_axes: Tuple[str, ...],
                                batch_axes: Tuple[str, ...],
                                k_scale=None, v_scale=None,
                                new_k_scale=None, new_v_scale=None):
    """Fused update+attend. Shapes (global):
      q, new_k, new_v: [B, H|Hk, d]; caches: [B, S, Hk, d]; pos/length: [B].
    Returns (out [B, H, d], k_cache', v_cache', k_scale', v_scale')."""
    b, s = k_cache.shape[0], k_cache.shape[1]
    d = q.shape[-1]
    n_seq = 1
    for ax in seq_axes:
        n_seq *= mesh.shape[ax]
    s_local = s // n_seq
    quantized = k_scale is not None
    seq_spec = seq_axes[0] if len(seq_axes) == 1 else tuple(seq_axes)
    bspec = batch_axes[0] if len(batch_axes) == 1 else \
        (tuple(batch_axes) if batch_axes else None)

    cache_p = P(bspec, seq_spec, None, None)
    scale_p = P(bspec, seq_spec, None, None)
    vec_p = P(bspec, None, None)
    s1_p = P(bspec)

    in_specs = [vec_p, cache_p, cache_p, vec_p, vec_p, s1_p, s1_p]
    out_specs = [vec_p, cache_p, cache_p]
    args = [q, k_cache, v_cache, new_k, new_v, pos, length]
    if quantized:
        in_specs += [scale_p, scale_p, vec_p, vec_p]
        out_specs += [scale_p, scale_p]
        args += [k_scale, v_scale, new_k_scale, new_v_scale]

    axis_for_index = seq_axes

    def body(q_l, k_l, v_l, nk, nv, pos_l, len_l, *rest):
        # shard index along the (possibly compound) seq axes
        idx = 0
        for ax in axis_for_index:
            idx = idx * mesh.shape[ax] + jax.lax.axis_index(ax)
        lo = idx * s_local
        if quantized:
            ks_l, vs_l, nks, nvs = rest
            k_l2 = _local_update(k_l, nk, pos_l, lo, s_local)
            v_l2 = _local_update(v_l, nv, pos_l, lo, s_local)
            ks2 = _local_update(ks_l, nks, pos_l, lo, s_local)
            vs2 = _local_update(vs_l, nvs, pos_l, lo, s_local)
            m, l, acc = _partial_attend(q_l, k_l2, v_l2, ks2, vs2, lo,
                                        len_l, 1.0 / (d ** 0.5))
        else:
            k_l2 = _local_update(k_l, nk, pos_l, lo, s_local)
            v_l2 = _local_update(v_l, nv, pos_l, lo, s_local)
            m, l, acc = _partial_attend(q_l, k_l2, v_l2, None, None, lo,
                                        len_l, 1.0 / (d ** 0.5))
        # cross-shard flash combine over the seq axes
        for ax in axis_for_index:
            m_g = jax.lax.pmax(m, ax)
            corr = jnp.exp(m - m_g)
            l = jax.lax.psum(l * corr, ax)
            acc = jax.lax.psum(acc * corr[..., None], ax)
            m = m_g
        out = (acc / jnp.maximum(l, 1e-30)[..., None]).astype(q_l.dtype)
        if quantized:
            return out, k_l2, v_l2, ks2, vs2
        return out, k_l2, v_l2

    fn = shard_map(body, mesh=mesh, in_specs=tuple(in_specs),
                   out_specs=tuple(out_specs), check_vma=False)
    return fn(*args)
