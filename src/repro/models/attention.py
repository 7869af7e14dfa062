"""GQA attention block: full-sequence (train/prefill) and cached decode.

KV cache layout: {"k"/"v": [B, S_max, Hk, hd]} (+ "k_scale"/"v_scale"
[B, S_max, Hk, 1] when cfg.quant_kv — the int8-KV beyond-paper lever), plus
"pos": [B] write cursor. Stacked per-layer caches carry a leading L dim and
are scanned together with the stacked layer params.
"""

from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp

from repro.core.axllm_linear import concat_weights, linear, \
    lora_delta_batched
from repro.dist.sharding import shard
from repro.kernels import ops
from repro.models import layers as L


def init_attention(rng, cfg, dtype=jnp.float32):
    d, h, hk, hd = (cfg.d_model, cfg.n_heads, cfg.n_kv_heads,
                    cfg.resolved_head_dim)
    ks = jax.random.split(rng, 6)
    p = {
        "wq": L.init_linear(ks[0], d, h * hd, dtype),
        "wk": L.init_linear(ks[1], d, hk * hd, dtype),
        "wv": L.init_linear(ks[2], d, hk * hd, dtype),
        "wo": L.init_linear(ks[3], h * hd, d, dtype),
    }
    if cfg.qkv_bias:
        p["wq_bias"] = jnp.zeros((h * hd,), dtype)
        p["wk_bias"] = jnp.zeros((hk * hd,), dtype)
        p["wv_bias"] = jnp.zeros((hk * hd,), dtype)
    if cfg.qk_norm:
        p["q_norm"] = {"scale": jnp.ones((hd,), jnp.float32)}
        p["k_norm"] = {"scale": jnp.ones((hd,), jnp.float32)}
    return p


def fuse_attention_params(p):
    """Replace wq/wk/wv with one fused wqkv (``[d, (H+2Hk)·hd]``): one
    activation pass and one codebook residency per attention block instead
    of three (deploy-time transform; works on dense or deploy-quantized
    params, stacked-layer leading dims included). The unfused layout keeps
    working — `_project_qkv` dispatches on key presence."""
    if "wqkv" in p or "wq" not in p:
        return p
    p2 = {k: v for k, v in p.items()
          if k not in ("wq", "wk", "wv", "wq_bias", "wk_bias", "wv_bias")}
    p2["wqkv"] = concat_weights([p["wq"], p["wk"], p["wv"]])
    if "wq_bias" in p:
        p2["wqkv_bias"] = jnp.concatenate(
            [p["wq_bias"], p["wk_bias"], p["wv_bias"]], axis=-1)
    return p2


def _project_qkv(p, x, cfg, impl, adapters=None, adapter_idx=None,
                 lora_scaling: float = 1.0):
    """Project x -> (q, k, v) heads; fused wqkv or separate wq/wk/wv.

    ``adapters``/``adapter_idx`` enable the serve-path LoRA pipeline: the
    base matmul (dense or quantized, fused included) is untouched and each
    targeted projection adds its gathered per-row low-rank delta. On the
    fused path the wqkv output is split into its q/k/v column blocks
    first and each block receives its target's delta — elementwise
    identical to scattering a concatenated [dq ‖ dk ‖ dv] delta into the
    fused output's columns, so fused and unfused LoRA decode stay
    token-for-token equal (tests/test_adapters.py).
    """
    b, s, d = x.shape
    h, hk, hd = cfg.n_heads, cfg.n_kv_heads, cfg.resolved_head_dim
    if "wqkv" in p:  # fused path: one [d, (H+2Hk)·hd] AxLLM matmul
        qkv = linear(x, p["wqkv"], impl=impl)
        if "wqkv_bias" in p:
            qkv = qkv + p["wqkv_bias"].astype(qkv.dtype)
        q, k, v = jnp.split(qkv, (h * hd, (h + hk) * hd), axis=-1)
    else:
        q = linear(x, p["wq"], impl=impl)
        k = linear(x, p["wk"], impl=impl)
        v = linear(x, p["wv"], impl=impl)
        if cfg.qkv_bias:
            q = q + p["wq_bias"].astype(q.dtype)
            k = k + p["wk_bias"].astype(k.dtype)
            v = v + p["wv_bias"].astype(v.dtype)
    if adapters is not None:
        if "wq" in adapters:
            q = q + lora_delta_batched(x, adapters["wq"], adapter_idx,
                                       lora_scaling).astype(q.dtype)
        if "wk" in adapters:
            k = k + lora_delta_batched(x, adapters["wk"], adapter_idx,
                                       lora_scaling).astype(k.dtype)
        if "wv" in adapters:
            v = v + lora_delta_batched(x, adapters["wv"], adapter_idx,
                                       lora_scaling).astype(v.dtype)
    q = q.reshape(b, s, h, hd)
    k = k.reshape(b, s, hk, hd)
    v = v.reshape(b, s, hk, hd)
    if cfg.qk_norm:  # chameleon: per-head RMS norm on q/k
        q = L.norm_fwd(p["q_norm"], q, cfg.norm_eps)
        k = L.norm_fwd(p["k_norm"], k, cfg.norm_eps)
    return q, k, v


def init_cache(cfg, batch: int, max_len: int, dtype=jnp.bfloat16,
               n_layers: Optional[int] = None):
    """Stacked-over-layers KV cache (leading L dim matches layer scan)."""
    hk, hd = cfg.n_kv_heads, cfg.resolved_head_dim
    nl = n_layers if n_layers is not None else cfg.n_layers
    kv_dtype = jnp.int8 if cfg.quant_kv else dtype
    cache = {
        "k": jnp.zeros((nl, batch, max_len, hk, hd), kv_dtype),
        "v": jnp.zeros((nl, batch, max_len, hk, hd), kv_dtype),
        "pos": jnp.zeros((batch,), jnp.int32),
    }
    if cfg.quant_kv:
        cache["k_scale"] = jnp.zeros((nl, batch, max_len, hk, 1), jnp.float32)
        cache["v_scale"] = jnp.zeros((nl, batch, max_len, hk, 1), jnp.float32)
    return cache


def cache_spec(cfg):
    """Batch axis per cache leaf — the serve-engine slot-insertion contract.

    KV leaves are stacked over layers (leading L dim), so batch sits at
    axis 1; the per-row write cursor ``pos`` is batch-leading (axis 0).
    Must mirror :func:`init_cache` leaf-for-leaf (tested against shape
    inference in tests/test_serve.py).
    """
    spec = {"k": 1, "v": 1, "pos": 0}
    if cfg.quant_kv:
        spec["k_scale"] = 1
        spec["v_scale"] = 1
    return spec


def init_paged_cache(cfg, batch: int, n_blocks: int, block_size: int,
                     max_blocks: int, dtype=jnp.bfloat16,
                     n_layers: Optional[int] = None):
    """Block-paged KV cache: one shared pool + per-slot block tables.

    KV lives in ``n_blocks`` fixed-size blocks of ``block_size`` tokens in
    a pool shared by every slot; each slot's logical sequence is the
    concatenation of the blocks its row of ``block_tables`` names
    (position p -> block ``table[p // block]``, offset ``p % block``).
    Block 0 is the trash block: table entries past a row's allocation
    point there, and out-of-range writes are routed to it — nothing ever
    reads it (the length mask stops first). Ownership (free list,
    refcounts, prefix index) is host-side state in
    :class:`repro.serve.paged_cache.PagedKVCache`.
    """
    hk, hd = cfg.n_kv_heads, cfg.resolved_head_dim
    nl = n_layers if n_layers is not None else cfg.n_layers
    kv_dtype = jnp.int8 if cfg.quant_kv else dtype
    cache = {
        "k": jnp.zeros((nl, n_blocks, block_size, hk, hd), kv_dtype),
        "v": jnp.zeros((nl, n_blocks, block_size, hk, hd), kv_dtype),
        "pos": jnp.zeros((batch,), jnp.int32),
        "block_tables": jnp.zeros((batch, max_blocks), jnp.int32),
    }
    if cfg.quant_kv:
        cache["k_scale"] = jnp.zeros((nl, n_blocks, block_size, hk, 1),
                                     jnp.float32)
        cache["v_scale"] = jnp.zeros((nl, n_blocks, block_size, hk, 1),
                                     jnp.float32)
    return cache


def paged_cache_spec(cfg):
    """Paged variant of :func:`cache_spec`: pool leaves name their *block*
    axis (the allocation unit — there is no per-slot batch axis in the
    pool), while ``pos`` / ``block_tables`` stay slot-leading (axis 0).
    Mirrors :func:`init_paged_cache` leaf-for-leaf.
    """
    spec = {"k": 1, "v": 1, "pos": 0, "block_tables": 0}
    if cfg.quant_kv:
        spec["k_scale"] = 1
        spec["v_scale"] = 1
    return spec


def _quantize_kv(x):
    """Per-(pos, head) int8 quantization of new KV entries."""
    s = jnp.maximum(jnp.max(jnp.abs(x), axis=-1, keepdims=True), 1e-8) / 127.0
    codes = jnp.clip(jnp.round(x / s), -127, 127).astype(jnp.int8)
    return codes, s.astype(jnp.float32)


def attention_fwd(p, x, cfg, *, positions=None, impl: str = "auto"):
    """Full-sequence causal attention (train / prefill)."""
    b, s, _ = x.shape
    if positions is None:
        positions = jnp.broadcast_to(jnp.arange(s), (b, s))
    q, k, v = _project_qkv(p, x, cfg, impl)
    q = L.rope(q, positions, cfg.rope_theta)
    k = L.rope(k, positions, cfg.rope_theta)
    q = shard(q, "batch", "seq", "heads")
    k = shard(k, "batch", "seq", "kv_heads")
    out = ops.flash_attention(q, k, v, causal=True, impl=impl)
    out = out.reshape(b, s, -1)
    return linear(out, p["wo"], impl=impl, row_parallel=True)


def _wo_project(p, out, impl, adapters, adapter_idx, lora_scaling):
    """Output projection with an optional gathered LoRA delta on wo."""
    y = linear(out, p["wo"], impl=impl, row_parallel=True)
    if adapters is not None and "wo" in adapters:
        y = y + lora_delta_batched(out, adapters["wo"], adapter_idx,
                                   lora_scaling).astype(y.dtype)
    return y


def attention_prefill(p, x, cfg, layer_cache, *, impl: str = "auto",
                      adapters=None, adapter_idx=None,
                      lora_scaling: float = 1.0, prefix=None,
                      prefix_len=None):
    """Full-seq attention that also fills this layer's cache slice.

    layer_cache: {"k": [B, S_max, Hk, hd], ...} (no leading L — the scan
    slices it). Returns (out, updated_layer_cache).

    ``adapters``: this layer's stacked-adapter slice ``{target:
    {"lora_a": [max_loras, n_in, r], "lora_b": [max_loras, r, n_out]}}``;
    ``adapter_idx``: [B] int32 per-row adapter selection (-1 = base).

    ``prefix``/``prefix_len``: suffix-only prefill against a cached prompt
    head (the prefix-reuse path). ``prefix`` is this layer's gathered
    prefix KV ``{"k"/"v": [B, P, Hk, hd]}`` (int8 codes + ``k_scale``/
    ``v_scale`` [B, P, Hk, 1] when cfg.quant_kv), right-padded with
    per-row valid lengths ``prefix_len`` [B]. Rows are position-offset by
    their prefix length (RoPE and masking), queries attend the valid
    prefix plus the causal suffix, and only the suffix KV is written to
    ``layer_cache`` — the prefix already lives in the shared pool.
    """
    b, s, _ = x.shape
    if prefix is None:
        positions = jnp.broadcast_to(jnp.arange(s), (b, s))
    else:
        positions = prefix_len[:, None] + jnp.arange(s)[None, :]
    q, k, v = _project_qkv(p, x, cfg, impl, adapters, adapter_idx,
                           lora_scaling)
    q = L.rope(q, positions, cfg.rope_theta)
    k = L.rope(k, positions, cfg.rope_theta)
    if prefix is None:
        out = ops.flash_attention(q, k, v, causal=True, impl=impl)
    else:
        kp, vp = prefix["k"], prefix["v"]
        if cfg.quant_kv:      # pool holds int8 codes + per-position scales
            kp = kp.astype(jnp.float32) * prefix["k_scale"]
            vp = vp.astype(jnp.float32) * prefix["v_scale"]
        out = ops.prefix_attention(q, kp, vp, prefix_len, k, v, impl=impl)
    out = out.reshape(b, s, -1)
    new_cache = dict(layer_cache)
    if cfg.quant_kv:
        kq, ks = _quantize_kv(k)
        vq, vs = _quantize_kv(v)
        new_cache["k"] = jax.lax.dynamic_update_slice_in_dim(
            layer_cache["k"], kq, 0, axis=1)
        new_cache["v"] = jax.lax.dynamic_update_slice_in_dim(
            layer_cache["v"], vq, 0, axis=1)
        new_cache["k_scale"] = jax.lax.dynamic_update_slice_in_dim(
            layer_cache["k_scale"], ks, 0, axis=1)
        new_cache["v_scale"] = jax.lax.dynamic_update_slice_in_dim(
            layer_cache["v_scale"], vs, 0, axis=1)
    else:
        new_cache["k"] = jax.lax.dynamic_update_slice_in_dim(
            layer_cache["k"], k.astype(layer_cache["k"].dtype), 0, axis=1)
        new_cache["v"] = jax.lax.dynamic_update_slice_in_dim(
            layer_cache["v"], v.astype(layer_cache["v"].dtype), 0, axis=1)
    return _wo_project(p, out, impl, adapters, adapter_idx,
                       lora_scaling), new_cache


def _seq_shard_ctx(cfg, batch: int, cache_len: int):
    """If a mesh context is active and the cache's seq dim actually shards,
    return (mesh, seq_axes, batch_axes) for the fused shard_map decode."""
    from repro.dist import sharding as shd
    ctx = shd._current()
    if ctx is None:
        return None
    mesh, rules = ctx
    shape = (batch, cache_len, cfg.n_kv_heads, cfg.resolved_head_dim)
    spec = shd.resolve_spec(shape, ("batch", "cache_seq", "kv_heads", None),
                            mesh, rules)
    seq_entry = spec[1]
    if seq_entry is None:
        return None
    seq_axes = (seq_entry,) if isinstance(seq_entry, str) \
        else tuple(seq_entry)
    b_entry = spec[0]
    batch_axes = () if b_entry is None else (
        (b_entry,) if isinstance(b_entry, str) else tuple(b_entry))
    return mesh, seq_axes, batch_axes


def attention_decode_paged(p, x, cfg, layer_pool, pos, block_tables, *,
                           impl: str = "auto", adapters=None,
                           adapter_idx=None, lora_scaling: float = 1.0):
    """One-token decode through a block-paged KV pool.

    x: [B, 1, d]; pos: [B] current positions; layer_pool: this layer's
    pool slice ``{"k"/"v": [NB, bs, Hk, hd], ...}``; block_tables:
    [B, MB] int32. The new KV entry is written at
    ``(table[pos // bs], pos % bs)`` — the scheduler guarantees the
    written block is uniquely owned (copy-on-write resolves sharing
    before the chunk dispatches), and rows whose position ran past their
    table (stopped slots riding through a scan) are routed to trash
    block 0. Attention reads gather through the table in the paged
    flash-decode kernel. ``adapters``/``adapter_idx`` as in
    :func:`attention_prefill`.
    """
    b = x.shape[0]
    h, hk, hd = cfg.n_heads, cfg.n_kv_heads, cfg.resolved_head_dim
    bs = layer_pool["k"].shape[1]
    mb = block_tables.shape[1]
    q, k, v = _project_qkv(p, x, cfg, impl, adapters, adapter_idx,
                           lora_scaling)             # [B, 1, ...]
    q = L.rope(q, pos[:, None], cfg.rope_theta)
    k = L.rope(k, pos[:, None], cfg.rope_theta)

    bidx_row = jnp.arange(b)
    blk = pos // bs
    in_range = blk < mb
    bid = jnp.where(in_range,
                    block_tables[bidx_row, jnp.clip(blk, 0, mb - 1)], 0)
    off = jnp.where(in_range, pos % bs, 0)
    pool = dict(layer_pool)
    if cfg.quant_kv:
        kq, ksc = _quantize_kv(k)
        vq, vsc = _quantize_kv(v)
        pool["k"] = layer_pool["k"].at[bid, off].set(kq[:, 0])
        pool["v"] = layer_pool["v"].at[bid, off].set(vq[:, 0])
        pool["k_scale"] = layer_pool["k_scale"].at[bid, off].set(ksc[:, 0])
        pool["v_scale"] = layer_pool["v_scale"].at[bid, off].set(vsc[:, 0])
        out = ops.decode_attention(
            q[:, 0], pool["k"], pool["v"], pos + 1,
            k_scale=pool["k_scale"], v_scale=pool["v_scale"],
            block_tables=block_tables, impl=impl)
    else:
        pool["k"] = layer_pool["k"].at[bid, off].set(
            k[:, 0].astype(layer_pool["k"].dtype))
        pool["v"] = layer_pool["v"].at[bid, off].set(
            v[:, 0].astype(layer_pool["v"].dtype))
        out = ops.decode_attention(q[:, 0], pool["k"], pool["v"], pos + 1,
                                   block_tables=block_tables, impl=impl)
    out = out.reshape(b, 1, h * hd)
    return _wo_project(p, out, impl, adapters, adapter_idx,
                       lora_scaling), pool


def attention_decode(p, x, cfg, layer_cache, pos, *, impl: str = "auto",
                     adapters=None, adapter_idx=None,
                     lora_scaling: float = 1.0):
    """One-token decode. x: [B, 1, d]; pos: [B] current positions.

    ``adapters``/``adapter_idx`` as in :func:`attention_prefill` — the
    LoRA delta pipeline rides through the same cached-decode step.
    """
    b = x.shape[0]
    h, hk, hd = cfg.n_heads, cfg.n_kv_heads, cfg.resolved_head_dim
    q, k, v = _project_qkv(p, x, cfg, impl, adapters, adapter_idx,
                           lora_scaling)             # [B, 1, ...]
    q = L.rope(q, pos[:, None], cfg.rope_theta)
    k = L.rope(k, pos[:, None], cfg.rope_theta)

    ctx = _seq_shard_ctx(cfg, b, layer_cache["k"].shape[1])
    if ctx is not None:
        # seq-sharded cache: fused local update + flash combine (avoids the
        # GSPMD cache all-gather — §Perf decode lever)
        from repro.kernels import sharded_decode as SD
        mesh, seq_axes, batch_axes = ctx
        cache = dict(layer_cache)
        if cfg.quant_kv:
            kq, ksc = _quantize_kv(k)
            vq, vsc = _quantize_kv(v)
            out, cache["k"], cache["v"], cache["k_scale"], cache["v_scale"] \
                = SD.decode_attention_seqsharded(
                    q[:, 0], layer_cache["k"], layer_cache["v"],
                    kq[:, 0], vq[:, 0], pos, pos + 1, mesh, seq_axes,
                    batch_axes, k_scale=layer_cache["k_scale"],
                    v_scale=layer_cache["v_scale"],
                    new_k_scale=ksc[:, 0], new_v_scale=vsc[:, 0])
        else:
            out, cache["k"], cache["v"] = SD.decode_attention_seqsharded(
                q[:, 0], layer_cache["k"], layer_cache["v"],
                k[:, 0], v[:, 0], pos, pos + 1, mesh, seq_axes, batch_axes)
        out = out.reshape(b, 1, h * hd)
        return _wo_project(p, out, impl, adapters, adapter_idx,
                           lora_scaling), cache

    cache = dict(layer_cache)
    bidx = jnp.arange(b)
    if cfg.quant_kv:
        kq, ksc = _quantize_kv(k)
        vq, vsc = _quantize_kv(v)
        cache["k"] = layer_cache["k"].at[bidx, pos].set(kq[:, 0])
        cache["v"] = layer_cache["v"].at[bidx, pos].set(vq[:, 0])
        cache["k_scale"] = layer_cache["k_scale"].at[bidx, pos].set(ksc[:, 0])
        cache["v_scale"] = layer_cache["v_scale"].at[bidx, pos].set(vsc[:, 0])
        out = ops.decode_attention(
            q[:, 0], cache["k"], cache["v"], pos + 1,
            k_scale=cache["k_scale"], v_scale=cache["v_scale"], impl=impl)
    else:
        cache["k"] = layer_cache["k"].at[bidx, pos].set(
            k[:, 0].astype(layer_cache["k"].dtype))
        cache["v"] = layer_cache["v"].at[bidx, pos].set(
            v[:, 0].astype(layer_cache["v"].dtype))
        out = ops.decode_attention(q[:, 0], cache["k"], cache["v"], pos + 1,
                                   impl=impl)
    out = out.reshape(b, 1, h * hd)
    return _wo_project(p, out, impl, adapters, adapter_idx,
                       lora_scaling), cache
