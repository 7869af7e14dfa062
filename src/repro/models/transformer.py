"""Unified decoder-only transformer LM (dense / GQA / MoE / VLM-backbone).

Covers chameleon-34b (qk-norm, early-fusion vocab), arctic-480b and
qwen2-moe-a2.7b (MoE FFN variants), internlm2-20b, qwen2-72b (QKV bias),
granite-3-8b, glm4-9b. Layers are stacked and scanned (HLO size O(1) in
depth; remat per layer); prefill/decode thread the stacked KV cache through
the same scan.
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp

from repro.dist.sharding import shard
from repro.models import attention as A
from repro.models import layers as L
from repro.models import moe as M


def _param_dtype(cfg):
    return jnp.bfloat16 if cfg.dtype == "bfloat16" else jnp.float32


def init_layer(rng, cfg, dtype):
    k1, k2 = jax.random.split(rng)
    p = {
        "ln1": L.init_norm(cfg),
        "attn": A.init_attention(k1, cfg, dtype),
        "ln2": L.init_norm(cfg),
    }
    if cfg.family == "moe":
        p["ffn"] = M.init_moe(k2, cfg, dtype)
    else:
        p["ffn"] = L.init_mlp(k2, cfg, dtype=dtype)
    return p


def init_params(rng, cfg):
    dtype = _param_dtype(cfg)
    ke, kl = jax.random.split(rng)
    layer_keys = jax.random.split(kl, cfg.n_layers)
    layers = jax.vmap(lambda k: init_layer(k, cfg, dtype))(layer_keys)
    return {
        "embed": L.init_embed(ke, cfg, dtype),
        "layers": layers,
        "final_norm": L.init_norm(cfg),
    }


def fuse_params(params, cfg):
    """Deploy-time fused-projection rewrite (cfg.fuse_qkv): wq/wk/wv ->
    wqkv and gate/up -> gate_up across the stacked layers. MoE routed
    experts keep their einsum layout (only the shared/dense mlp_fwd paths
    fuse); apply AFTER deploy_quantize so QTensors concat exactly."""
    layers = dict(params["layers"])
    layers["attn"] = A.fuse_attention_params(layers["attn"])
    ffn = dict(layers["ffn"])
    if cfg.family == "moe":
        for key in ("shared", "dense"):
            if key in ffn:
                ffn[key] = L.fuse_mlp_params(ffn[key])
    else:
        ffn = L.fuse_mlp_params(ffn)
    layers["ffn"] = ffn
    return {**params, "layers": layers}


def _ffn_fwd(p, x, cfg, impl):
    if cfg.family == "moe":
        return M.moe_ffn(p, x, cfg, impl=impl)
    return L.mlp_fwd(p, x, cfg, impl=impl)


def _layer_fwd(lp, x, cfg, impl):
    h = L.norm_fwd(lp["ln1"], x, cfg.norm_eps)
    x = x + A.attention_fwd(lp["attn"], h, cfg, impl=impl)
    x = shard(x, "batch", "seq")
    h = L.norm_fwd(lp["ln2"], x, cfg.norm_eps)
    x = x + _ffn_fwd(lp["ffn"], h, cfg, impl)
    return shard(x, "batch", "seq")


def forward(params, tokens, cfg, impl: str = "auto"):
    """tokens: [B, S] -> logits [B, S, V_padded]."""
    x = L.embed_fwd(params["embed"], tokens).astype(_param_dtype(cfg))
    x = shard(x, "batch", "seq")

    def body(carry, lp):
        return _layer_fwd(lp, carry, cfg, impl), None

    if cfg.remat:
        body = jax.checkpoint(body, prevent_cse=False)
    if cfg.scan_layers:
        x, _ = jax.lax.scan(body, x, params["layers"])
    else:
        for i in range(cfg.n_layers):
            lp = jax.tree_util.tree_map(lambda a: a[i], params["layers"])
            x, _ = body(x, lp)
    x = L.norm_fwd(params["final_norm"], x, cfg.norm_eps)
    logits = L.head_fwd(params["embed"], x, cfg, impl=impl)
    return shard(logits, "batch", "seq", "vocab")


def loss_fn(params, batch, cfg, impl: str = "auto"):
    logits = forward(params, batch["tokens"], cfg, impl=impl)
    return L.cross_entropy(logits, batch["targets"], cfg.vocab_size)


# ---------------------------------------------------------------------------
# Serving: prefill + decode through the same layer scan
# ---------------------------------------------------------------------------

def init_cache(cfg, batch: int, max_len: int, dtype=None):
    dtype = dtype or (jnp.bfloat16 if cfg.dtype == "bfloat16"
                      else jnp.float32)
    return A.init_cache(cfg, batch, max_len, dtype)


def cache_spec(cfg):
    """Batch axis per cache leaf (see attention.cache_spec)."""
    return A.cache_spec(cfg)


def init_paged_cache(cfg, batch: int, n_blocks: int, block_size: int,
                     max_blocks: int, dtype=None):
    """Block-paged KV pool + per-slot tables (attention.init_paged_cache)."""
    dtype = dtype or (jnp.bfloat16 if cfg.dtype == "bfloat16"
                      else jnp.float32)
    return A.init_paged_cache(cfg, batch, n_blocks, block_size, max_blocks,
                              dtype)


def paged_cache_spec(cfg):
    """Block/slot axis per paged-cache leaf (attention.paged_cache_spec)."""
    return A.paged_cache_spec(cfg)


# host-side per-slot leaves excluded from the layer scan's xs
_SLOT_LEAVES = ("pos", "block_tables")


def _cache_xs(cache):
    return {k: v for k, v in cache.items() if k not in _SLOT_LEAVES}


def prefill(params, tokens, cfg, cache, impl: str = "auto", lengths=None,
            adapters=None, adapter_idx=None, lora_scaling: float = 1.0,
            prefix=None):
    """tokens: [B, S] -> (last-position logits [B, V], filled cache).

    With `lengths` ([B] int32, ragged right-padded prompts), logits are
    gathered at each row's final real position and the cache cursor is set
    to `lengths`. Causal masking keeps real tokens from attending to the
    padding (pads sit *after* them); pad-position KV entries are garbage
    but live beyond the per-row cursor, so decode's length mask never
    reads them and subsequent writes overwrite them in place.

    ``adapters`` ({target: {"lora_a": [n_layers, max_loras, n_in, r],
    "lora_b": [n_layers, max_loras, r, n_out]}}) and ``adapter_idx``
    ([B] int32, -1 = base-only) enable the multi-LoRA delta pipeline:
    the stacked per-layer adapter slices scan together with the layer
    params, and each attention block adds its gathered per-row delta.

    ``prefix`` (requires ``lengths``) makes this a *suffix-only* prefill
    against an already-cached prompt head: ``{"k"/"v":
    [L, B, P, Hk, hd]`` (+ ``k_scale``/``v_scale`` when cfg.quant_kv),
    ``"len": [B]}``. ``tokens``/``lengths`` then describe only the
    un-cached tail; every row is position-offset by its prefix length and
    the cursor lands at ``prefix_len + lengths``. The returned cache
    holds suffix KV only — the prefix stays wherever it was cached.
    """
    b, s = tokens.shape
    x = L.embed_fwd(params["embed"], tokens).astype(_param_dtype(cfg))
    prefix_len = None
    prefix_kv = None
    if prefix is not None:
        if lengths is None:
            raise ValueError("prefix-reuse prefill needs per-row lengths")
        prefix_len = jnp.asarray(prefix["len"], jnp.int32)
        prefix_kv = {k: v for k, v in prefix.items() if k != "len"}

    def body(carry, inp):
        inp = list(inp)
        lp, lc = inp[0], inp[1]
        pf = inp[2] if prefix_kv is not None else None
        ad = inp[-1] if adapters is not None else None
        h = L.norm_fwd(lp["ln1"], carry, cfg.norm_eps)
        att, new_lc = A.attention_prefill(
            lp["attn"], h, cfg, lc, impl=impl, adapters=ad,
            adapter_idx=adapter_idx, lora_scaling=lora_scaling,
            prefix=pf, prefix_len=prefix_len)
        x1 = carry + att
        h2 = L.norm_fwd(lp["ln2"], x1, cfg.norm_eps)
        x2 = x1 + _ffn_fwd(lp["ffn"], h2, cfg, impl)
        return shard(x2, "batch", "seq"), new_lc

    xs = (_cache_xs(cache),)
    if prefix_kv is not None:
        xs = xs + (prefix_kv,)
    if adapters is not None:
        xs = xs + (adapters,)
    x, new_kv = L.scan_layer_params(body, x, params["layers"], xs,
                                    cfg.scan_layers)
    if lengths is None:
        x = x[:, -1:]
        pos = jnp.full((b,), s, jnp.int32)
    else:
        pos = jnp.asarray(lengths, jnp.int32)
        x = x[jnp.arange(b), pos - 1][:, None]
    x = L.norm_fwd(params["final_norm"], x, cfg.norm_eps)
    logits = L.head_fwd(params["embed"], x, cfg, impl=impl)[:, 0]
    new_cache = dict(new_kv)
    new_cache["pos"] = pos if prefix_len is None else pos + prefix_len
    return logits, new_cache


def decode_step(params, token, cfg, cache, impl: str = "auto",
                adapters=None, adapter_idx=None, lora_scaling: float = 1.0):
    """token: [B] int32 -> (logits [B, V], cache advanced by one).

    ``adapters``/``adapter_idx``/``lora_scaling`` as in :func:`prefill` —
    the same stacked-adapter slices scan with the layers so a mixed batch
    of base and N distinct adapters decodes in one dispatch.

    A cache carrying ``block_tables`` (built by :func:`init_paged_cache`)
    routes every layer through the block-paged decode path: the stacked
    pool rides through the layer scan in the carry, each layer's KV write
    lands in place at ``(layer, table[pos // bs], pos % bs)``, and
    attention reads the layer's blocks straight out of the stack through
    the table (``ops.decode_attention(block_tables=, layer=)``), so no
    layer's pool is sliced out, relaid or written back.
    """
    pos = cache["pos"]
    block_tables = cache.get("block_tables")
    paged = block_tables is not None
    x = L.embed_fwd(params["embed"], token[:, None]).astype(_param_dtype(cfg))

    def body(carry, inp):
        # paged, the pool rides in the carry and lc is this layer's index
        # into it; dense, lc is this layer's cache slice
        x, pool = carry
        lp, lc, *ad = inp
        ad = ad[0] if ad else None
        h = L.norm_fwd(lp["ln1"], x, cfg.norm_eps)
        if paged:
            att, pool = A.attention_decode_paged(
                lp["attn"], h, cfg, pool, lc, pos, block_tables, impl=impl,
                adapters=ad, adapter_idx=adapter_idx,
                lora_scaling=lora_scaling)
        else:
            att, lc = A.attention_decode(
                lp["attn"], h, cfg, lc, pos, impl=impl, adapters=ad,
                adapter_idx=adapter_idx, lora_scaling=lora_scaling)
        x1 = x + att
        h2 = L.norm_fwd(lp["ln2"], x1, cfg.norm_eps)
        x2 = x1 + _ffn_fwd(lp["ffn"], h2, cfg, impl)
        return (x2, pool), None if paged else lc

    if paged:
        pool = _cache_xs(cache)
        xs = (L.layer_indices(cache["k"].shape[0], cfg.scan_layers),)
    else:
        pool, xs = None, (_cache_xs(cache),)
    if adapters is not None:
        xs = xs + (adapters,)
    (x, pool), ys = L.scan_layer_params(body, (x, pool), params["layers"],
                                        xs, cfg.scan_layers)
    new_kv = pool if paged else ys
    x = L.norm_fwd(params["final_norm"], x, cfg.norm_eps)
    logits = L.head_fwd(params["embed"], x, cfg, impl=impl)[:, 0]
    new_cache = dict(new_kv)
    new_cache["pos"] = pos + 1
    if paged:
        new_cache["block_tables"] = block_tables
    return logits, new_cache
