"""Shared neural-net building blocks (pure functions over param pytrees).

No module framework in the container (no flax) — params are nested dicts of
arrays, initialized by `init_*` helpers and consumed by matching `*_fwd`
functions. Every weight matrix is stored [in, out] so the AxLLM serving
conversion (quantize_tree) and the sharding rules apply uniformly.
"""

from __future__ import annotations

import dataclasses
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.axllm_linear import linear
from repro.core.quantization import QTensor
from repro.dist.sharding import shard as _shard


def maybe_scan(body, carry, xs, use_scan: bool = True):
    """lax.scan or an unrolled python loop over the leading dim of `xs`.

    The unrolled form exists for the roofline aux lowering: XLA's HLO cost
    analysis counts a while-loop body once, so per-layer cost deltas are
    measured on 1-/2-group UNROLLED variants (launch/dryrun.run_aux)."""
    if use_scan:
        return jax.lax.scan(body, carry, xs)
    length = jax.tree_util.tree_leaves(xs)[0].shape[0]
    ys = []
    for i in range(length):
        x_i = jax.tree_util.tree_map(lambda a: a[i], xs)
        carry, y = body(carry, x_i)
        ys.append(y)
    if ys and ys[0] is not None:
        ys = jax.tree_util.tree_map(lambda *a: jnp.stack(a), *ys)
    else:
        ys = None
    return carry, ys


def layer_indices(n: int, use_scan: bool = True):
    """Each layer's index as :func:`maybe_scan` hands it to a step: an
    int32 scalar in the scan, a concrete index unrolled."""
    return jnp.arange(n, dtype=jnp.int32) if use_scan else np.arange(n)


def scan_layer_params(body, carry, layers, xs, use_scan: bool = True):
    """:func:`maybe_scan` of ``body(carry, (lp, *xs_i))`` over the stacked
    layer params ``layers`` and the per-layer tuple ``xs``, with every
    QTensor's codes left whole.

    Scanning a stacked QTensor would hand each layer a [K, N] slice of its
    codes, which XLA materializes before a Pallas call (it cannot pass part
    of a buffer to one). Here the codes are closed over instead, everything
    else is scanned, and ``lp`` carries QTensors with the whole codes and
    ``layer`` set to this layer's index (an int32 scalar in the scan, a
    concrete index unrolled); see ``ops.axllm_matmul``."""
    leaves, tree = jax.tree_util.tree_flatten(
        layers, is_leaf=lambda a: isinstance(a, QTensor))
    codes = [a.codes if isinstance(a, QTensor) else None for a in leaves]
    rest = [dataclasses.replace(a, codes=None) if isinstance(a, QTensor)
            else a for a in leaves]
    index = layer_indices(jax.tree_util.tree_leaves(layers)[0].shape[0],
                          use_scan)

    def step(c, inp):
        i, rest_i, *xs_i = inp
        lp = jax.tree_util.tree_unflatten(tree, [
            a if w is None else dataclasses.replace(a, codes=w, layer=i)
            for a, w in zip(rest_i, codes)])
        return body(c, (lp, *xs_i))

    return maybe_scan(step, carry, (index, rest, *xs), use_scan)


def truncated_normal(rng, shape, std, dtype=jnp.float32):
    return jax.random.truncated_normal(rng, -3.0, 3.0, shape, jnp.float32) \
        .astype(dtype) * std


def init_linear(rng, n_in, n_out, dtype=jnp.float32, std=None):
    std = std if std is not None else (1.0 / jnp.sqrt(n_in)).astype(jnp.float32)
    return truncated_normal(rng, (n_in, n_out), std, dtype)


# ---------------------------------------------------------------------------
# Norms
# ---------------------------------------------------------------------------

def init_norm(cfg, d=None):
    d = d or cfg.d_model
    if cfg.norm == "layernorm":
        return {"scale": jnp.ones((d,), jnp.float32),
                "bias": jnp.zeros((d,), jnp.float32)}
    return {"scale": jnp.ones((d,), jnp.float32)}


def norm_fwd(p, x, eps: float = 1e-5):
    xf = x.astype(jnp.float32)
    if "bias" in p:
        mu = xf.mean(-1, keepdims=True)
        var = ((xf - mu) ** 2).mean(-1, keepdims=True)
        y = (xf - mu) * jax.lax.rsqrt(var + eps)
        y = y * p["scale"] + p["bias"]
    else:
        var = (xf ** 2).mean(-1, keepdims=True)
        y = xf * jax.lax.rsqrt(var + eps) * p["scale"]
    return y.astype(x.dtype)


# ---------------------------------------------------------------------------
# MLP (SwiGLU / GELU)
# ---------------------------------------------------------------------------

def init_mlp(rng, cfg, d=None, d_ff=None, dtype=jnp.float32):
    d = d or cfg.d_model
    d_ff = d_ff or cfg.d_ff
    ks = jax.random.split(rng, 3)
    if cfg.act == "swiglu":
        return {"gate": init_linear(ks[0], d, d_ff, dtype),
                "up": init_linear(ks[1], d, d_ff, dtype),
                "down": init_linear(ks[2], d_ff, d, dtype)}
    return {"up": init_linear(ks[0], d, d_ff, dtype),
            "down": init_linear(ks[1], d_ff, d, dtype)}


def fuse_mlp_params(p):
    """Replace gate/up with one fused gate_up (``[d, 2·d_ff]``) — the MLP
    analogue of the fused-QKV projection. GELU MLPs (no gate) are returned
    unchanged; `mlp_fwd` dispatches on key presence."""
    if "gate_up" in p or "gate" not in p:
        return p
    from repro.core.axllm_linear import concat_weights
    p2 = {k: v for k, v in p.items() if k not in ("gate", "up")}
    p2["gate_up"] = concat_weights([p["gate"], p["up"]])
    return p2


def mlp_fwd(p, x, cfg, impl: str = "auto"):
    if "gate_up" in p:   # fused path: one activation pass over [d, 2·d_ff]
        gu = linear(x, p["gate_up"], impl=impl)
        g, u = jnp.split(gu, 2, axis=-1)
        h = jax.nn.silu(g) * u
    elif "gate" in p:
        h = jax.nn.silu(linear(x, p["gate"], impl=impl)) \
            * linear(x, p["up"], impl=impl)
    else:
        h = jax.nn.gelu(linear(x, p["up"], impl=impl))
    h = _shard(h, "batch", "seq", "mlp")
    return linear(h, p["down"], impl=impl, row_parallel=True)


# ---------------------------------------------------------------------------
# RoPE
# ---------------------------------------------------------------------------

def rope(x, positions, theta: float = 1e4):
    """x: [..., S, H, d]; positions: broadcastable [..., S]."""
    d = x.shape[-1]
    half = d // 2
    freqs = 1.0 / (theta ** (jnp.arange(0, half, dtype=jnp.float32) / half))
    angles = positions[..., None].astype(jnp.float32) * freqs  # [..., S, half]
    cos = jnp.cos(angles)[..., None, :]
    sin = jnp.sin(angles)[..., None, :]
    x1, x2 = x[..., :half], x[..., half:]
    out = jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)
    return out.astype(x.dtype)


# ---------------------------------------------------------------------------
# Embedding / head / loss
# ---------------------------------------------------------------------------

def init_embed(rng, cfg, dtype=jnp.float32):
    v, d = cfg.padded_vocab, cfg.d_model
    ks = jax.random.split(rng, 2)
    p = {"embedding": truncated_normal(ks[0], (v, d), 0.02, dtype)}
    if not cfg.tie_embeddings:
        p["lm_head"] = init_linear(ks[1], d, v, dtype)
    return p


def embed_fwd(p, tokens):
    return jnp.take(p["embedding"], tokens, axis=0)


def head_fwd(p, x, cfg, impl: str = "auto"):
    if cfg.tie_embeddings:
        w = p["embedding"]
        from repro.core.quantization import QTensor
        if isinstance(w, QTensor):
            from repro.core.quantization import dequantize
            w = dequantize(w, x.dtype)
        return jnp.dot(x, w.T.astype(x.dtype))
    return linear(x, p["lm_head"], impl=impl)


def cross_entropy(logits, targets, vocab_size: int):
    """Mean CE over all positions; ids >= vocab_size (padding) are masked in
    the normalizer (padded logit columns are trained toward -inf only via the
    softmax denominator, never as targets)."""
    lf = logits.astype(jnp.float32)
    padded_v = lf.shape[-1]
    if padded_v > vocab_size:
        # elementwise iota mask (partitionable along a sharded vocab dim;
        # a scatter here would force an all-gather under GSPMD)
        mask = jnp.arange(padded_v) >= vocab_size
        lf = jnp.where(mask, -1e30, lf)
    logz = jax.nn.logsumexp(lf, axis=-1)
    gold = jnp.take_along_axis(lf, targets[..., None], axis=-1)[..., 0]
    return (logz - gold).mean()
