"""Seeded model weights, drawn on the device, in AxLLM int8 weight codes.

Every weight matrix ``[n_in, n_out]`` is drawn as ``N(0, 1/n_in)`` in
float32 and stored as per-output-channel symmetric codes:
``w = codes / qmax * absmax`` with ``absmax`` the column's largest
magnitude (``qmax`` 127 for int8, 7 for int4). That is the deployed form
of a post-training-quantized checkpoint; the embedding stays bfloat16.

This module imports nothing of the program under test: the harness packs
its arrays into the program's parameter layout, and the reference draws
them again from the same seed, layer by layer.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

# tensor order inside a layer key; a new tensor appends, never reorders
MATRICES = ("wq", "wk", "wv", "wo", "gate", "up", "down")
BIASES = ("wq_bias", "wk_bias", "wv_bias")


def base_key(seed: int):
    """PRNG key for any seed in [0, 2**64): both 32-bit halves are folded
    in, so seeds past 2**31 neither overflow nor collide."""
    seed = int(seed)
    if not 0 <= seed < 2 ** 64:
        raise ValueError(f"seed must be in [0, 2**64), got {seed}")
    k = jax.random.PRNGKey(0)
    k = jax.random.fold_in(k, np.uint32(seed >> 32))
    return jax.random.fold_in(k, np.uint32(seed & 0xFFFFFFFF))


def shapes(m: dict) -> dict:
    """Matrix shapes ``[n_in, n_out]`` of one layer from a config's sizes."""
    d, hd = m["hidden_size"], m["head_dim"]
    h, hk, ff = (m["num_attention_heads"], m["num_key_value_heads"],
                 m["intermediate_size"])
    return {"wq": (d, h * hd), "wk": (d, hk * hd), "wv": (d, hk * hd),
            "wo": (h * hd, d), "gate": (d, ff), "up": (d, ff),
            "down": (ff, d)}


def padded_vocab(m: dict) -> int:
    mult = m["vocab_pad_multiple"]
    return -(-m["vocab_size"] // mult) * mult


def codes_of(key, n_in: int, n_out: int, bits: int):
    """(codes int8 [n_in, n_out], absmax f32 [1, n_out])."""
    qmax = (1 << (bits - 1)) - 1
    w = jax.random.normal(key, (n_in, n_out), jnp.float32) * np.float32(
        1.0 / np.sqrt(n_in))
    absmax = jnp.maximum(jnp.max(jnp.abs(w), axis=0, keepdims=True), 1e-8)
    codes = jnp.clip(jnp.round(w / absmax * qmax), -qmax, qmax)
    return codes.astype(jnp.int8), absmax


def draw_layer(key, m: dict, bits: int = 8) -> dict:
    """One decoder layer: ``{name: (codes, absmax)}`` for the matrices,
    plus norm scales (f32, ``1 + 0.1·N(0,1)``) and, where the config has
    them, QKV biases (bf16, ``0.1·N(0,1)``)."""
    out = {}
    for i, (name, (n_in, n_out)) in enumerate(shapes(m).items()):
        out[name] = codes_of(jax.random.fold_in(key, i), n_in, n_out, bits)
    d = m["hidden_size"]
    for j, name in enumerate(("ln1", "ln2")):
        out[name] = 1.0 + 0.1 * jax.random.normal(
            jax.random.fold_in(key, 100 + j), (d,), jnp.float32)
    if m["attention_bias"]:
        sh = shapes(m)
        for j, name in enumerate(BIASES):
            n = sh[name[:2]][1]
            out[name] = (0.1 * jax.random.normal(
                jax.random.fold_in(key, 200 + j), (n,), jnp.float32)
            ).astype(jnp.bfloat16)
    return out


def layer_key(seed_key, i):
    return jax.random.fold_in(jax.random.fold_in(seed_key, 1), i)


# column (row) blocks the head (embedding) is drawn in: bounds the float32
# temporaries to a sixteenth of the vocabulary
HEAD_BLOCKS = 16


def draw_head(seed_key, m: dict, bits: int = 8):
    """lm_head ``[d, V_padded]`` codes and absmax, drawn in column blocks."""
    d, vp, nb = m["hidden_size"], padded_vocab(m), HEAD_BLOCKS
    cb = vp // nb
    keys = jax.random.split(jax.random.fold_in(seed_key, 2), nb)
    codes, absmax = jax.lax.map(lambda k: codes_of(k, d, cb, bits), keys)
    # [nb, d, cb] -> [d, nb*cb]
    return (jnp.moveaxis(codes, 0, 1).reshape(d, vp),
            jnp.moveaxis(absmax, 0, 1).reshape(1, vp))


def draw_embedding(seed_key, m: dict):
    """Embedding ``[V_padded, d]`` bf16, ``0.02·N(0,1)``, in row blocks."""
    d, vp, nb = m["hidden_size"], padded_vocab(m), HEAD_BLOCKS
    keys = jax.random.split(jax.random.fold_in(seed_key, 3), nb)
    rows = jax.lax.map(
        lambda k: (0.02 * jax.random.normal(k, (vp // nb, d), jnp.float32)
                   ).astype(jnp.bfloat16), keys)
    return rows.reshape(vp, d)


def draw_final_norm(seed_key, m: dict):
    return 1.0 + 0.1 * jax.random.normal(
        jax.random.fold_in(seed_key, 4), (m["hidden_size"],), jnp.float32)


def draw_all(seed_key, m: dict, bits: int = 8) -> dict:
    """Every weight of the model; layers stacked on a leading axis.

    Runs as one program: the layers are drawn one at a time inside a
    ``lax.map``, so the float32 draw of one layer is the largest
    temporary, whatever the depth."""
    idx = jnp.arange(m["num_hidden_layers"])
    layers = jax.lax.map(
        lambda i: draw_layer(layer_key(seed_key, i), m, bits), idx)
    return {"layers": layers, "head": draw_head(seed_key, m, bits),
            "embedding": draw_embedding(seed_key, m),
            "final_norm": draw_final_norm(seed_key, m)}


def dequant(codes, absmax, bits: int = 8):
    """float32 weight from codes and per-channel absmax."""
    qmax = (1 << (bits - 1)) - 1
    return codes.astype(jnp.float32) / qmax * absmax
