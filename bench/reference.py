"""Plain float32 reference of the served model, and the served-token check.

The reference imports nothing of the program under test. It draws the
weights again from the seed (``bench.weights``), dequantizes each layer's
codes to float32, and runs the decoder in ``jax.numpy`` at HIGHEST matmul
precision, one layer at a time over every sampled sequence, attention in
blocks of queries. It follows the configuration's equations: RMSNorm,
rotate-half RoPE over the whole head, grouped-query causal attention with
optional QKV bias, SwiGLU, an untied int8 head over the real vocabulary.

``bits=4`` gives the control: the same weights requantized to int4 codes
per channel, the precision one step below the configuration's int8.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

from bench import weights

Q_BLOCK = 256          # queries per attention block
LEN_BUCKET = 1024      # sequences are padded to a multiple of this


def rms(x, scale, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * scale


def rope(x, theta):
    """x: [S, heads, d] at positions 0..S-1, rotate-half form."""
    s, _, d = x.shape
    half = d // 2
    freqs = 1.0 / (theta ** (jnp.arange(half, dtype=jnp.float32) / half))
    ang = jnp.arange(s, dtype=jnp.float32)[:, None] * freqs
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def attention(q, k, v):
    """Causal GQA. q: [S, H, d]; k, v: [S, Hk, d] -> [S, H*d]."""
    s, h, d = q.shape
    hk = k.shape[1]
    qb = q.reshape(s // Q_BLOCK, Q_BLOCK, hk, h // hk, d)
    kpos = jnp.arange(s)

    def block(args):
        i, qi = args
        sc = jnp.einsum("qgrd,kgd->grqk", qi, k) / np.sqrt(d)
        qpos = i * Q_BLOCK + jnp.arange(Q_BLOCK)
        sc = jnp.where(kpos[None, None, None, :] <= qpos[None, None, :, None],
                       sc, -jnp.inf)
        p = jax.nn.softmax(sc, axis=-1)
        return jnp.einsum("grqk,kgd->qgrd", p, v).reshape(Q_BLOCK, h * d)

    out = jax.lax.map(block, (jnp.arange(s // Q_BLOCK), qb))
    return out.reshape(s, h * d)


@functools.partial(jax.jit, static_argnames=("m",))
def layer(x, w, m):
    """One decoder layer in float32. x: [S, d]; w: float32 weights."""
    eps, theta = m["rms_norm_eps"], m["rope_theta"]
    h, hk, hd = (m["num_attention_heads"], m["num_key_value_heads"],
                 m["head_dim"])
    s = x.shape[0]
    a = rms(x, w["ln1"], eps)
    q, k, v = a @ w["wq"], a @ w["wk"], a @ w["wv"]
    if "wq_bias" in w:
        q, k, v = q + w["wq_bias"], k + w["wk_bias"], v + w["wv_bias"]
    q = rope(q.reshape(s, h, hd), theta)
    k = rope(k.reshape(s, hk, hd), theta)
    x = x + attention(q, k, v.reshape(s, hk, hd)) @ w["wo"]
    a = rms(x, w["ln2"], eps)
    return x + (jax.nn.silu(a @ w["gate"]) * (a @ w["up"])) @ w["down"]


@functools.partial(jax.jit, static_argnames=("m", "bits"))
def layer_weights(seed_key, i, m, bits):
    lw = weights.draw_layer(weights.layer_key(seed_key, i), m, bits)
    out = {}
    for name, val in lw.items():
        if name in weights.MATRICES:
            out[name] = weights.dequant(*val, bits)
        else:
            out[name] = val.astype(jnp.float32)
    return out


@functools.partial(jax.jit, static_argnames=("m", "bits"))
def head_logits(seed_key, xf, m, bits):
    """Logits over the real vocabulary for final hidden states xf [n, d]."""
    codes, absmax = weights.draw_head(seed_key, m, bits)
    v = m["vocab_size"]
    return xf @ weights.dequant(codes[:, :v], absmax[:, :v], bits)


def frozen(m: dict):
    """A hashable view of a configuration's sizes, for static arguments."""
    keys = ("num_hidden_layers", "hidden_size", "intermediate_size",
            "num_attention_heads", "num_key_value_heads", "head_dim",
            "vocab_size", "vocab_pad_multiple", "rms_norm_eps", "rope_theta",
            "attention_bias")
    return _Frozen((k, m[k]) for k in keys)


class _Frozen(dict):
    def __hash__(self):
        return hash(tuple(sorted(self.items())))


def judge(seed: int, m: dict, seqs, at, control: bool = False):
    """Run the reference over each sequence and judge its next tokens.

    ``at[i]`` lists positions of sequence ``i`` whose next token the
    program served. Returns per sequence the gaps of the served tokens
    and, with ``control``, the gaps of the tokens that the int4 control
    puts first at the same positions (both under the float32 reference)."""
    m = frozen(m)
    key = weights.base_key(seed)
    streams = (8, 4) if control else (8,)
    with jax.default_matmul_precision("highest"):
        emb = jax.jit(lambda k: weights.draw_embedding(k, m))(key)
        xs = {}
        for b in streams:
            xs[b] = []
            for s in seqs:
                pad = -(-len(s) // LEN_BUCKET) * LEN_BUCKET
                ids = np.zeros(pad, np.int32)
                ids[:len(s)] = s
                xs[b].append(emb[jnp.asarray(ids)].astype(jnp.float32))
        del emb
        for i in range(m["num_hidden_layers"]):
            for b in streams:
                w = layer_weights(key, i, m, b)
                xs[b] = [layer(x, w, m) for x in xs[b]]
                del w
        norm = jax.jit(lambda k: weights.draw_final_norm(k, m))(key)
        served, ctrl = [], []
        for j, (s, pos) in enumerate(zip(seqs, at)):
            p = jnp.asarray(pos)
            ref = head_logits(key, rms(xs[8][j][p], norm, m["rms_norm_eps"]),
                              m, 8)
            served.append(gap_of(ref, jnp.asarray(np.asarray(s)[pos + 1])))
            if control:
                c = head_logits(key, rms(xs[4][j][p], norm,
                                         m["rms_norm_eps"]), m, 4)
                ctrl.append(gap_of(ref, jnp.argmax(c, -1)))
    return served, ctrl


def gap_of(ref, tokens):
    """How far each chosen token's reference logit lies below the
    reference's best at that position (0 where it is the argmax; +inf for
    an id outside the vocabulary)."""
    v = ref.shape[-1]
    ok = (tokens >= 0) & (tokens < v)
    pick = jnp.take_along_axis(ref, jnp.where(ok, tokens, 0)[:, None],
                               -1)[:, 0]
    return np.asarray(jnp.where(ok, ref.max(-1) - pick, jnp.inf))
