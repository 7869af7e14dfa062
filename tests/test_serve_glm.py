"""A GLM-4-shaped model served through ServeEngine's paged path against a
plain float32 forward pass.

GLM-4-9B's distinctive shapes, scaled down: grouped-query attention with
16 query heads per KV head, QKV bias, and a SwiGLU width that is an odd
multiple of 128 (13696 = 107·128 there, 640 = 5·128 here), so the weight
kernel runs its 512-lane tiles with a ragged last block on the FFN. The
engine serves int8 weight codes with the Pallas kernels in interpret mode
over a two-turn session whose second turn reuses the first turn's KV from
the prefix index. Every logit it computes (each prefill's last position,
each decode step) is compared with a plain float32 forward pass over the
whole sequence, written here in jax.numpy from the dequantized codes.
"""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs.base import ModelConfig
from repro.core.axllm_linear import deploy_quantize
from repro.core.quantization import QTensor, QuantConfig, dequantize
from repro.kernels import ops
from repro.models.model import get_model
from repro.serve.engine import ServeEngine

CFG = ModelConfig(name="glm-shaped", family="dense", n_layers=2,
                  d_model=128, n_heads=16, n_kv_heads=1, d_ff=640,
                  vocab_size=256, head_dim=16, vocab_pad_multiple=64,
                  qkv_bias=True, norm_eps=1.5625e-07, dtype="float32")

# Float32 everywhere, the same int8 codes on both sides: the served logits
# differ from the plain forward pass only by summation order (blocked
# matmul accumulation, online softmax over KV blocks, the suffix prefill
# against a gathered prefix), about 1e-6 of the logits' spread. 1e-4 of
# it leaves room for that and fails a bf16 rounding anywhere on the path
# (~4e-3), a dropped bias or a wrong position.
TOL = 1e-4


@pytest.fixture(scope="module")
def params():
    p = get_model(CFG).init(jax.random.PRNGKey(0))
    attn = dict(p["layers"]["attn"])
    for i, name in enumerate(("wq_bias", "wk_bias", "wv_bias")):
        attn[name] = 0.5 * jax.random.normal(jax.random.PRNGKey(10 + i),
                                             attn[name].shape)
    p["layers"] = dict(p["layers"], attn=attn)
    return deploy_quantize(p, QuantConfig(bits=8))


def _plain_forward(params, tokens):
    """Logits [S, V] of the whole sequence in plain float32 jax.numpy."""
    w = jax.tree_util.tree_map(
        lambda a: dequantize(a) if isinstance(a, QTensor) else a, params,
        is_leaf=lambda a: isinstance(a, QTensor))
    h, hk, hd = CFG.n_heads, CFG.n_kv_heads, CFG.head_dim
    s = len(tokens)

    def rms(x, scale):
        return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True)
                                 + CFG.norm_eps) * scale

    def rope(x):
        half = hd // 2
        freqs = 1.0 / (CFG.rope_theta ** (jnp.arange(half) / half))
        ang = jnp.arange(s)[:, None, None] * freqs
        x1, x2 = x[..., :half], x[..., half:]
        return jnp.concatenate([x1 * jnp.cos(ang) - x2 * jnp.sin(ang),
                                x2 * jnp.cos(ang) + x1 * jnp.sin(ang)], -1)

    x = w["embed"]["embedding"][jnp.asarray(tokens)]
    for i in range(CFG.n_layers):
        lw = jax.tree_util.tree_map(lambda a: a[i], w["layers"])
        at = lw["attn"]
        a = rms(x, lw["ln1"]["scale"])
        q = rope((a @ at["wq"] + at["wq_bias"]).reshape(s, h, hd))
        k = rope((a @ at["wk"] + at["wk_bias"]).reshape(s, hk, hd))
        v = (a @ at["wv"] + at["wv_bias"]).reshape(s, hk, hd)
        k, v = (jnp.repeat(t, h // hk, axis=1) for t in (k, v))
        sc = jnp.einsum("qhd,khd->hqk", q, k) / np.sqrt(hd)
        sc = jnp.where(jnp.tril(jnp.ones((s, s), bool)), sc, -jnp.inf)
        o = jnp.einsum("hqk,khd->qhd", jax.nn.softmax(sc, -1), v)
        x = x + o.reshape(s, h * hd) @ at["wo"]
        f = lw["ffn"]
        a = rms(x, lw["ln2"]["scale"])
        x = x + (jax.nn.silu(a @ f["gate"]) * (a @ f["up"])) @ f["down"]
    x = rms(x, w["final_norm"]["scale"])
    return (x @ w["embed"]["lm_head"])[:, :CFG.vocab_size]


def _spy(eng):
    """Record ``(position, logits)`` for every row the engine computes
    logits of: each prefill wave's last position (its chunk's end) and
    each decode step's input position, from inside the jitted programs."""
    seen = []
    api = eng.api

    def record(pos, logits):
        seen.append((int(pos[0]), np.asarray(logits)[0]))

    def prefill(p, b, c, lengths=None, prefix=None, **kw):
        logits, c = api.prefill(p, b, c, lengths=lengths, prefix=prefix,
                                **kw)
        start = 0 if prefix is None else prefix["len"]
        jax.debug.callback(record, start + lengths - 1, logits)
        return logits, c

    def decode(p, t, c, **kw):
        pos = c["pos"]
        logits, c = api.decode(p, t, c, **kw)
        jax.debug.callback(record, pos, logits)
        return logits, c

    eng.api = dataclasses.replace(api, prefill=prefill, decode=decode)
    return seen


def test_glm_shaped_session_matches_plain_forward(params):
    # the FFN's weight kernels take 512-lane tiles with a ragged tail
    bm, bk, bn, _ = ops.pick_blocks(8, CFG.d_model, CFG.d_ff)
    assert bn == 512 and CFG.d_ff % bn
    assert ops.pick_blocks(8, CFG.d_ff, CFG.d_model)[1] == 512
    eng = ServeEngine(CFG, params, n_slots=1, max_len=128, paged=True,
                      kv_block_size=16, prefill_budget=32, decode_chunk=4,
                      impl="pallas_interpret")
    seen = _spy(eng)
    rng = np.random.default_rng(0)
    prompt = rng.integers(0, CFG.vocab_size, 40).astype(np.int32)
    turns = []
    for max_new, tool in ((7, 19), (6, 0)):
        eng.submit(prompt, max_new)
        while eng.step():
            pass
        turns.append((eng.finished[-1], len(seen)))
        prompt = np.concatenate([prompt, eng.finished[-1].tokens,
                                 rng.integers(0, CFG.vocab_size, tool)]
                                ).astype(np.int32)
    # the second turn took turn one's prompt and reply (46 positions, two
    # whole blocks) from the prefix index and prefilled 34 in two chunks
    assert eng.stats.prefix_hit_tokens == 32
    assert eng.stats.prefill_chunks == 4

    got, want, start = [], [], 0
    for r, end in turns:
        assert len(r.tokens) == r.max_new
        seq = np.concatenate([r.prompt, r.tokens[:-1]])
        ref = np.asarray(_plain_forward(params, seq))
        rows = seen[start:end]
        start = end
        # every chunk's end, then every decode step's input position
        ends = [31, 39] if r is turns[0][0] else [63, 65]
        assert [p for p, _ in rows] == \
            ends + list(range(ends[-1] + 1, len(seq)))
        got += [lg for _, lg in rows]
        want += [ref[p] for p, _ in rows]
    got, want = np.stack(got), np.stack(want)
    spread = float(want.max() - want.min())
    err = float(np.abs(got - want).max())
    print(f"widest logit error {err:.3g} of a {spread:.3g} spread")
    assert err <= TOL * spread, (err, spread)
