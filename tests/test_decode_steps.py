"""Scan-decode equivalence: decode_steps(n) must be token-for-token
identical to n sequential api.decode calls — fp and deploy-quantized,
across all four families, plus stop-mask semantics and the Pallas
interpret-mode path."""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs.base import ModelConfig
from repro.core.axllm_linear import deploy_quantize
from repro.core.quantization import QuantConfig
from repro.models.model import get_model, make_batch
from repro.serve.decode import decode_steps

DENSE = ModelConfig(name="d", family="dense", n_layers=2, d_model=64,
                    n_heads=4, n_kv_heads=2, d_ff=128, vocab_size=256,
                    head_dim=16, vocab_pad_multiple=64, dtype="float32")
SSM = ModelConfig(name="x", family="ssm", n_layers=2, d_model=64, n_heads=4,
                  n_kv_heads=4, d_ff=0, vocab_size=256, vocab_pad_multiple=64,
                  xlstm_slstm_every=2, dtype="float32", remat=False)
HYBRID = ModelConfig(name="h", family="hybrid", n_layers=5, d_model=64,
                     n_heads=4, n_kv_heads=2, d_ff=128, vocab_size=256,
                     head_dim=16, vocab_pad_multiple=64, ssm_state=16,
                     ssm_head_dim=16, hybrid_attn_every=2, dtype="float32",
                     remat=False)
AUDIO = ModelConfig(name="a", family="audio", n_layers=2, d_model=64,
                    n_heads=4, n_kv_heads=4, d_ff=128, vocab_size=256,
                    head_dim=16, vocab_pad_multiple=64, act="gelu",
                    is_encoder_decoder=True, n_enc_layers=1, enc_seq=9,
                    d_feat=4, dtype="float32", remat=False)
FAMILIES = {"dense": DENSE, "ssm": SSM, "hybrid": HYBRID, "audio": AUDIO}

MAX_LEN = 32
B, PROMPT, N = 2, 6, 5


def _prefill(cfg, params, api, impl="auto"):
    cache = api.init_cache(B, MAX_LEN)
    batch = make_batch(cfg, 0, B, PROMPT)
    if cfg.is_encoder_decoder:
        logits, cache = api.prefill(params, batch, cache)
    else:
        logits, cache = api.prefill(params, {"tokens": batch["tokens"]},
                                    cache)
    last = jnp.argmax(logits[:, : cfg.vocab_size], -1).astype(jnp.int32)
    return last, cache


def _sequential(cfg, api, params, last, cache, n):
    toks = []
    for _ in range(n):
        logits, cache = api.decode(params, last, cache)
        last = jnp.argmax(logits[:, : cfg.vocab_size], -1).astype(jnp.int32)
        toks.append(np.asarray(last))
    return np.stack(toks)


def _chunked(cfg, api, params, last, cache, n):
    out = decode_steps(
        api.decode, params, last, cache, jax.random.PRNGKey(0),
        jnp.zeros((B,), bool), jnp.ones((B,), jnp.int32),
        jnp.full((B,), n + 10, jnp.int32), n=n,
        vocab_size=cfg.vocab_size, max_len=MAX_LEN)
    assert bool(np.asarray(out.valid).all())
    return np.asarray(out.tokens)


@pytest.mark.parametrize("family", sorted(FAMILIES))
@pytest.mark.parametrize("quantized", [False, True],
                         ids=["fp", "axllm-int8"])
def test_scan_decode_matches_sequential(family, quantized):
    cfg = FAMILIES[family]
    api = get_model(cfg)
    params = api.init(jax.random.PRNGKey(0))
    if quantized:
        params = deploy_quantize(params, QuantConfig(
            bits=8, mode="affine", granularity="per_channel"))
    last, cache = _prefill(cfg, params, api)
    # the scan donates nothing here: hand each path its own cache copy
    cache2 = jax.tree_util.tree_map(jnp.array, cache)
    seq = _sequential(cfg, api, params, last, cache, N)
    got = _chunked(cfg, api, params, last, cache2, N)
    np.testing.assert_array_equal(got, seq)


def test_scan_decode_interpret_mode():
    """Quantized dense decode through the Pallas kernels in interpret mode:
    the chunked scan must match the sequential interpret-mode loop."""
    cfg = DENSE
    api = get_model(cfg, impl="pallas_interpret")
    params = api.init(jax.random.PRNGKey(0))
    params = deploy_quantize(params, QuantConfig(
        bits=8, mode="affine", granularity="per_channel"))
    last, cache = _prefill(cfg, params, api)
    cache2 = jax.tree_util.tree_map(jnp.array, cache)
    seq = _sequential(cfg, api, params, last, cache, 3)
    got = _chunked(cfg, api, params, last, cache2, 3)
    np.testing.assert_array_equal(got, seq)


def _paged_from_dense(cache, bs: int, seed: int = 0):
    """The dense cache's KV scattered into a block pool through shuffled
    per-row block tables (block 0, the trash block, left out), every
    layer at once: [L, B, S, Hk, d] -> pool [L, 1 + B·S/bs, bs, Hk, d]."""
    nl, b, s = cache["k"].shape[:3]
    mb = s // bs
    order = 1 + np.random.default_rng(seed).permutation(b * mb)
    tables = order.reshape(b, mb)
    paged = {"pos": cache["pos"],
             "block_tables": jnp.asarray(tables, jnp.int32)}
    for name, a in cache.items():
        if name == "pos":
            continue
        blocks = a.reshape(nl, b * mb, bs, *a.shape[3:])
        pool = jnp.zeros((nl, 1 + b * mb, bs, *a.shape[3:]), a.dtype)
        paged[name] = pool.at[:, tables.reshape(-1)].set(blocks)
    return paged


PAGED = {"fp": (DENSE, False, "auto"),
         "axllm-int8": (DENSE, True, "auto"),
         "int8-kv-3-layers": (dataclasses.replace(DENSE, n_layers=3,
                                                  quant_kv=True),
                              False, "auto"),
         "interpret": (DENSE, True, "pallas_interpret")}


@pytest.mark.parametrize("mode", sorted(PAGED))
def test_paged_scan_decode_matches_dense(mode):
    """decode_steps over a block-paged cache, whose pool rides through
    the layer scan and is written and read in place at each layer's
    index, emits the same tokens as sequential decode over the dense
    cache (its own code path): a token written into another layer's
    pool, or read from one, changes the stream."""
    cfg, quantized, impl = PAGED[mode]
    api = get_model(cfg, impl=impl)
    params = api.init(jax.random.PRNGKey(0))
    if quantized:
        params = deploy_quantize(params, QuantConfig(
            bits=8, mode="affine", granularity="per_channel"))
    last, cache = _prefill(cfg, params, api)
    paged = _paged_from_dense(cache, bs=8)
    n = 3 if impl == "pallas_interpret" else N
    seq = _sequential(cfg, api, params, last, cache, n)
    got = _chunked(cfg, api, params, last, paged, n)
    np.testing.assert_array_equal(got, seq)


def test_stop_mask_freezes_slot_on_eos():
    """EOS mid-chunk: the slot's valid mask must become (and stay) False
    and its last token must freeze while other slots keep decoding."""
    cfg = DENSE
    api = get_model(cfg)
    params = api.init(jax.random.PRNGKey(0))
    last, cache = _prefill(cfg, params, api)
    free = _sequential(cfg, api, params, last,
                       jax.tree_util.tree_map(jnp.array, cache), N)
    eos = int(free[1, 0])          # row 0 emits this at step 1
    out = decode_steps(
        api.decode, params, last, cache, jax.random.PRNGKey(0),
        jnp.zeros((B,), bool), jnp.ones((B,), jnp.int32),
        jnp.full((B,), N + 10, jnp.int32), n=N,
        vocab_size=cfg.vocab_size, max_len=MAX_LEN, eos_id=eos)
    valid = np.asarray(out.valid)
    toks = np.asarray(out.tokens)
    stopped_at = int(np.argmax(toks[:, 0] == eos))
    assert valid[: stopped_at + 1, 0].all()
    assert not valid[stopped_at + 1:, 0].any()      # prefix semantics
    assert (toks[stopped_at:, 0] == eos).all()      # frozen last token
    assert bool(np.asarray(out.stop_mask)[0])
    if not (free[:, 1] == eos).any():               # other slot unaffected
        assert valid[:, 1].all()
        np.testing.assert_array_equal(toks[:, 1], free[:, 1])


def test_stop_mask_max_new_and_cache_full():
    cfg = DENSE
    api = get_model(cfg)
    params = api.init(jax.random.PRNGKey(0))
    last, cache = _prefill(cfg, params, api)
    # per-slot budgets: slot 0 may emit 2 more tokens, slot 1 four more
    out = decode_steps(
        api.decode, params, last, cache, jax.random.PRNGKey(0),
        jnp.zeros((B,), bool), jnp.ones((B,), jnp.int32),
        jnp.asarray([3, 5], jnp.int32), n=6,
        vocab_size=cfg.vocab_size, max_len=MAX_LEN)
    valid = np.asarray(out.valid)
    assert valid[:, 0].sum() == 2 and valid[:, 1].sum() == 4
    assert np.asarray(out.stop_mask).all()
    assert np.asarray(out.gen).tolist() == [3, 5]
    # cache-full: pos starts at PROMPT, so max_len = PROMPT + 2 stops
    # both rows after exactly 2 emitted tokens regardless of max_new
    _, cache = _prefill(cfg, params, api)
    out = decode_steps(
        api.decode, params, last, cache, jax.random.PRNGKey(0),
        jnp.zeros((B,), bool), jnp.ones((B,), jnp.int32),
        jnp.full((B,), 99, jnp.int32), n=6,
        vocab_size=cfg.vocab_size, max_len=PROMPT + 2)
    assert np.asarray(out.valid).sum(0).tolist() == [2, 2]


def test_sampled_chunk_invariance():
    """Non-greedy sampling splits one key per step on device, so the token
    stream must not depend on how the steps are chunked."""
    cfg = DENSE
    api = get_model(cfg)
    params = api.init(jax.random.PRNGKey(0))
    last, cache = _prefill(cfg, params, api)

    def draw(chunks):
        l, c = last, jax.tree_util.tree_map(jnp.array, cache)
        rng = jax.random.PRNGKey(42)
        stop = jnp.zeros((B,), bool)
        gen = jnp.ones((B,), jnp.int32)
        budget = jnp.full((B,), 99, jnp.int32)
        toks = []
        for n in chunks:
            out = decode_steps(api.decode, params, l, c, rng, stop, gen,
                               budget, n=n, vocab_size=cfg.vocab_size,
                               max_len=MAX_LEN, greedy=False)
            l, c, rng, stop, gen = (out.last, out.cache, out.rng,
                                    out.stop_mask, out.gen)
            toks.append(np.asarray(out.tokens))
        return np.concatenate(toks, axis=0)

    np.testing.assert_array_equal(draw([6]), draw([1] * 6))
    np.testing.assert_array_equal(draw([6]), draw([2, 3, 1]))
