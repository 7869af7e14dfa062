"""Block-paged KV cache: paged flash-decode kernel vs oracle, the host
allocator/radix-index manager, and end-to-end engine equivalence — paged
decode must be token-identical to the dense path across every serving
configuration (fp32, int8 weights, int8 KV, pallas_interpret, fused QKV,
multi-LoRA, decode_chunk 1/8), with prefix reuse and eviction on top."""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs.base import ModelConfig
from repro.kernels import ops, ref
from repro.models.model import get_model
from repro.serve.engine import ServeEngine
from repro.serve.paged_cache import PagedKVCache

CFG = ModelConfig(name="s", family="dense", n_layers=2, d_model=64,
                  n_heads=4, n_kv_heads=2, d_ff=128, vocab_size=256,
                  head_dim=16, vocab_pad_multiple=64, dtype="float32")

MIXED = [np.arange(8), np.arange(12) + 3, np.arange(31) + 7,
         np.arange(12) + 40, np.arange(8) + 60, np.arange(31) + 90]


@pytest.fixture(scope="module")
def params():
    return get_model(CFG).init(jax.random.PRNGKey(0))


# ---------------------------------------------------------------------------
# Kernel: paged flash-decode vs oracle vs dense
# ---------------------------------------------------------------------------

def _rand(rng, shape):
    return jnp.asarray(rng.normal(size=shape), jnp.float32)


def _kv_quant(x):
    s = jnp.maximum(jnp.max(jnp.abs(x), -1, keepdims=True), 1e-8) / 127.0
    return (jnp.clip(jnp.round(x / s), -127, 127).astype(jnp.int8),
            s.astype(jnp.float32))


@pytest.mark.parametrize("case", [(3, 16, 8, 8, 2, 64), (2, 32, 4, 4, 4, 32)])
def test_paged_decode_kernel_vs_oracle(case):
    b, bs, mb, h, hk, d = case
    nb = b * mb + 4
    rng = np.random.default_rng(11)
    q = _rand(rng, (b, h, d))
    pk = _rand(rng, (nb, bs, hk, d))
    pv = _rand(rng, (nb, bs, hk, d))
    # non-trivial tables: a permutation of the pool, trash beyond length
    bt = jnp.asarray(1 + rng.permutation(nb - 1)[: b * mb].reshape(b, mb),
                     jnp.int32)
    length = jnp.asarray([0, bs + 3, mb * bs][:b], jnp.int32)
    o_ref = ref.paged_decode_attention_ref(q, pk, pv, bt, length)
    o_pal = ops.decode_attention(q, pk, pv, length, block_tables=bt,
                                 impl="pallas_interpret")
    np.testing.assert_allclose(np.asarray(o_pal), np.asarray(o_ref),
                               rtol=2e-5, atol=2e-5)
    # length == 0 rows are exact zeros on both paths
    assert np.allclose(np.asarray(o_ref[0]), 0.0)
    assert np.allclose(np.asarray(o_pal[0]), 0.0)
    # gathering the table into a dense cache reproduces the dense oracle
    kd = pk[bt].reshape(b, mb * bs, hk, d)
    vd = pv[bt].reshape(b, mb * bs, hk, d)
    np.testing.assert_allclose(
        np.asarray(ref.decode_attention_ref(q, kd, vd, length)),
        np.asarray(o_ref), rtol=1e-6, atol=1e-6)


def test_paged_decode_kernel_int8_kv():
    b, bs, mb, h, hk, d = 2, 16, 4, 8, 2, 64
    nb = b * mb + 2
    rng = np.random.default_rng(12)
    q = _rand(rng, (b, h, d))
    kq, ks = _kv_quant(_rand(rng, (nb, bs, hk, d)))
    vq, vs = _kv_quant(_rand(rng, (nb, bs, hk, d)))
    bt = jnp.asarray(1 + rng.permutation(nb - 1)[: b * mb].reshape(b, mb),
                     jnp.int32)
    length = jnp.asarray([0, 3 * bs + 5], jnp.int32)
    o_ref = ref.paged_decode_attention_ref(q, kq, vq, bt, length,
                                           k_scale=ks, v_scale=vs)
    o_pal = ops.decode_attention(q, kq, vq, length, block_tables=bt,
                                 k_scale=ks, v_scale=vs,
                                 impl="pallas_interpret")
    np.testing.assert_allclose(np.asarray(o_pal), np.asarray(o_ref),
                               rtol=2e-5, atol=2e-5)


@pytest.mark.parametrize("kv", ["bf16", "int8"])
@pytest.mark.parametrize("h,hk", [(8, 2), (16, 1)], ids=["gqa4", "gqa16"])
def test_stacked_pool_kernel_vs_oracle(kv, h, hk):
    """The kernel reads one layer of an [L, NB, bs, Hk, d] stack in place
    (the layer a prefetched index): it matches the oracle on that layer's
    slice, at a non-zero layer whose neighbours hold other values, over
    ragged lengths (empty, mid-block, full) and rows whose tables run
    into the trash block, which holds large finite garbage."""
    nl, layer, b, bs, mb, d = 3, 1, 4, 16, 4, 32
    nb = b * mb + 1
    rng = np.random.default_rng(15)
    q = _rand(rng, (b, h, d))
    k, v = _rand(rng, (nl, nb, bs, hk, d)), _rand(rng, (nl, nb, bs, hk, d))
    k, v = k.at[:, 0].set(1e4), v.at[:, 0].set(-1e4)
    length = jnp.asarray([0, bs + 3, 2 * bs, mb * bs], jnp.int32)
    tables = 1 + rng.permutation(nb - 1).reshape(b, mb)
    # past its length a row's table names the trash block, as it does
    # for a stopped slot or a block not yet allocated
    past = np.arange(mb)[None, :] * bs >= np.asarray(length)[:, None]
    bt = jnp.asarray(np.where(past, 0, tables), jnp.int32)
    kw = {}
    if kv == "int8":
        (k, ks), (v, vs) = _kv_quant(k), _kv_quant(v)
        kw = dict(k_scale=ks, v_scale=vs)
    else:
        k, v = k.astype(jnp.bfloat16), v.astype(jnp.bfloat16)
    want = ref.paged_decode_attention_ref(
        q, k[layer], v[layer], bt, length,
        **{n: a[layer] for n, a in kw.items()})
    for impl in ("pallas_interpret", "ref"):
        got = ops.decode_attention(q, k, v, length, block_tables=bt,
                                   layer=jnp.int32(layer), impl=impl, **kw)
        np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                   rtol=2e-5, atol=2e-5)
    assert np.allclose(np.asarray(want[0]), 0.0)
    other = ref.paged_decode_attention_ref(
        q, k[layer + 1], v[layer + 1], bt, length,
        **{n: a[layer + 1] for n, a in kw.items()})
    assert not np.allclose(np.asarray(other), np.asarray(want), atol=1e-2)


def test_prefix_attention_matches_causal_oracle():
    """Suffix-only prefill attention == full causal attention restricted to
    the suffix rows, per row of a ragged (padded) prefix."""
    rng = np.random.default_rng(13)
    b, s, h, hk, d, pad = 2, 6, 4, 2, 16, 8
    plen = np.array([5, 8], np.int32)
    kp, vp = _rand(rng, (b, pad, hk, d)), _rand(rng, (b, pad, hk, d))
    q = _rand(rng, (b, s, h, d))
    ks, vs = _rand(rng, (b, s, hk, d)), _rand(rng, (b, s, hk, d))
    out = ops.prefix_attention(q, kp, vp, jnp.asarray(plen), ks, vs)
    for i in range(b):
        n = int(plen[i])
        kf = jnp.concatenate([kp[i:i + 1, :n], ks[i:i + 1]], axis=1)
        vf = jnp.concatenate([vp[i:i + 1, :n], vs[i:i + 1]], axis=1)
        want = ref.attention_ref(q[i:i + 1], kf, vf, causal=True)
        np.testing.assert_allclose(np.asarray(out[i:i + 1]),
                                   np.asarray(want), rtol=2e-5, atol=2e-5)


def test_prefix_attention_dispatch_regression():
    """impl='pallas' used to silently run the jnp oracle — the dispatch
    must now refuse loudly until a compiled kernel exists, while
    'pallas_interpret' (oracle semantics) and the reuse impl aliases
    (normalized to their base dispatch) keep working."""
    rng = np.random.default_rng(14)
    b, s, h, hk, d, pad = 1, 3, 2, 1, 8, 4
    plen = jnp.asarray([2], jnp.int32)
    kp, vp = _rand(rng, (b, pad, hk, d)), _rand(rng, (b, pad, hk, d))
    q = _rand(rng, (b, s, h, d))
    ks, vs = _rand(rng, (b, s, hk, d)), _rand(rng, (b, s, hk, d))
    base = ops.prefix_attention(q, kp, vp, plen, ks, vs, impl="auto")
    with pytest.raises(NotImplementedError, match="prefix_attention"):
        ops.prefix_attention(q, kp, vp, plen, ks, vs, impl="pallas")
    for impl in ("pallas_interpret", "ref", "reuse", "reuse_ref"):
        out = ops.prefix_attention(q, kp, vp, plen, ks, vs, impl=impl)
        np.testing.assert_array_equal(np.asarray(out), np.asarray(base))


# ---------------------------------------------------------------------------
# Host manager: allocator, radix index, CoW, eviction
# ---------------------------------------------------------------------------

def _pager(**kw):
    args = dict(n_slots=2, n_blocks=12, block_size=4, max_blocks_per_slot=4)
    args.update(kw)
    return PagedKVCache(**args)


def test_pager_alloc_free_and_trash_reserved():
    p = _pager()
    assert p.blocks_in_use == 0
    bids = [p.alloc() for _ in range(11)]       # 12 blocks minus trash
    assert 0 not in bids and len(set(bids)) == 11
    with pytest.raises(RuntimeError, match="exhausted"):
        p.alloc()                               # nothing evictable
    p._release_block(bids[0])
    assert p.alloc() == bids[0]


def test_pager_undersized_pool_rejected():
    with pytest.raises(ValueError, match="cannot back"):
        _pager(n_blocks=9)                      # needs 2*4 + 2
    with pytest.raises(ValueError, match="power of two"):
        _pager(block_size=6)


def test_pager_match_insert_roundtrip():
    p = _pager()
    toks = list(range(11))                      # 2 full blocks + 3 tail
    b0, b1 = p.alloc(), p.alloc()
    assert p.insert(toks, [b0, b1]) == 2
    hit, n = p.match(toks)
    assert hit == [b0, b1] and n == 8
    # divergent second chunk stops the walk after one block
    hit, n = p.match(list(range(4)) + [99] * 7)
    assert hit == [b0] and n == 4
    # a prompt that is exactly the cached blocks keeps one token for
    # prefill: the hit is capped at len-1 and floored to full blocks
    hit, n = p.match(list(range(8)))
    assert n == 4 and hit == [b0]
    # duplicate insert publishes nothing new
    assert p.insert(toks, [p.alloc(), p.alloc()]) == 0


def test_pager_cow_on_shared_block():
    p = _pager()
    toks = list(range(8))
    b0, b1 = p.alloc(), p.alloc()
    p.insert(toks, [b0, b1])
    # two slots take the same cached blocks, then each makes its window
    # writable: the shared block must be copy-on-written, once per slot
    p.acquire_blocks(0, [b0, b1])
    p.acquire_blocks(1, [b0, b1])
    cow0 = p.prepare_decode(0, 6, 2)            # writes inside block 1
    assert len(cow0) == 1 and cow0[0][0] == b1
    assert p.tables[0, 1] == cow0[0][1] != b1
    cow1 = p.prepare_decode(1, 6, 2)
    assert len(cow1) == 1 and cow1[0][0] == b1
    # fresh appends past the table end need no copy
    assert p.prepare_decode(0, 8, 4) == []
    assert p.slot_blocks(0)[2] != 0


def test_pager_release_keeps_indexed_blocks():
    p = _pager()
    toks = list(range(8))
    b0, b1 = p.alloc(), p.alloc()
    p.insert(toks, [b0, b1])
    p.acquire_blocks(0, [b0, b1])
    p.release_slot(0)
    assert p.blocks_in_use == 2                 # index still holds them
    assert p.match(toks)[0] == [b0]             # capped at len-1


def test_pager_lru_eviction():
    p = _pager(n_blocks=13)                     # 12 usable
    old = [p.alloc(), p.alloc()]
    new = [p.alloc(), p.alloc()]
    p.insert(list(range(8)), old)
    p.insert(list(range(100, 108)), new)
    for b in old + new:                         # slots finished: index-only
        p._release_block(b)
    p.match(list(range(9)))                     # touch `old`: now MRU
    taken = [p.alloc() for _ in range(8)]       # pool is now dry
    got = p.alloc()                             # must evict an LRU leaf
    assert p.evictions == 1 and got == new[1]   # deepest LRU leaf first
    hit, n = p.match(list(range(100, 109)))
    assert n == 4                               # new[1] gone, new[0] stays
    assert p.match(list(range(9)))[1] == 8      # MRU chain untouched
    del taken, got


# ---------------------------------------------------------------------------
# Engine: paged == dense, token for token
# ---------------------------------------------------------------------------

def _tokens(cfg, params, prompts, max_new=6, **kw):
    eng = ServeEngine(cfg, params, n_slots=2, max_len=64, **kw)
    return eng.generate(prompts, max_new=max_new), eng


@pytest.mark.parametrize("mode", ["fp32", "int8", "int8kv", "fused",
                                  "chunk1"])
def test_paged_engine_matches_dense(params, mode):
    cfg = CFG
    kw = {}
    if mode == "int8":
        kw["quantize"] = True
    elif mode == "int8kv":
        cfg = dataclasses.replace(CFG, quant_kv=True)
        kw["quantize"] = True
    elif mode == "fused":
        kw.update(quantize=True, fuse_qkv=True)
    elif mode == "chunk1":
        kw["decode_chunk"] = 1
    dense, _ = _tokens(cfg, params, MIXED, **kw)
    paged, eng = _tokens(cfg, params, MIXED, paged=True, kv_block_size=8,
                         **kw)
    assert dense == paged
    assert eng.stats.finished == len(MIXED)


def test_paged_engine_interpret_mode(params):
    """The real Pallas kernel bodies (paged decode included) under
    interpret mode produce the same tokens as the oracle path."""
    prompts = MIXED[:2]
    dense, _ = _tokens(CFG, params, prompts, max_new=4, impl="ref")
    paged, _ = _tokens(CFG, params, prompts, max_new=4, paged=True,
                       kv_block_size=8, impl="pallas_interpret")
    assert dense == paged


def test_paged_engine_multi_lora(params):
    from repro.launch.serve import make_synthetic_adapters
    reg, names = make_synthetic_adapters(CFG, n=2)
    adapters = [None, names[0], names[1], names[0]]
    prompts = [np.arange(8), np.arange(8), np.arange(8) + 40,
               np.arange(12) + 3]
    dense = ServeEngine(CFG, params, n_slots=2, max_len=64, quantize=True,
                        adapters=reg).generate(prompts, max_new=5,
                                               adapters=adapters)
    reg2, _ = make_synthetic_adapters(CFG, n=2)
    eng = ServeEngine(CFG, params, n_slots=2, max_len=64, quantize=True,
                      adapters=reg2, paged=True, kv_block_size=8)
    paged = eng.generate(prompts, max_new=5, adapters=adapters)
    assert dense == paged


def test_paged_lora_never_reuses_base_prefix(params):
    """Adapters targeting wv make the KV adapter-specific: a LoRA request
    whose prompt is already indexed from a base-model run must NOT take
    the cached base KV (it recomputes its own, and publishes nothing)."""
    from repro.launch.serve import make_synthetic_adapters
    prompt = np.arange(20)
    reg, names = make_synthetic_adapters(CFG, n=1)     # targets wq, wv
    eng = ServeEngine(CFG, params, n_slots=2, max_len=64, quantize=True,
                      adapters=reg, paged=True, kv_block_size=8)
    base = eng.generate([prompt], max_new=5)           # indexes the prompt
    assert eng.stats.prefix_hit_tokens == 0
    lora = eng.generate([prompt], max_new=5, adapters=[names[0]])
    assert eng.stats.prefix_hit_tokens == 0            # no cross-hit
    # the reference: a dense engine decoding the same adapter solo
    reg2, n2 = make_synthetic_adapters(CFG, n=1)
    want = ServeEngine(CFG, params, n_slots=1, max_len=64, quantize=True,
                       adapters=reg2).generate([prompt], max_new=5,
                                               adapters=[n2[0]])
    assert lora == want and lora != base
    # base requests still hit the index afterwards
    assert eng.generate([prompt], max_new=5) == base
    assert eng.stats.prefix_hit_tokens > 0


def test_paged_prefix_reuse_and_stats(params):
    prefix = np.arange(16) + 5
    prompts = [np.concatenate([prefix, np.arange(4) + 100 + 7 * i])
               for i in range(4)]
    dense, _ = _tokens(CFG, params, prompts)
    paged, eng = _tokens(CFG, params, prompts, paged=True, kv_block_size=8)
    assert dense == paged
    assert eng.stats.prefix_hit_tokens > 0
    assert eng.stats.blocks_in_use > 0
    # a second identical batch on the same engine reuses even more (the
    # full prompts are indexed now) and still matches
    hits0 = eng.stats.prefix_hit_tokens
    assert eng.generate(prompts, max_new=6) == dense
    assert eng.stats.prefix_hit_tokens > hits0


def test_paged_prefix_cache_off(params):
    dense, _ = _tokens(CFG, params, MIXED)
    paged, eng = _tokens(CFG, params, MIXED, paged=True, kv_block_size=8,
                         prefix_cache=False)
    assert dense == paged
    assert eng.stats.prefix_hit_tokens == 0
    # without the index, drained slots return every block to the free list
    assert eng.stats.blocks_in_use == 0


def test_paged_eviction_under_pressure(params):
    """A pool sized at the bare minimum forces index eviction between
    generations; tokens stay identical to dense."""
    mb = 64 // 8
    eng = ServeEngine(CFG, params, n_slots=2, max_len=64, paged=True,
                      kv_block_size=8, num_blocks=2 * mb + 2)
    dense, _ = _tokens(CFG, params, MIXED)
    for _ in range(2):
        assert eng.generate(MIXED, max_new=6) == dense
    assert eng.pager.evictions > 0


def test_paged_rejects_recurrent_family():
    cfg = ModelConfig(name="sx", family="ssm", n_layers=2, d_model=64,
                      n_heads=4, n_kv_heads=4, d_ff=0, vocab_size=256,
                      vocab_pad_multiple=64, xlstm_slstm_every=2,
                      dtype="float32", remat=False)
    p = get_model(cfg).init(jax.random.PRNGKey(1))
    with pytest.raises(ValueError, match="no paged KV cache path"):
        ServeEngine(cfg, p, n_slots=2, max_len=64, paged=True)


def test_paged_long_prompt_and_cache_full(params):
    """Truncation + cache-full stop conditions behave exactly as dense."""
    dense = ServeEngine(CFG, params, n_slots=1, max_len=16).generate(
        [np.arange(40)], max_new=8, return_requests=True)
    eng = ServeEngine(CFG, params, n_slots=1, max_len=16, paged=True,
                      kv_block_size=8)
    paged = eng.generate([np.arange(40)], max_new=8, return_requests=True)
    assert dense[0].tokens == paged[0].tokens
    assert paged[0].prompt_truncated and paged[0].truncated


def test_paged_moe_family():
    cfg = ModelConfig(name="sm", family="moe", n_layers=2, d_model=64,
                      n_heads=4, n_kv_heads=2, d_ff=64, vocab_size=256,
                      head_dim=16, vocab_pad_multiple=64, n_experts=4,
                      top_k=2, expert_pad_to=4, capacity_factor=8.0,
                      dtype="float32", remat=False)
    p = get_model(cfg).init(jax.random.PRNGKey(3))
    dense, _ = _tokens(cfg, p, MIXED[:3], max_new=4)
    paged, _ = _tokens(cfg, p, MIXED[:3], max_new=4, paged=True,
                       kv_block_size=8)
    assert dense == paged


def test_paged_cache_spec_validation(params):
    eng = ServeEngine(CFG, params, n_slots=2, max_len=64, paged=True,
                      kv_block_size=8)
    api = eng.api
    spec = api.paged_cache_spec
    cache = jax.eval_shape(lambda: api.init_paged_cache(3, 20, 8, 4))
    assert set(spec) == set(cache)
    for name, ax in spec.items():
        want = 20 if ax == 1 else 3
        assert cache[name].shape[ax] == want, (name, cache[name].shape)
