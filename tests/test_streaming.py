"""Streaming serving: chunked prefill, token emission, cancellation-safe
teardown, and mid-run execution deadlines.

The invariants under test mirror docs/ARCHITECTURE.md's request lifecycle:
a prefill-token budget bounds every step's prefill work while staying
token-identical to the unbudgeted path (including across mid-prefill
preemption), `on_token` / `stream()` emit exactly the tokens the finished
request holds, cancellation at any lifecycle point (queued, mid-prefill
chunk, mid-decode, mid-speculative round) balances the books — slot,
blocks, adapter pins — while published prefixes survive for reuse, and
TTFT / inter-token deadlines expire a stream mid-run with its resources
freed.
"""

import itertools

import jax
import numpy as np
import pytest

from repro.configs.base import ModelConfig
from repro.models.model import get_model
from repro.serve.engine import ServeEngine, StopStream

CFG = ModelConfig(name="s", family="dense", n_layers=2, d_model=64,
                  n_heads=4, n_kv_heads=2, d_ff=128, vocab_size=256,
                  head_dim=16, vocab_pad_multiple=64, dtype="float32")

MIXED = [np.arange(8), np.arange(31) + 7, np.arange(45) % 256,
         np.arange(12) + 40]

MAX_NEW = 6


@pytest.fixture(scope="module")
def params():
    return get_model(CFG).init(jax.random.PRNGKey(0))


@pytest.fixture(scope="module")
def reference(params):
    eng = ServeEngine(CFG, params, n_slots=4, max_len=64)
    return eng.generate(MIXED, max_new=MAX_NEW)


def _paged(params, **kw):
    kw.setdefault("n_slots", 2)
    kw.setdefault("max_len", 64)
    kw.setdefault("kv_block_size", 8)
    return ServeEngine(CFG, params, paged=True, **kw)


# ---------------------------------------------------------------------------
# Chunked prefill
# ---------------------------------------------------------------------------

def test_budgeted_prefill_token_identical(params, reference):
    eng = _paged(params, prefill_budget=16)
    got = eng.generate(MIXED, max_new=MAX_NEW)
    assert got == reference
    # the 45-token prompt cannot fit one 16-token chunk: prefill really
    # was chunked, not just admitted whole
    assert eng.stats.prefill_chunks > len(MIXED)
    eng.pager.check_consistency()


def test_budget_bounds_every_steps_prefill(params):
    budget = 16
    eng = _paged(params, prefill_budget=budget)
    for p in MIXED:
        eng.submit(p, max_new=MAX_NEW)
    done = 0
    while True:
        before = eng.stats.prefill_tokens
        if not eng.step():
            break
        done += 1
        assert eng.stats.prefill_tokens - before <= budget
    assert done > 0
    # computed chunks + radix-reused prefix tokens cover every prompt
    assert eng.stats.prefill_tokens + eng.stats.prefix_hit_tokens == \
        sum(len(p) for p in MIXED)


def test_budget_spent_by_a_first_token_finish_does_not_stall(params):
    """A prompt that finishes on its first token spends most of the
    step's budget; the queued prompt behind it cannot take a whole block
    of what is left and nothing else is seated. The step ends there (it
    did its prefill work) and the next one admits the queue, where the
    engine used to raise "admission stalled"."""
    prompts = [np.arange(10) + 3, np.arange(20) + 60]

    def serve(eng, first=None):
        for p, n in zip(prompts, (1, 3)):
            eng.submit(p, max_new=n)
        if first is not None:
            first(eng)
        while eng.step():
            pass
        return [r.tokens for r in sorted(eng.finished, key=lambda r: r.rid)]

    def first_step(eng):
        assert eng.step()                   # first prompt done, queue held
        assert [r.max_new for r in eng.finished] == [1]
        assert len(eng.queue) == 1

    want = serve(ServeEngine(CFG, params, n_slots=2, max_len=64))
    eng = _paged(params, prefill_budget=16)
    assert serve(eng, first_step) == want
    eng.pager.check_consistency()


def test_prefill_budget_init_rejections(params):
    with pytest.raises(ValueError, match="paged"):
        ServeEngine(CFG, params, n_slots=2, max_len=64, prefill_budget=16)
    with pytest.raises(ValueError, match="kv_block_size"):
        _paged(params, prefill_budget=4)      # below one block
    with pytest.raises(ValueError, match="speculate"):
        _paged(params, prefill_budget=16, speculate=True)


def test_adopt_compiled_rejects_budget_mismatch(params):
    budgeted = _paged(params, prefill_budget=16)
    unbudgeted = _paged(params)
    with pytest.raises(ValueError):
        unbudgeted.adopt_compiled(budgeted)


def test_mid_prefill_preemption_token_identical(params, reference):
    """A long prompt preempted mid-prefill by higher-priority arrivals
    must publish its consumed prefix and resume token-identically."""
    eng = _paged(params, prefill_budget=8,
                 num_blocks=2 * 2 * 8 + 2)    # tight pool: preemption bites
    long_rid = eng.submit(MIXED[2], max_new=MAX_NEW, priority=0)
    eng.step()                                # first chunk consumed
    assert any(s is not None and s.prefilling for s in eng.slots)
    hi = [eng.submit(MIXED[0], max_new=MAX_NEW, priority=5),
          eng.submit(MIXED[3], max_new=MAX_NEW, priority=5)]
    while eng.step():
        pass
    assert eng.stats.preempted_prefill >= 1
    by_rid = {r.rid: r for r in eng.finished}
    assert by_rid[long_rid].tokens == reference[2]
    assert by_rid[hi[0]].tokens == reference[0]
    assert by_rid[hi[1]].tokens == reference[3]
    eng.pager.check_consistency()


# ---------------------------------------------------------------------------
# Streaming emission
# ---------------------------------------------------------------------------

def test_on_token_emits_exactly_the_finished_tokens(params, reference):
    got = {}

    def tap(req, tok):
        got.setdefault(req.rid, []).append(tok)

    eng = _paged(params, decode_chunk=1)
    rids = [eng.submit(p, max_new=MAX_NEW, on_token=tap) for p in MIXED]
    while eng.step():
        pass
    by_rid = {r.rid: r for r in eng.finished}
    for rid, want in zip(rids, reference):
        assert got[rid] == want == by_rid[rid].tokens


def test_t_first_stamped_at_first_emission(params):
    clock = itertools.count(0)
    eng = _paged(params, decode_chunk=1,
                 clock=lambda: float(next(clock)))
    rid = eng.submit(MIXED[0], max_new=MAX_NEW)
    while eng.step():
        pass
    r = {x.rid: x for x in eng.finished}[rid]
    # first token comes out of the prefill harvest; later decode chunks
    # must not move the stamp (the old bug stamped at finish-harvest)
    assert r.t_first is not None and r.t_submit < r.t_first <= r.t_last


def test_stream_generator_matches_generate(params, reference):
    eng = _paged(params)
    assert list(eng.stream(MIXED[1], max_new=MAX_NEW)) == reference[1]


def test_stream_early_close_cancels(params, reference):
    eng = _paged(params, decode_chunk=1)
    seen = []
    for tok in eng.stream(MIXED[0], max_new=MAX_NEW):
        seen.append(tok)
        if len(seen) == 2:
            break                             # client walks away
    assert seen == reference[0][:2]
    assert eng.stats.cancelled == 1
    assert all(s is None for s in eng.slots)
    eng.pager.evict_prefixes()
    assert eng.pager.blocks_in_use == 0


# ---------------------------------------------------------------------------
# Cancellation matrix: queued / mid-prefill / mid-decode / mid-speculation
# ---------------------------------------------------------------------------

def _finish_of(eng, rid):
    return {r.rid: r for r in eng.finished}[rid]


def test_cancel_while_queued(params, reference):
    eng = _paged(params, n_slots=1)
    keep = eng.submit(MIXED[0], max_new=MAX_NEW)
    victim = eng.submit(MIXED[3], max_new=MAX_NEW)
    assert eng.cancel(victim) is True
    while eng.step():
        pass
    assert _finish_of(eng, victim).finish_reason == "cancelled"
    assert _finish_of(eng, victim).tokens == []
    assert _finish_of(eng, keep).tokens == reference[0]
    assert eng.cancel(victim) is False        # already finished
    with pytest.raises(KeyError):
        eng.cancel(10_000)


def test_cancel_mid_prefill_chunk_keeps_published_prefix(params, reference):
    eng = _paged(params, prefill_budget=8, decode_chunk=1)
    victim = eng.submit(MIXED[2], max_new=MAX_NEW)
    eng.step()
    s = next(s for s in eng.slots if s is not None and s.rid == victim)
    assert s.prefilling and 0 < s.prefill_cursor < len(MIXED[2])
    assert eng.cancel(victim) is True
    r = _finish_of(eng, victim)
    assert r.finish_reason == "cancelled" and r.tokens == []
    assert all(s is None for s in eng.slots)
    eng.pager.check_consistency()
    # the consumed chunks were published: resubmitting the same prompt
    # reuses them and still decodes token-identically
    hits_before = eng.stats.prefix_hit_tokens
    retry = eng.submit(MIXED[2], max_new=MAX_NEW)
    while eng.step():
        pass
    assert eng.stats.prefix_hit_tokens > hits_before
    assert _finish_of(eng, retry).tokens == reference[2]
    eng.pager.evict_prefixes()
    assert eng.pager.blocks_in_use == 0


def test_cancel_mid_decode_leaves_prefix_and_survivors_identical(
        params, reference):
    eng = _paged(params, decode_chunk=1)
    victim = eng.submit(MIXED[0], max_new=MAX_NEW)
    keep = eng.submit(MIXED[1], max_new=MAX_NEW)
    while not _seated_tokens(eng, victim):
        eng.step()
    assert eng.cancel(victim) is True
    while eng.step():
        pass
    r = _finish_of(eng, victim)
    assert r.finish_reason == "cancelled"
    assert 0 < len(r.tokens) < len(reference[0])
    assert r.tokens == reference[0][:len(r.tokens)]
    assert _finish_of(eng, keep).tokens == reference[1]
    eng.pager.evict_prefixes()
    assert eng.pager.blocks_in_use == 0


def _seated_tokens(eng, rid):
    for s in eng.slots:
        if s is not None and s.rid == rid and not s.prefilling:
            return list(s.tokens)
    return []


def test_stop_stream_from_callback_cancels(params, reference):
    emitted = []

    def client(req, tok):
        emitted.append(tok)
        if len(emitted) == 3:
            raise StopStream()

    eng = _paged(params)
    rid = eng.submit(MIXED[1], max_new=MAX_NEW, on_token=client)
    while eng.step():
        pass
    r = _finish_of(eng, rid)
    assert r.finish_reason == "cancelled"
    assert r.tokens == emitted == reference[1][:3]
    assert eng.stats.cancelled == 1
    eng.pager.evict_prefixes()
    assert eng.pager.blocks_in_use == 0


def test_cancel_mid_speculative_round(params, reference):
    """Cancelling a speculating slot releases target blocks AND the
    dense draft cache row; the survivor must stay bit-identical to the
    target-only reference."""
    eng = _paged(params, speculate=True, spec_k=4)
    victim = eng.submit(MIXED[0], max_new=MAX_NEW)
    keep = eng.submit(MIXED[1], max_new=MAX_NEW)
    eng.step()                                # prefill + first spec round
    if any(s is not None and s.rid == victim for s in eng.slots):
        assert eng.cancel(victim) is True
        assert _finish_of(eng, victim).finish_reason == "cancelled"
    tokens = _finish_of(eng, victim).tokens
    assert tokens == reference[0][:len(tokens)]
    while eng.step():
        pass
    assert _finish_of(eng, keep).tokens == reference[1]
    assert all(s is None for s in eng.slots)
    eng.pager.evict_prefixes()
    assert eng.pager.blocks_in_use == 0
    eng.pager.check_consistency()


def test_cancel_releases_adapter_pin(params):
    from repro.launch.serve import make_synthetic_adapters
    reg, names = make_synthetic_adapters(CFG, n=1)
    eng = ServeEngine(CFG, params, n_slots=2, max_len=64, paged=True,
                      kv_block_size=8, adapters=reg, decode_chunk=1)
    rid = eng.submit(MIXED[0], max_new=MAX_NEW, adapter=names[0])
    eng.step()
    assert any(reg._refs)                     # pinned while in flight
    assert eng.cancel(rid) is True
    assert not any(reg._refs)
    eng.pager.evict_prefixes()
    assert eng.pager.blocks_in_use == 0


# ---------------------------------------------------------------------------
# Execution deadlines
# ---------------------------------------------------------------------------

def test_ttft_deadline_expires_mid_prefill(params):
    clock = itertools.count(0)                # 1 virtual second per read
    eng = _paged(params, prefill_budget=8,
                 clock=lambda: float(next(clock)))
    rid = eng.submit(MIXED[2], max_new=MAX_NEW, ttft_deadline_s=2.0)
    while eng.step():
        pass
    r = _finish_of(eng, rid)
    assert r.finish_reason == "expired" and r.tokens == []
    assert all(s is None for s in eng.slots)
    eng.pager.evict_prefixes()
    assert eng.pager.blocks_in_use == 0


def test_itl_deadline_expires_stalled_stream(params, reference):
    clock = itertools.count(0)
    eng = _paged(params, decode_chunk=1, clock=lambda: float(next(clock)))
    rid = eng.submit(MIXED[0], max_new=MAX_NEW, itl_deadline_s=0.0)
    while eng.step():
        pass
    r = _finish_of(eng, rid)
    # the virtual clock advances every observation, so any gap after the
    # first token blows an inter-token deadline of zero
    assert r.finish_reason == "expired"
    assert 0 < len(r.tokens) < len(reference[0])
    assert r.tokens == reference[0][:len(r.tokens)]
    eng.pager.evict_prefixes()
    assert eng.pager.blocks_in_use == 0


def test_generous_deadlines_do_not_expire(params, reference):
    eng = _paged(params, prefill_budget=16)
    rids = [eng.submit(p, max_new=MAX_NEW, ttft_deadline_s=1e6,
                       itl_deadline_s=1e6) for p in MIXED]
    while eng.step():
        pass
    assert eng.stats.expired == 0
    for rid, want in zip(rids, reference):
        assert _finish_of(eng, rid).tokens == want
