"""ServeEngine's spans and named programs, read back from a profiler trace.

A tiny engine serves the same requests twice, once with the profiler off
and once inside ``jax.profiler.start_trace``; the trace is read back with
``jax.profiler.ProfileData``. In every serving mode the spans nest as
docs/ARCHITECTURE.md's Tracing table says, their work counts add up to the
engine's own counters, every request id they carry was submitted, every
program that ran is one of the engine's named programs, the profiler
changes no token, and each request's timestamps are ordered.
"""

import glob

import jax
import numpy as np
import pytest

from repro.configs.base import ModelConfig
from repro.launch.serve import make_synthetic_adapters
from repro.models.model import get_model
from repro.serve.engine import ServeEngine

CFG = ModelConfig(name="s", family="dense", n_layers=2, d_model=64,
                  n_heads=4, n_kv_heads=2, d_ff=128, vocab_size=256,
                  head_dim=16, vocab_pad_multiple=64, dtype="float32")

PROMPTS = [np.arange(8) + 1, np.arange(8) + 50, np.arange(45) % 256,
           np.arange(12) + 40]
LATE = np.arange(20) + 90          # arrives after two steps
MAX_NEW = 12

PROGRAMS = {"prefill", "paged_prefill", "decode_chunk", "copy_blocks",
            "write_wave", "sample_tokens", "spec_draft", "spec_verify"}
# jax.numpy indexing outside any jax.jit: a preemption's swap-out slices
# the tail block, a fast restore scatters it back
PREEMPT_OPS = {"dynamic_slice", "squeeze", "_squeeze", "scatter",
               "broadcast_in_dim", "convert_element_type"}

# span -> the span it must lie inside
PARENT = {"engine.admit": "engine.step", "engine.prefill": "engine.admit",
          "engine.prefill_sync": "engine.admit", "engine.plan": "engine.step",
          "engine.decode": "engine.step", "engine.emit": "engine.step",
          "engine.preempt": "engine.step"}
SYNC_PARENT = {"prefill": "engine.admit", "decode": "engine.step",
               "draft": "engine.step", "verify": "engine.step",
               "swap": "engine.preempt"}

MODES = {
    "dense": dict(),
    # the late request outranks the running ones: a priority preemption
    "paged_chunked": dict(paged=True, kv_block_size=8, prefill_budget=16),
    "speculative": dict(speculate=True),
    "lora": dict(paged=True, kv_block_size=8, lora=True),
}


@pytest.fixture(scope="module")
def params():
    return get_model(CFG).init(jax.random.PRNGKey(0))


def _serve(params, mode):
    """Serve PROMPTS, then LATE at priority 1 after two steps; returns the
    engine and its finished requests by rid."""
    kw = dict(MODES[mode])
    names = [None]
    if kw.pop("lora", False):
        reg, names = make_synthetic_adapters(CFG, 2)
        kw["adapters"] = reg
    eng = ServeEngine(CFG, params, n_slots=2, max_len=64, decode_chunk=2,
                      **kw)
    for j, p in enumerate(PROMPTS):
        eng.submit(p, MAX_NEW, adapter=names[j % len(names)])
    eng.step()
    eng.step()
    eng.submit(LATE, MAX_NEW, priority=1 if eng.paged else 0)
    while eng.step():
        pass
    return eng, {r.rid: r for r in eng.finished}


def _read(trace_dir):
    """(engine spans as (name, start, end, stats), names of the programs
    that ran inside ``engine.step``)."""
    pb = sorted(glob.glob(f"{trace_dir}/**/*.xplane.pb", recursive=True))[-1]
    spans, ran = [], []
    for plane in jax.profiler.ProfileData.from_file(pb).planes:
        for line in plane.lines:
            for e in line.events:
                stats = dict(e.stats)
                if e.name.startswith("engine."):
                    spans.append((e.name, e.start_ns,
                                  e.start_ns + e.duration_ns, stats))
                if "hlo_module" in stats:
                    ran.append((stats["hlo_module"], e.start_ns))
    steps = [(s, e) for n, s, e, _ in spans if n == "engine.step"]
    programs = {m for m, t in ran if any(s <= t < e for s, e in steps)}
    return spans, programs


def _inside(span, spans, parent):
    _, s, e, _ = span
    return any(n == parent and ps <= s and e <= pe
               for n, ps, pe, _ in spans)


@pytest.mark.parametrize("mode", sorted(MODES))
def test_engine_spans_match_the_engine(params, mode, tmp_path):
    _, want = _serve(params, mode)
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0        # engine spans only, no call stacks
    jax.profiler.start_trace(str(tmp_path), profiler_options=opts)
    try:
        eng, got = _serve(params, mode)
    finally:
        jax.profiler.stop_trace()
    spans, programs = _read(tmp_path)
    names = {n for n, *_ in spans}

    # the profiler changes no token
    assert {k: r.tokens for k, r in got.items()} == \
        {k: r.tokens for k, r in want.items()}
    assert {"engine.step", "engine.admit", "engine.prefill", "engine.sync",
            "engine.prefill_sync", "engine.plan", "engine.decode",
            "engine.emit"} <= names
    for sp in spans:
        name, _, _, stats = sp
        if name == "engine.sync":
            assert _inside(sp, spans, SYNC_PARENT[stats["what"]]), sp
        elif name in PARENT:
            assert _inside(sp, spans, PARENT[name]), sp
    # work counts add up to the engine's counters (the stats were reset
    # by construction: one engine per traced run)
    decode = [st for n, _, _, st in spans if n == "engine.decode"]
    prefill = [st for n, _, _, st in spans if n == "engine.prefill"]
    assert sum(st["steps"] for st in decode
               if st.get("phase") != "draft") == eng.stats.steps
    assert sum(st["tokens"] for st in prefill
               if st["kind"] != "draft") == eng.stats.prefill_tokens
    assert sum(st["hit"] for st in prefill) == eng.stats.prefix_hit_tokens
    assert sum(n == "engine.preempt" for n, *_ in spans) == \
        eng.stats.preempted
    if mode == "paged_chunked":
        assert eng.stats.preempted >= 1
        assert any(st["kind"] == "continue" for st in prefill)
    if mode == "speculative":
        assert {st.get("phase") for st in decode} == {"draft", "verify"}
    # every request id on a span was submitted
    rids = set()
    for name, _, _, st in spans:
        if "rids" in st:
            rids |= {int(x) for x in str(st["rids"]).split()}
        if "rid" in st:
            rids.add(int(st["rid"]))
    assert rids and rids <= set(got)
    # every program that ran in a step is one of the engine's named ones,
    # or an indexing op of a preemption
    named = {f"jit_{p}" for p in PROGRAMS}
    assert programs & named
    eager = PREEMPT_OPS if eng.stats.preempted else set()
    assert programs - named <= {f"jit_{p}" for p in eager}
    # timestamps, on the engine clock
    wait = 0.0
    for r in got.values():
        assert r.t_submit <= r.t_admit <= r.t_first
        wait += r.t_admit - r.t_submit
    assert eng.stats.queue_wait_s == pytest.approx(wait)


def test_decode_keys_and_prefill_prefix_on_a_session(params, tmp_path):
    """A two-turn session whose second turn hits the prefix index: each
    ``engine.prefill`` carries the cached positions its rows attend
    (``prefix``: its chunk's start) and each ``engine.decode`` the keys
    its rows attend over its steps (``keys``), both as the host knows
    them at dispatch."""
    eng = ServeEngine(CFG, params, n_slots=2, max_len=64, decode_chunk=2,
                      paged=True, kv_block_size=8, prefill_budget=16)
    first = np.arange(21) + 5
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    jax.profiler.start_trace(str(tmp_path), profiler_options=opts)
    try:
        eng.submit(first, 6)
        while eng.step():
            pass
        turn = eng.finished[0]
        second = np.concatenate([first, turn.tokens, np.arange(10) + 200])
        eng.submit(second, 5)
        while eng.step():
            pass
    finally:
        jax.profiler.stop_trace()
    spans, _ = _read(tmp_path)
    prefill = [st for n, _, _, st in spans if n == "engine.prefill"]
    decode = [st for n, _, _, st in spans if n == "engine.decode"]
    # turn 1: chunks of 16 and 5 tokens, the second after 16 of its own;
    # turn 2: 24 positions (three whole blocks of turn 1's prompt and
    # reply) from the index, then its 13 new tokens in one chunk
    assert [(st["kind"], st["tokens"], st["hit"], st["prefix"])
            for st in prefill] == [("first", 16, 0, 0),
                                   ("continue", 5, 0, 16),
                                   ("first", 13, 24, 24)]
    # token g >= 1 of a turn is decoded from position len(prompt) + g - 1
    # and attends the len(prompt) + g keys up to it
    want = sum(len(r.prompt) + g for r in eng.finished
               for g in range(1, len(r.tokens)))
    assert sum(st["keys"] for st in decode) == want
    assert [len(r.tokens) for r in eng.finished] == [6, 5]
    # one lattice for first chunks and continuations, (wave, chunk length,
    # prefix blocks): the budget-cut first chunk, the 5-token continuation
    # after 2 blocks of its own, the 13-token turn after a 3-block hit
    assert set(eng._prefill_cache) == {("paged", 1, 16, 0),
                                       ("paged", 1, 8, 2),
                                       ("paged", 1, 16, 4)}
