"""The check that decides ``correct``, shown to fail.

Each test skips the harness's look for a chip and drives the rest of a
run at a reduced size on the CPU. A sound run comes out correct; a run
whose timed path is broken underneath comes out not correct, once for
each fault a serving cell can have: a token altered where it is
produced, a decode step that returns its KV state unchanged, and a
program shape left out of the warm-up, which compiles in the window. The
control (the reference with int4 weight codes, one precision below the
configuration's int8, in the program's place) reads above each cell's
limit.

    JAX_PLATFORMS=cpu PYTHONPATH=src:. python -m pytest -q bench/tests
"""

import json
import os

import numpy as np
import pytest

from bench import reference, run
from bench.tests.tiny import tiny

SEED = 2 ** 33 + 5
CELLS = ("granite-3-8b-int8.chat", "glm4-9b-int8.sessions")
LIMITS = json.loads((run.ROOT / "bench" / "limits.json").read_text())


@pytest.fixture(autouse=True)
def _no_persistent_cache(tmp_path, monkeypatch):
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))


def tiny_run(workload, seconds=3.0):
    entry, conf, mix = tiny(workload)
    return run.run(workload, SEED, seconds, False, conf=conf, mix=mix,
                   entry=entry, check_chip=False, log=open(os.devnull, "w"))


@pytest.mark.parametrize("workload", CELLS)
def test_sound_run_is_correct(workload):
    res = tiny_run(workload)
    assert res["correct"], res["checks"]
    assert res["checks"]["judged_tokens"]["value"] > 0
    # the per-step work log the readers count from agrees with the
    # engine's counters: every prompt token computed, every token produced
    info = res["info"]
    assert info["work_prefill_tokens"] == info["prefill_tokens"]
    assert info["work_tokens"] == info["engine_tokens"]


@pytest.mark.parametrize("workload", CELLS)
def test_altered_token_is_not_correct(workload, monkeypatch):
    from repro.serve import engine as E
    orig = E.decode_steps

    def altered(*a, **kw):
        out = orig(*a, **kw)
        return out._replace(tokens=(out.tokens + 1) % kw["vocab_size"])
    monkeypatch.setattr(E, "decode_steps", altered)
    res = tiny_run(workload)
    assert not res["correct"]
    assert res["checks"]["logit_gap"]["value"] > \
        res["checks"]["logit_gap"]["limit"]


@pytest.mark.parametrize("workload", CELLS)
def test_unchanged_kv_state_is_not_correct(workload, monkeypatch):
    from repro.models import transformer as T
    orig = T.decode_step

    def stale(params, token, cfg, cache, **kw):
        logits, new = orig(params, token, cfg, cache, **kw)
        kept = {k: (cache[k] if k in ("k", "v") else v)
                for k, v in new.items()}
        return logits, kept
    monkeypatch.setattr(T, "decode_step", stale)
    res = tiny_run(workload)
    assert not res["correct"]


@pytest.mark.parametrize("workload", CELLS)
def test_shape_left_out_of_the_warm_up_is_not_correct(workload,
                                                      monkeypatch):
    # no prefill program warmed: the window compiles its own, and a run
    # that compiles inside its window is not a measurement
    monkeypatch.setattr(run.harness, "lattice", lambda *a: [])
    res = tiny_run(workload)
    assert res["checks"]["window_compiles"]["value"] > 0
    assert not res["correct"]


@pytest.mark.parametrize("workload", CELLS)
def test_int4_control_reads_above_the_limit(workload):
    entry, conf, mix = tiny(workload)
    rng = np.random.default_rng(0)
    seqs = [rng.integers(0, conf["vocab_size"], 300, dtype=np.int32)
            for _ in range(2)]
    at = [np.arange(100, 299)] * 2
    served, ctrl = reference.judge(SEED, conf, seqs, at, control=True)
    gap = float(np.concatenate(ctrl).max())
    assert gap > LIMITS[conf["name"]]["logit_gap"]
