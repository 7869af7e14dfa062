"""Traffic generators: determinism by seed, clipping, session structure.

    JAX_PLATFORMS=cpu PYTHONPATH=src:. python -m pytest -q bench/tests
"""

import json

import numpy as np

from bench import cell, traffic

SEED = 2 ** 40 + 12345


def test_open_loop_is_a_function_of_the_seed():
    _, _, mix = cell.resolve("granite-3-8b-int8.chat")
    a = traffic.open_loop(mix, SEED, 20, 1000)
    b = traffic.open_loop(mix, SEED, 20, 1000)
    c = traffic.open_loop(mix, SEED + 1, 20, 1000)
    assert [r.due for r in a] == [r.due for r in b]
    assert all(np.array_equal(x.prompt, y.prompt) for x, y in zip(a, b))
    assert [len(r.prompt) for r in a] != [len(r.prompt) for r in c]
    # another seed: the same set of sizes and gaps, in another order
    assert sorted(len(r.prompt) for r in a) == sorted(len(r.prompt) for r in c)
    assert sorted(r.max_new for r in a) == sorted(r.max_new for r in c)
    gaps = lambda rs: sorted(np.round(np.diff([r.due for r in rs]), 9))
    assert len(a) == len(c) == round(mix["rate_rps"] * 20)


def test_open_loop_lengths_are_clipped_and_arrivals_fill_the_window():
    _, _, mix = cell.resolve("granite-3-8b-int8.chat")
    reqs = traffic.open_loop(mix, SEED, 51, 49155)
    p = np.array([len(r.prompt) for r in reqs])
    o = np.array([r.max_new for r in reqs])
    assert p.min() >= mix["prompt"]["min"] and p.max() <= mix["prompt"]["max"]
    assert o.min() >= mix["output"]["min"] and o.max() <= mix["output"]["max"]
    due = np.array([r.due for r in reqs])
    assert due[0] == 0 and np.all(np.diff(due) >= 0) and due[-1] < 51
    assert all(r.prompt.max() < 49155 for r in reqs)


def test_lognormal_set_has_the_stated_median():
    spec = {"median": 1024, "sigma": 0.9, "min": 1, "max": 10 ** 9}
    x = traffic.lognormal_set(spec, 1001)
    assert x[500] == 1024


def test_sessions_share_the_system_prompt_and_clip_every_length():
    mix = json.loads((cell.BENCH / "traffic" / "sessions.json").read_text())
    ss = traffic.sessions(mix, SEED, 151552)
    assert len(ss) == mix["sessions"]
    sys0 = ss[0].context[:mix["system"]]
    h = mix["history"]
    for s in ss:
        assert np.array_equal(s.context[:mix["system"]], sys0)
        assert h["min"] <= len(s.context) - mix["system"] <= h["max"]
        assert len(s.tools) == len(s.replies) == mix["turns"]
        assert all(mix["tool"]["min"] <= len(t) <= mix["tool"]["max"]
                   for t in s.tools)
        assert all(mix["reply"]["min"] <= r <= mix["reply"]["max"]
                   for r in s.replies)
    again = traffic.sessions(mix, SEED, 151552)
    assert all(np.array_equal(a.context, b.context) for a, b in zip(ss, again))
    other = traffic.sessions(mix, SEED + 1, 151552)
    assert sorted(len(s.context) for s in ss) == \
        sorted(len(s.context) for s in other)
