"""The warm-up runs every prefill program a cell's traffic can reach,
through the engine's public ``submit``/``step`` path.

    JAX_PLATFORMS=cpu PYTHONPATH=src:. python -m pytest -q bench/tests
"""

import pytest

from bench import cell, harness
from bench.tests.tiny import tiny

CELLS = ("granite-3-8b-int8.chat", "glm4-9b-int8.sessions")


def reachable(conf, shapes):
    """Shapes that a prompt fitting ``max_len`` can run."""
    s = conf["serve"]
    bs = s["kv_block_size"]
    out = []
    for wb, pl, npb in shapes:
        top = pl if pl == bs else pl // 2 + 1
        if npb == 0 or (npb // 2 + 1) * bs + top <= s["max_len"] - 1:
            out.append((wb, pl, npb))
    return out


@pytest.mark.parametrize("workload", CELLS)
@pytest.mark.parametrize("size", ("full", "tiny"))
def test_warm_groups_cover_the_lattice(workload, size):
    if size == "full":
        conf = cell.load_config(workload.split(".")[0])
    else:
        _, conf, _ = tiny(workload)
    shapes = harness.lattice(conf, conf["serve"]["n_slots"],
                             harness.prefix_blocks(conf))
    ran = set()
    for group in harness.warm_groups(conf, shapes):
        # every prompt but the last is prefilled whole in the first wave,
        # and the last gets at least one block of it
        budget = conf["serve"]["prefill_budget"]
        assert sum(group) <= budget or \
            budget - sum(group[:-1]) >= conf["serve"]["kv_block_size"]
        assert max(group) <= conf["serve"]["max_len"] - 1
        ran.update(harness.chunk_shapes(conf, group))
    assert set(reachable(conf, shapes)) <= ran


def test_chunk_shapes_follow_the_budget():
    _, conf, _ = tiny("granite-3-8b-int8.chat")
    s = conf["serve"]           # blocks of 16, budget 64, max_len 512
    assert (s["kv_block_size"], s["prefill_budget"]) == (16, 64)
    # a filler of 32 leaves 32 for the long prompt: 2 blocks, then chunks
    # of 64 against 2, 6 and 10 blocks, and a last one of 5 tokens
    assert harness.chunk_shapes(conf, [32, 32 + 64 * 2 + 5]) == [
        (2, 32, 0), (1, 64, 2), (1, 64, 8), (1, 16, 16)]
    assert harness.chunk_shapes(conf, [10, 10, 10]) == [(4, 16, 0)]
