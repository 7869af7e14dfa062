"""Share (%) of the prompt positions of the window's prefill waves that
came from the prefix index: the ``hit`` of every non-draft
``engine.prefill`` span over its ``hit`` plus ``tokens``. Moves
itl_p95_ms."""

import sys

from bench import spans as S


def read(ctx):
    red = S.reduce_dir()
    if red is None:
        return None
    waves = [st for st in S.of(red, "engine.prefill")
             if st.get("kind") != "draft"]
    hit = sum(st.get("hit", 0) for st in waves)
    total = hit + sum(st.get("tokens", 0) for st in waves)
    if not total:
        return None
    print(f"prefix_hit: {hit} of {total} prompt positions over "
          f"{len(waves)} waves", file=sys.stderr)
    return 100.0 * hit / total
