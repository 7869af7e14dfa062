"""Device time per computed prompt token (us): the ``prefill`` and
``paged_prefill`` programs' device time in the traced window over the
prompt tokens the window's ``engine.prefill`` spans computed (their
``tokens``; prefix hits and draft prefills left out). Moves itl_p95_ms."""

import sys

from bench import spans as S


def read(ctx):
    red = S.reduce_dir()
    if red is None:
        return None
    tokens = sum(st.get("tokens", 0) for st in S.of(red, "engine.prefill")
                 if st.get("kind") != "draft")
    t = sum(red["programs_s"].get(p, 0.0) for p in ("prefill",
                                                     "paged_prefill"))
    if not tokens or not t:
        return None
    print(f"prefill: {t:.4g} s over {tokens} prompt tokens", file=sys.stderr)
    return 1e6 * t / tokens
