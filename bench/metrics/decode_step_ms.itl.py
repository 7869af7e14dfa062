"""Device time of one decode step (ms): the ``decode_chunk`` programs'
device time in the traced window over the decode steps the window's
``engine.decode`` spans dispatched (their ``steps``). Moves itl_p95_ms."""

import sys

from bench import spans as S


def read(ctx):
    red = S.reduce_dir()
    if red is None:
        return None
    steps = sum(st.get("steps", 0) for st in S.of(red, "engine.decode")
                if "phase" not in st)
    t = red["programs_s"].get("decode_chunk")
    if not steps or not t:
        return None
    print(f"decode_step: decode_chunk {t:.4g} s over {steps} steps",
          file=sys.stderr)
    return 1e3 * t / steps
