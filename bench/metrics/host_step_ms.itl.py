"""Host time of one engine step (ms), 90th percentile: each ``engine.step``
span of the traced window less the time its ``engine.sync`` and
``engine.prefill_sync`` children spent waiting on the device. Also prints
the program split and the idle gaps attributed to the engine's spans.
Moves itl_p95_ms."""

import sys

import numpy as np

from bench import spans as S


def read(ctx):
    red = S.reduce_dir()
    if red is None:
        return None
    host = S.host_steps_ms(red)
    if not host:
        return None
    progs = ", ".join(f"{k} {v:.4g}" for k, v in red["programs_s"].items())
    print(f"programs (device s): {progs}", file=sys.stderr)
    print(f"idle gaps (s): {red['idle_gaps']}", file=sys.stderr)
    print(f"host_step: {len(host)} steps, median {np.median(host):.4g} ms",
          file=sys.stderr)
    return float(np.percentile(host, 90))
