"""Reduce a profiler trace of the measured window to the numbers the
per-layer readers and the result line need.

The window is the span of the harness's own annotations (``bench.*``) on
the host; device time is the ``XLA Ops`` line of each TPU plane, on the
same clock, without the loop and call ops that only contain others.
Busy time is the union of the device operations inside the window,
averaged over the chips; every gap in it is attributed to the
harness span the host was in at the gap's middle. Kernel time is the sum
of the device operations that ``bench/kernels.json`` maps to a layer; its
patterns match an event's whole text, the HLO instruction with its
result type.
"""

from __future__ import annotations

import glob
import json
import re
from collections import defaultdict
from pathlib import Path

BENCH = Path(__file__).resolve().parent
OPS_LINE = "XLA Ops"
SPAN_PREFIX = "bench."
IDLE_SPANS = ("bench.wait",)        # waiting for an arrival, not the engine


def kernel_table():
    table = json.loads((BENCH / "kernels.json").read_text())["layers"]
    return {layer: re.compile(pat) for layer, pat in table.items()}


def load(path):
    """Events of an ``.xplane.pb`` file (or the newest one under a
    directory): ``{"device": {plane: [(name, start, end)]}, "host":
    [(name, start, end)]}`` in nanoseconds."""
    import jax
    path = Path(path)
    if path.is_dir():
        found = sorted(glob.glob(str(path / "**" / "*.xplane.pb"),
                                 recursive=True))
        if not found:
            raise FileNotFoundError(f"no .xplane.pb under {path}")
        path = Path(found[-1])
    pd = jax.profiler.ProfileData.from_file(str(path))
    dev, host = {}, []
    for plane in pd.planes:
        if plane.name.startswith("/device:TPU:"):
            evs = []
            for line in plane.lines:
                if line.name == OPS_LINE:
                    evs += [(e.name, e.start_ns, e.start_ns + e.duration_ns)
                            for e in line.events]
            dev[plane.name] = [e for e in evs
                               if base_name(e[0]) not in CONTAINERS]
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                host += [(e.name, e.start_ns, e.start_ns + e.duration_ns)
                         for e in line.events
                         if e.name.startswith(SPAN_PREFIX)]
    return {"device": dev, "host": host}


def union(intervals):
    """Merged, sorted, non-overlapping intervals."""
    out = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return out


def gaps_of(busy, lo, hi):
    out, cur = [], lo
    for s, e in busy:
        if s > cur:
            out.append((cur, min(s, hi)))
        cur = max(cur, e)
        if cur >= hi:
            break
    if cur < hi:
        out.append((cur, hi))
    return [(s, e) for s, e in out if e > s]


def span_at(spans, t):
    """Innermost harness span covering time t (the shortest one)."""
    best = None
    for name, s, e in spans:
        if s <= t < e and (best is None or e - s < best[2] - best[1]):
            best = (name, s, e)
    return best[0] if best else "outside"


def overlap(a, b):
    """Total overlap between two sorted interval lists."""
    i = j = 0
    tot = 0
    while i < len(a) and j < len(b):
        s, e = max(a[i][0], b[j][0]), min(a[i][1], b[j][1])
        if e > s:
            tot += e - s
        if a[i][1] < b[j][1]:
            i += 1
        else:
            j += 1
    return tot


CONTAINERS = ("while", "conditional", "call")


def base_name(name: str) -> str:
    """Op name of a trace event, which holds the HLO instruction's text:
    ``%fusion.12 = f32[...] fusion(...)`` -> ``fusion``."""
    head = name.split(" = ", 1)[0].lstrip("%")
    return re.sub(r"(\.\d+)+$", "", head)


def reduce(path, *_ignored, top: int = 10):
    ev = load(path)
    spans = ev["host"]
    if not spans or not ev["device"]:
        raise ValueError("trace holds no harness spans or no TPU plane")
    lo = min(s for _, s, _ in spans)
    hi = max(e for _, _, e in spans)
    window = hi - lo
    table = kernel_table()
    busy_tot, engine_idle, wait_idle = 0, 0, 0
    kernel = defaultdict(int)
    ops = defaultdict(int)
    gaps = []
    engine_spans = union([(s, e) for n, s, e in spans
                          if n not in IDLE_SPANS])
    for plane, evs in ev["device"].items():
        inside = [(n, max(s, lo), min(e, hi)) for n, s, e in evs
                  if e > lo and s < hi]
        busy = union([(s, e) for _, s, e in inside])
        busy_tot += sum(e - s for s, e in busy)
        g = gaps_of(busy, lo, hi)
        engine_idle += overlap(g, engine_spans)
        gaps += g
        for n, s, e in inside:
            ops[base_name(n)] += e - s
            for layer, pat in table.items():
                if pat.search(n):
                    kernel[layer] += e - s
                    break
    n_dev = len(ev["device"])
    gaps.sort(key=lambda x: x[0] - x[1])
    gaps = [(span_at(spans, (s + e) // 2), e - s) for s, e in gaps[:top]]
    return {
        "window_s": window / 1e9,
        "busy_s": busy_tot / n_dev / 1e9,
        "engine_idle_s": engine_idle / n_dev / 1e9,
        "kernel_s": {k: v / n_dev / 1e9 for k, v in kernel.items()},
        "device_ops": [[k, v / n_dev / 1e9] for k, v in
                       sorted(ops.items(), key=lambda x: -x[1])[:top]],
        "idle_gaps": [[k, v / 1e9] for k, v in gaps],
    }
