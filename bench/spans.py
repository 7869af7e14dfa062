"""Reduce what the program itself writes into a profiler trace: the
``engine.*`` host spans ``ServeEngine`` opens around each step, dispatch,
host pull and harvest (with their keyword stats), and each TPU plane's
``XLA Modules`` line, whose events name the program that ran
(``jit_decode_chunk(<id>)``).

The window is ``bench/trace.py``'s: from the first to the last harness
annotation (``bench.*``). Program time is the union of a program's module
events inside the window, averaged over the chips. Engine spans are kept
when they start inside the window, their ends clipped to it. Every idle
gap of the device is attributed, as ``trace.reduce`` does with the
harness spans alone, to the innermost ``bench.*`` or ``engine.*`` span
the host was in at the gap's middle. A trace with no engine spans (a
program that opens none) reduces to no spans, and the readers built on
it return None.
"""

from __future__ import annotations

import functools
import glob
import re
from collections import defaultdict
from pathlib import Path

from bench import trace

BENCH = Path(__file__).resolve().parent
TRACE_DIR = BENCH / ".trace"            # where bench/run.py traces
MODULES_LINE = "XLA Modules"
ENGINE_PREFIX = "engine."


def newest(path) -> Path:
    """``path``, or the newest ``.xplane.pb`` under it."""
    path = Path(path)
    found = [str(path)] if path.is_file() else sorted(
        glob.glob(str(path / "**" / "*.xplane.pb"), recursive=True))
    if not found:
        raise FileNotFoundError(f"no .xplane.pb at {path}")
    return Path(found[-1])


def program_name(event: str) -> str:
    """``jit_decode_chunk(1234)`` -> ``decode_chunk``."""
    return re.sub(r"^jit_|\(\d*\)$", "", event.strip())


def load(path):
    """``{"bench": [(name, start, end)], "engine": [(name, start, end,
    stats)], "ops": {plane: [(name, start, end)]}, "modules": {plane:
    [(name, start, end)]}}`` in nanoseconds; ops as ``trace.load`` keeps
    them."""
    import jax
    pd = jax.profiler.ProfileData.from_file(str(newest(path)))
    out = {"bench": [], "engine": [], "ops": {}, "modules": {}}
    for plane in pd.planes:
        if plane.name.startswith("/device:TPU:"):
            ops, mods = [], []
            for line in plane.lines:
                if line.name == trace.OPS_LINE:
                    ops += [(e.name, e.start_ns, e.start_ns + e.duration_ns)
                            for e in line.events
                            if trace.base_name(e.name)
                            not in trace.CONTAINERS]
                elif line.name == MODULES_LINE:
                    mods += [(e.name, e.start_ns, e.start_ns + e.duration_ns)
                             for e in line.events]
            out["ops"][plane.name] = ops
            out["modules"][plane.name] = mods
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                for e in line.events:
                    if e.name.startswith(trace.SPAN_PREFIX):
                        out["bench"].append(
                            (e.name, e.start_ns, e.start_ns + e.duration_ns))
                    elif e.name.startswith(ENGINE_PREFIX):
                        out["engine"].append(
                            (e.name, e.start_ns, e.start_ns + e.duration_ns,
                             dict(e.stats)))
    return out


def reduce(path, top: int = 10):
    """Program seconds, engine spans and span-attributed idle gaps of the
    window; None where the trace has no harness span or no TPU plane."""
    ev = load(path)
    if not ev["bench"] or not ev["ops"]:
        return None
    lo = min(s for _, s, _ in ev["bench"])
    hi = max(e for _, _, e in ev["bench"])
    n_dev = len(ev["ops"])
    progs = defaultdict(float)
    for mods in ev["modules"].values():
        by = defaultdict(list)
        for n, s, e in mods:
            if e > lo and s < hi:
                by[program_name(n)].append((max(s, lo), min(e, hi)))
        for name, iv in by.items():
            progs[name] += sum(e - s for s, e in trace.union(iv))
    spans = sorted(((n, s, min(e, hi), st) for n, s, e, st in ev["engine"]
                    if lo <= s < hi), key=lambda x: x[1])
    named = ev["bench"] + [(n, s, e) for n, s, e, _ in spans]
    gaps = []
    for evs in ev["ops"].values():
        busy = trace.union([(max(s, lo), min(e, hi)) for _, s, e in evs
                            if e > lo and s < hi])
        gaps += trace.gaps_of(busy, lo, hi)
    gaps.sort(key=lambda g: g[0] - g[1])
    return {
        "window_s": (hi - lo) / 1e9,
        "programs_s": {k: v / n_dev / 1e9 for k, v in
                       sorted(progs.items(), key=lambda x: -x[1])},
        "spans": spans,
        "idle_gaps": [[trace.span_at(named, (s + e) // 2), (e - s) / 1e9]
                      for s, e in gaps[:top]],
    }


def reduce_dir(path=None):
    """``reduce`` of the newest trace under ``path`` (``TRACE_DIR``), once
    per file."""
    try:
        pb = newest(TRACE_DIR if path is None else path)
    except FileNotFoundError:
        return None
    return _reduce_file(str(pb), pb.stat().st_mtime_ns)


@functools.lru_cache(maxsize=1)
def _reduce_file(path: str, mtime_ns: int):
    return reduce(path)


def of(red, name: str):
    """Stats of the window's spans called ``name``."""
    return [st for n, _, _, st in red["spans"] if n == name]


def host_steps_ms(red):
    """Each ``engine.step`` of the window, less the time its
    ``engine.sync`` / ``engine.prefill_sync`` children cover (ms)."""
    waits = [(s, e) for n, s, e, _ in red["spans"]
             if n in ("engine.sync", "engine.prefill_sync")]
    out = []
    for n, s, e, _ in red["spans"]:
        if n != "engine.step":
            continue
        inner = trace.union([(max(a, s), min(b, e)) for a, b in waits
                             if b > s and a < e])
        out.append((e - s - sum(b - a for a, b in inner)) / 1e6)
    return out
