"""Write a profiler trace (``.xplane.pb``) from hand-made events, so the
trace reduction can be checked on known intervals through the same
``jax.profiler.ProfileData`` reader that a chip trace goes through.

Only the fields the reader needs are encoded (tsl's ``xplane.proto``):
planes with lines of events, each event a name, a start and a duration
in nanoseconds, and optional stats (int or str).
"""

from __future__ import annotations


def _varint(n: int) -> bytes:
    n &= (1 << 64) - 1
    out = bytearray()
    while True:
        b = n & 0x7F
        n >>= 7
        if n:
            out.append(b | 0x80)
        else:
            out.append(b)
            return bytes(out)


def _int(field: int, n: int) -> bytes:
    return _varint(field << 3) + _varint(n)


def _bytes(field: int, b) -> bytes:
    if isinstance(b, str):
        b = b.encode()
    return _varint(field << 3 | 2) + _varint(len(b)) + b


def plane(pid: int, name: str, lines) -> bytes:
    """One XPlane. ``lines``: {line name: [(event name, start_ns,
    duration_ns, {stat: int | str})]}."""
    ev_ids, st_ids = {}, {}

    def ev_id(n):
        return ev_ids.setdefault(n, len(ev_ids) + 1)

    def st_id(n):
        return st_ids.setdefault(n, len(st_ids) + 1)

    body = _int(1, pid) + _bytes(2, name)
    for lid, (lname, events) in enumerate(lines.items(), 1):
        line = _int(1, lid) + _bytes(2, lname) + _int(3, 0)
        for ename, start, dur, stats in events:
            ev = _int(1, ev_id(ename)) + _int(2, start * 1000) \
                + _int(3, dur * 1000)
            for k, v in (stats or {}).items():
                val = _bytes(5, v) if isinstance(v, str) else _int(4, v)
                ev += _bytes(4, _int(1, st_id(k)) + val)
            line += _bytes(4, ev)
        body += _bytes(3, line)
    for n, i in ev_ids.items():
        body += _bytes(4, _int(1, i) + _bytes(2, _int(1, i) + _bytes(2, n)))
    for n, i in st_ids.items():
        body += _bytes(5, _int(1, i) + _bytes(2, _int(1, i) + _bytes(2, n)))
    return body


def write(path, planes) -> None:
    """``planes``: [(plane name, {line name: [events]})]."""
    space = b"".join(_bytes(1, plane(i, name, lines))
                     for i, (name, lines) in enumerate(planes, 1))
    with open(path, "wb") as f:
        f.write(space)
