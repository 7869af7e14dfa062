"""One benchmark run: build the cell, warm it, measure a window, check it.

The program under test is driven only through its public serving path:
``ServeEngine.submit`` / ``step`` with tokens streamed to ``on_token``.
What the harness records (token times, per-step work, spans) it records
around those calls, from its own files.
"""

from __future__ import annotations

import json
import time
from pathlib import Path

import numpy as np

from bench import cell, traffic, weights

BENCH = Path(__file__).resolve().parent


class NoDevice(RuntimeError):
    """No accelerator, too few chips, or a chip without a peaks entry."""


def check_device(chips: int):
    import jax
    devs = jax.devices()
    d = devs[0]
    if d.platform != "tpu":
        raise NoDevice(f"no TPU: JAX's first device is {d.platform!r}")
    if len(devs) < chips:
        raise NoDevice(f"cell needs {chips} chips, JAX sees {len(devs)}")
    peaks = json.loads((BENCH / "peaks.json").read_text())["devices"]
    if d.device_kind not in peaks:
        raise NoDevice(f"device kind {d.device_kind!r} has no entry in "
                       f"bench/peaks.json")
    return devs[:chips], peaks[d.device_kind]


# ---------------------------------------------------------------------------
# build
# ---------------------------------------------------------------------------

def build_engine(conf: dict, seed: int):
    """The program's engine, serving weights drawn on the device from the
    seed in one jitted call."""
    import jax
    from repro.serve.engine import ServeEngine
    cfg = cell.model_config(conf)
    w = jax.jit(lambda k: weights.draw_all(k, conf))(weights.base_key(seed))
    params = cell.pack_params(w, conf)
    del w
    return ServeEngine(cfg, params, seed=seed, **cell.engine_kwargs(conf))


def pow2_upto(n: int):
    out, b = [], 1
    while b <= n:
        out.append(b)
        b *= 2
    if out[-1] < n:
        out.append(n)
    return out


def lattice(conf: dict, first_wave: int, prefix_blocks):
    """Prefill programs (wave, chunk length, prefix blocks) the cell's
    traffic can reach: first chunks without a cached prefix at every wave
    width up to ``first_wave``, and one-row waves against every cached
    prefix length in ``prefix_blocks`` (continuation chunks and turns that
    hit the prefix index)."""
    s = conf["serve"]
    bs, budget = s["kv_block_size"], s["prefill_budget"]
    lens = [p for p in pow2_upto(budget) if p >= bs]
    waves = sorted({min(1 << (w - 1).bit_length(), s["n_slots"])
                    for w in range(1, first_wave + 1)})
    shapes = [(wb, pl, 0) for wb in waves for pl in lens]
    shapes += [(1, pl, npb) for npb in prefix_blocks for pl in lens]
    return shapes


def bucket(n: int, lo: int, hi: int) -> int:
    """Smallest power of two >= n, clamped to [lo, hi]: how the engine
    rounds wave widths, chunk lengths and prefix lengths."""
    b = lo
    while b < n:
        b *= 2
    return min(b, hi)


def chunk_shapes(conf: dict, group):
    """Prefill programs (wave, chunk length, prefix blocks) that a group
    of prompt lengths, submitted together to an idle engine, runs under
    the chunked-prefill budget: one first-chunk wave, then one-row
    continuations of the prompt the budget cut."""
    s = conf["serve"]
    bs, budget = s["kv_block_size"], s["prefill_budget"]
    mb = -(-s["max_len"] // bs)
    top = min(mb * bs, bucket(budget, bs, mb * bs))
    left, takes = budget, []
    for n in group:
        take = n if n <= left else left // bs * bs
        takes.append(take)
        left -= take
    out = [(bucket(len(takes), 1, s["n_slots"]), bucket(max(takes), bs, top),
            0)]
    n, cur = group[-1], takes[-1]
    while cur < n:
        take = min(n - cur, budget)
        out.append((1, bucket(take, bs, top), bucket(cur // bs, 1, mb)))
        cur += take
    return out


def warm_groups(conf: dict, shapes):
    """Groups of prompt lengths that run every prefill program in
    ``shapes`` through the public path: a first-chunk wave of ``wb``
    prompts whose longest rounds to ``pl``; or, for a chunk against
    ``npb`` cached blocks, a prompt cut by the budget after a filler
    prompt, so that its last chunk of ~``pl`` tokens follows ``p`` blocks
    of its own, ``p`` rounding to ``npb``. Longest first, and a group is
    left out when earlier ones already ran what it would."""
    s = conf["serve"]
    bs, budget = s["kv_block_size"], s["prefill_budget"]
    per_chunk = budget // bs
    done, groups = set(), []
    for wb, pl, npb in sorted(shapes, key=lambda x: (-x[2], -x[1], -x[0])):
        if (wb, pl, npb) in done:
            continue
        top = pl if pl == bs else pl // 2 + 1
        if npb == 0:
            top = min(top, budget - (wb - 1))
            rest = (budget - top) // (wb - 1) if wb > 1 else 0
            group = [top] + [max(1, min(top, rest))] * (wb - 1)
        else:
            p = min(npb, (s["max_len"] - 1 - top) // bs)
            if p <= npb // 2:
                continue            # no prompt that fits max_len runs it
            c = p % per_chunk or per_chunk
            group = ([budget - c * bs] if c < per_chunk else []) + \
                [p * bs + top]
        ran = chunk_shapes(conf, group)
        if (wb, pl, npb) in ran:
            groups.append(group)
            done.update(ran)
    return groups


def warm(eng, conf: dict, shapes, seed: int):
    """Serve, through ``submit``/``step``, prompts that run every prefill
    program in ``shapes`` and every decode chunk length once, so the
    window compiles nothing. Token ids come from a stream of the seed of
    their own: no prompt of the window shares a block with them."""
    import jax
    s = conf["serve"]
    tok = traffic.rng_for(seed, 5)
    vocab = conf["vocab_size"]

    def serve(lengths, max_new):
        for n in lengths:
            eng.submit(tok.integers(0, vocab, n, dtype=np.int32), max_new)
        while eng.step():
            pass

    for group in warm_groups(conf, shapes):
        serve(group, 1)
    for n in range(1, s["decode_chunk"] + 1):
        serve([s["kv_block_size"]], n + 1)      # one decode chunk of n
    eng.finished.clear()
    jax.block_until_ready(eng.cache)


# ---------------------------------------------------------------------------
# bookkeeping around the engine
# ---------------------------------------------------------------------------

class Client:
    """What a streaming client sees: token arrival times per request."""

    def __init__(self, clock):
        self.clock = clock
        self.times = {}             # rid -> list of arrival times
        self.due = {}               # rid -> due time

    def on_token(self, req, tok):
        self.times.setdefault(req.rid, []).append(self.clock())


class WorkLog:
    """Per-step work, from the engine's counters and request states: what
    the per-layer readers turn into operations and bytes."""

    def __init__(self, eng):
        self.eng = eng
        self.decode_ctx = []        # context length of each decoded token
        self.decode_steps = 0
        self.prefill_pos = []       # (start, end) positions computed
        self.prefill_waves = 0
        self.prefill_rows = 0        # prompts whose last logits were used
        self.prefill_tokens = 0
        self.hit_tokens = 0

    def before(self):
        e = self.eng
        self._stats = (e.stats.steps, e.stats.prefill_waves,
                       e.stats.prefill_tokens, e.stats.prefix_hit_tokens)
        self._fin = len(e.finished)
        self._slots = {id(r): (r.prefill_cursor, len(r.tokens), r.prefilling)
                       for r in e.slots if r is not None}
        self._queued = {id(r) for r in e.queue}

    def after(self):
        e = self.eng
        s0 = self._stats
        self.decode_steps += e.stats.steps - s0[0]
        self.prefill_waves += e.stats.prefill_waves - s0[1]
        self.prefill_tokens += e.stats.prefill_tokens - s0[2]
        hits = e.stats.prefix_hit_tokens - s0[3]
        self.hit_tokens += hits
        for r in [r for r in e.slots if r is not None] + e.finished[self._fin:]:
            if id(r) in self._slots:
                cur0, tok0, pre = self._slots[id(r)]
            elif id(r) in self._queued and (r.prefill_cursor or r.tokens):
                # admitted this step: its first chunk starts after the
                # cached blocks the prefix index gave it. The step's hits
                # go to the first prompts admitted, each up to its last
                # whole block; exact whenever a step admits at most one
                # prompt that hits, as both cells' traffic does
                hit = min(hits, (r.prefill_cursor - 1) // e.kv_block_size
                          * e.kv_block_size)
                hits -= hit
                cur0, tok0, pre = hit, 0, True
            else:
                continue
            if pre and r.prefill_cursor > cur0:
                self.prefill_pos.append((cur0, r.prefill_cursor))
            if tok0 == 0 and r.tokens:
                self.prefill_rows += 1      # logits of a finished prompt
            # token 0 comes from prefill; token g >= 1 from the decode step
            # whose input sits at position len(prompt) + g - 1
            for g in range(max(tok0, 1), len(r.tokens)):
                self.decode_ctx.append(len(r.prompt) + g)


def serve_open(eng, reqs, seconds, client, log, span):
    """Open loop: submit each request at its due time; step while work is
    queued or running; sleep only when the engine is empty."""
    clock = client.clock
    t0 = clock()
    i, n = 0, len(reqs)
    while True:
        now = clock() - t0
        if now >= seconds:
            break
        while i < n and reqs[i].due <= now:
            with span("bench.submit"):
                rid = eng.submit(reqs[i].prompt, reqs[i].max_new,
                                 on_token=client.on_token)
            client.due[rid] = t0 + reqs[i].due
            i += 1
        busy = bool(eng.queue) or any(s is not None for s in eng.slots)
        if busy:
            log.before()
            with span("bench.step"):
                eng.step()
            log.after()
        else:
            nxt = reqs[i].due if i < n else seconds
            with span("bench.wait"):
                time.sleep(max(0.0, min(nxt, seconds) - (clock() - t0)))
    return t0


def serve_sessions(eng, sess, seconds, client, log, span):
    """Closed loop: each session sends its next turn when its reply ends."""
    clock = client.clock
    state = []                      # per session: (context, turn index)
    turn_of = {}                    # rid -> (session, turn)
    t0 = clock()

    def send(k, due):
        ctx, t = state[k]
        s = sess[k]
        if t >= len(s.tools):
            return
        prompt = np.concatenate([ctx, s.tools[t]])
        with span("bench.submit"):
            rid = eng.submit(prompt, s.replies[t], on_token=client.on_token)
        client.due[rid] = due
        turn_of[rid] = (k, t)
        state[k] = (prompt, t + 1)

    for k, s in enumerate(sess):
        state.append((s.context, 0))
    for k in range(len(sess)):
        send(k, t0)
    done = 0
    while clock() - t0 < seconds:
        if not (eng.queue or any(s is not None for s in eng.slots)):
            break                   # every session ran out of turns
        log.before()
        with span("bench.step"):
            eng.step()
        log.after()
        now = clock()
        for r in eng.finished[done:]:
            k, _ = turn_of[r.rid]
            ctx, t = state[k]
            state[k] = (np.concatenate([ctx, np.asarray(r.tokens,
                                                        np.int32)]), t)
            send(k, now)
        done = len(eng.finished)
    return t0, turn_of


def prefill_sessions(eng, sess):
    """Set-up: every session's system prompt and history go through the
    engine once (one token each, discarded), so the window starts with
    their KV in the prefix index. One at a time: with the queue empty when
    a request finishes on its first token, the step whose prefill budget
    that request spent has nothing left to admit."""
    for s in sess:
        eng.submit(s.context, 1)
        while eng.step():
            pass
    n = len(eng.finished)
    eng.finished.clear()
    return n


# ---------------------------------------------------------------------------
# end-to-end metrics
# ---------------------------------------------------------------------------

def pct(values, q):
    """The q-th percentile (0..100), linear between order statistics."""
    if not len(values):
        return float("nan")
    return float(np.percentile(np.asarray(values, np.float64), q))


def e2e(client, t0, seconds):
    """TTFT of every request due in the window (with its wait so far when
    no token came), all token gaps inside the window, tokens delivered."""
    end = t0 + seconds
    ttft, gaps, n_tok = [], [], 0
    for rid, due in client.due.items():
        if due > end:
            continue
        ts = [t for t in client.times.get(rid, []) if t <= end]
        ttft.append((ts[0] if ts else end) - due)
        gaps += list(np.diff(ts))
        n_tok += len(ts)
    return {"ttft_s": ttft, "gaps_s": gaps, "tokens": n_tok}


# ---------------------------------------------------------------------------
# one run
# ---------------------------------------------------------------------------

def capacity_check(conf: dict, mix: dict, reqs=None, sess=None):
    """The pool must hold the cell's worst case without preempting: the
    ``n_slots`` longest requests of an open mix, or every session at its
    full length. A request must also fit ``max_len``."""
    s = conf["serve"]
    bs, pool = s["kv_block_size"], s["num_blocks"] - 2   # trash + spare
    if reqs is not None:
        need = sorted((traffic.blocks(len(r.prompt) + r.max_new, bs)
                       for r in reqs), reverse=True)
        longest = max(len(r.prompt) + r.max_new for r in reqs)
        demand = sum(need[:s["n_slots"]])
    else:
        shared = traffic.blocks(mix["system"], bs)
        longest = max(traffic.session_tokens(x) for x in sess)
        demand = shared + sum(traffic.blocks(traffic.session_tokens(x), bs)
                              - shared for x in sess)
    if longest > s["max_len"] or demand > pool:
        raise ValueError(f"traffic needs {demand} blocks (pool {pool}) and "
                         f"{longest} positions (max_len {s['max_len']})")


def prefix_blocks(conf: dict):
    """Cached-prefix lengths (in blocks, pow2 buckets as the engine rounds
    them) that one-row prefill waves can carry."""
    s = conf["serve"]
    mb = -(-s["max_len"] // s["kv_block_size"])
    return pow2_upto(mb)


def first_wave(conf: dict, reqs=None) -> int:
    """Widest first-chunk wave: every prompt that fits the budget after
    the smallest ones, plus one partial chunk."""
    s = conf["serve"]
    if reqs is None:
        return 1
    left, n = s["prefill_budget"], 1
    for p in sorted(len(r.prompt) for r in reqs):
        if p > left:
            break
        left -= p
        n += 1
    return min(n, s["n_slots"])


def halves(client, t0, seconds):
    """Median TTFT (ms) of requests due in the first and second half of
    the window: a backlog that grows shows as a second half far above the
    first."""
    out = []
    for lo, hi in ((t0, t0 + seconds / 2), (t0 + seconds / 2, t0 + seconds)):
        sub = []
        for rid, due in client.due.items():
            if lo <= due < hi:
                ts = [t for t in client.times.get(rid, []) if t <= t0 + seconds]
                sub.append((ts[0] if ts else t0 + seconds) - due)
        out.append(pct(sub, 50) * 1e3)
    return out
