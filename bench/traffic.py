"""One general traffic generator; each mix is a data file of parameters.

A mix names a ``kind``:

- ``open``: independent requests on an open-loop arrival schedule at a
  fixed ``rate_rps`` (Poisson gaps), prompt and output lengths lognormal
  and clipped.
- ``sessions``: closed-loop agent sessions. Every session starts from a
  ``system`` prompt shared by all sessions plus a ``history`` of its own,
  both prefilled during set-up; each turn appends a ``tool`` result and
  asks for a reply of a drawn length; the next turn is sent when the
  reply ends.

Sizes come from fixed quantiles of each distribution, and the seed only
permutes them and draws the token ids. Every seed therefore offers the
same work in another order, so runs with different seeds differ by
ordering and arrival, not by how much there is to do.
"""

from __future__ import annotations

import dataclasses
import math
from statistics import NormalDist
from typing import List

import numpy as np


def rng_for(seed: int, stream: int) -> np.random.Generator:
    """Independent generator per purpose; any seed in [0, 2**64)."""
    return np.random.default_rng([int(seed) & 0xFFFFFFFF, int(seed) >> 32,
                                  stream])


def lognormal_set(spec: dict, n: int) -> np.ndarray:
    """``n`` lengths at the quantiles (i + 0.5) / n of a lognormal with
    ``median`` and ``sigma``, rounded and clipped to [min, max]."""
    z = np.array([NormalDist().inv_cdf((i + 0.5) / n) for i in range(n)])
    x = np.round(spec["median"] * np.exp(spec["sigma"] * z))
    return np.clip(x, spec["min"], spec["max"]).astype(np.int64)


def exponential_gaps(rate: float, n: int) -> np.ndarray:
    """``n`` inter-arrival gaps at the quantiles of an exponential with
    mean ``1/rate`` (the seeded schedule of ``poisson:<rate>``, with the
    same set of gaps for every seed)."""
    q = (np.arange(n) + 0.5) / n
    return -np.log1p(-q) / rate


@dataclasses.dataclass
class OpenRequest:
    due: float                 # seconds after the window opens
    prompt: np.ndarray         # int32 token ids
    max_new: int


def open_loop(mix: dict, seed: int, seconds: float, vocab: int
              ) -> List[OpenRequest]:
    """The requests due in a window of ``seconds``."""
    n = max(1, int(round(mix["rate_rps"] * seconds)))
    r = rng_for(seed, 1)
    prompts = r.permutation(lognormal_set(mix["prompt"], n))
    outputs = r.permutation(lognormal_set(mix["output"], n))
    gaps = r.permutation(exponential_gaps(mix["rate_rps"], n))
    due = np.cumsum(gaps) - gaps[0]
    due = due * (seconds * (n - 0.5) / n) / max(due[-1], 1e-9) \
        if n > 1 else due
    tok = rng_for(seed, 2)
    return [OpenRequest(float(d), tok.integers(0, vocab, int(p),
                                               dtype=np.int32), int(o))
            for d, p, o in zip(due, prompts, outputs)]


@dataclasses.dataclass
class Session:
    context: np.ndarray        # system + history: prefilled in set-up
    tools: List[np.ndarray]    # tool result appended by each turn
    replies: List[int]         # reply length asked for by each turn


def sessions(mix: dict, seed: int, vocab: int) -> List[Session]:
    n, turns = mix["sessions"], mix["turns"]
    r = rng_for(seed, 1)
    hist = r.permutation(lognormal_set(mix["history"], n))
    tools = r.permutation(lognormal_set(mix["tool"], n * turns))
    replies = r.permutation(lognormal_set(mix["reply"], n * turns))
    tok = rng_for(seed, 2)
    system = tok.integers(0, vocab, mix["system"], dtype=np.int32)
    out = []
    for i in range(n):
        ctx = np.concatenate([system, tok.integers(0, vocab, int(hist[i]),
                                                   dtype=np.int32)])
        t = [tok.integers(0, vocab, int(k), dtype=np.int32)
             for k in tools[i * turns:(i + 1) * turns]]
        out.append(Session(ctx, t, [int(k) for k in
                                    replies[i * turns:(i + 1) * turns]]))
    return out


def session_tokens(s: Session) -> int:
    """Context a session reaches if every turn completes."""
    return len(s.context) + sum(len(t) for t in s.tools) + sum(s.replies)


def blocks(n_tokens: int, block: int) -> int:
    return math.ceil(n_tokens / block)
