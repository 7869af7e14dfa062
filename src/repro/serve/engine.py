"""Continuous-batching serving engine (the AxLLM deployment surface).

`ServeEngine(..., quantize=True)` converts trained params post-training
(zero setup, paper §I) to int8 codes; every linear then runs the fused
dequant-matmul path. The scheduler keeps `n_slots` request slots full:

Scheduler contract
------------------
- **Admission (prefill waves).** Every `step()` first admits queued
  requests into free slots. Attention-family models (`api.ragged_prefill`)
  take mixed-length prompts in ONE right-padded batch: causal masking
  keeps real tokens from seeing the pads, logits are gathered at each
  row's last real position, and the per-row cache cursor is set to the
  true length (pad KV beyond the cursor is dead and overwritten by
  decode). Recurrent families (ssm/hybrid) fold every position into
  state, so the wave is split into equal-length sub-batches — slots still
  fill in the same step.
- **Cache layout.** Slot insertion is driven by `api.cache_spec`, a
  pytree (same treedef as the cache) giving the batch axis of every leaf.
  This replaces shape-guessing (`shape[i] == n_slots`), which silently
  corrupted the cache whenever `n_slots` collided with a stacked-layer /
  head dim (e.g. xLSTM superblocks).
- **Hot loops.** Prefill is jitted and bucketed by `(wave_size,
  padded_len)`. Ragged families round both up to powers of two, so a
  steady mixed stream hits a handful of compiles
  (`stats.prefill_compiles`); recurrent families bucket wave size only —
  padded_len is the exact group length, i.e. one compile per distinct
  prompt length. Decode is one jitted chunked-scan dispatch over all
  slots with the cache buffer donated (see "Chunked decode" below).
- **Chunked decode (the hot loop's hot loop).** `step()` dispatches ONE
  on-device `lax.scan` of up to `decode_chunk` decode steps
  (`repro.serve.decode.decode_steps`): sampling, PRNG splitting and the
  per-slot stop masks all run on device, and the cache is donated into
  the scan carry. The host syncs once per chunk ([n, B] tokens + validity
  mask) instead of once per token. The chunk length is clamped to the
  largest per-slot remaining budget (and to `run()`'s step budget), so a
  wave that needs 3 tokens never pays for 8. `decode_chunk=1` reproduces
  the per-token scheduler exactly (same tokens, same stats); larger
  chunks trade admission latency (slots freed mid-chunk only refill at
  the chunk boundary) for dispatch amortization.
- **Fused projections.** `fuse_qkv=True` (engine arg or `cfg.fuse_qkv`)
  rewrites the deployed params through `api.fuse_params` after
  quantization: wq/wk/wv concatenate into one `[d, (H+2Hk)·hd]` wqkv
  QTensor (`qconcat` — exact, scales travel with their columns), gate/up
  into gate_up, so every attention/MLP block makes one pass over its
  activations with one codebook residency.
- **Stop conditions.** Per-slot: EOS token (`eos_id`, engine arg or
  `cfg.eos_id`), `max_new` tokens, or cache-full (`prompt + generated`
  reaching `max_len` — flagged `truncated`). The same three conditions
  are evaluated on device inside the chunk (the mask freezes finished
  rows) and re-derived on the host at harvest; finished slots free at
  the chunk boundary and refill on the next step.
- **Long prompts.** `long_prompt="truncate"` keeps the last
  `max_len - 1` prompt tokens (flagging `prompt_truncated`);
  `"reject"` raises at `submit()`. Nothing silently overflows the cache.
- **Multi-LoRA serving (the paper's dual-pipeline claim).** Built with
  `adapters=AdapterRegistry`, the engine serves mixed batches of the
  frozen base model and up to `max_loras` registered LoRA fine-tunes in
  the same waves and decode chunks: `submit(..., adapter="name")` pins a
  registered adapter, a per-slot `[B]` adapter-index array (−1 = base)
  threads through every prefill wave and the chunked decode scan, and
  each attention block adds the gathered low-rank bf16 delta on top of
  the untouched (quantized, fused included) base matmul — no parameter
  rewrites, no per-adapter engine. The stacked A/B tensors are jit
  *arguments*, so hot `add`/`evict` between waves reuses every compile.
  Recurrent families reject registries at engine init.
- **Paged KV cache + prefix reuse (`paged=True`).** Attention families
  can swap the dense per-slot `[n_slots, max_len]` cache for a shared
  block pool `[n_layers, num_blocks, kv_block_size]` with per-slot block
  tables (`repro.serve.paged_cache.PagedKVCache` owns the free list,
  refcounts and radix prefix index; `repro.models.attention` owns the
  device layout). `submit()` prompts are matched against the radix index
  at admission: the longest cached *full-block* prefix is taken by
  reference (refcount++) and prefill runs only on the un-cached suffix —
  rows position-offset by their hit, one joint softmax over
  [gathered prefix ‖ suffix] (`ops.prefix_attention`). Decode reads KV
  through the block table in the paged flash-decode kernel and writes to
  uniquely owned blocks (copy-on-write resolves sharing at chunk
  boundaries, batched into one device copy — a defensive invariant:
  current flows keep written blocks unshared by construction, so
  `cow_copies` stays 0 until a sharing mode like forked sampling lands).
  Finished requests publish
  their full blocks back into the index; when the pool runs dry, LRU
  index-only blocks are evicted. This extends the paper's
  computation-reuse principle from weight products to whole KV rows:
  shared system prompts / few-shot templates prefill once, not per
  request. Paged decode is token-identical to the dense path
  (tests/test_paged.py). Recurrent families reject `paged=True`.
- **Overload robustness (admission control + preemption).** The wait
  queue is a bounded priority queue (`repro.serve.scheduler.WaitQueue`):
  `submit(..., priority=, deadline_s=)` applies the engine's admission
  policy when it is full (`"block"` backpressure / `"reject"` load
  shedding / `"evict"` priority shedding — shed requests finish with
  `finish_reason="rejected"`, nothing raises), and requests whose queue
  wait exceeds their deadline expire (`"expired"`). When the block pool
  runs dry mid-flight, or a strictly-higher-priority request is waiting,
  the engine *preempts* the lowest-priority running slot instead of
  failing: full KV blocks are published into the radix index and the
  partial tail block is buffered on host (`SwapState`), the slot's
  blocks are released, and the request re-enters the queue keeping its
  original rid. Restore is a fast path (uncapped index `lookup` + tail
  scatter into a fresh block, straight back to decode) when every full
  block survived, else a recompute through the normal prefill path on
  `prompt ++ tokens` — both resume bit-identically to an uninterrupted
  decode. Admission itself is atomic (`PagedKVCache.admit`,
  plan-then-commit) and each decode window's block budget is reserved
  before any pool mutation (`plan_decode`/`can_allocate`), so no
  exception can leave blocks half-allocated; an engine-level
  `fault_hook` (see `repro.serve.chaos`) fires right before each jitted
  prefill/decode dispatch, and any exception there rolls admission back,
  requeues the wave (adapter pins intact) and leaves the decode step
  idempotently retryable.
- **Chunked prefill (`prefill_budget=N`, paged only).** Bounded step
  time, Sarathi-style: every `step()` spends at most N prompt tokens on
  prefill work (first chunks and continuations combined), so a long
  prompt is consumed over several steps *interleaved with decode chunks*
  instead of stalling every running stream behind one all-or-nothing
  wave. A partially prefilled slot carries a block-aligned
  `prefill_cursor` (non-final chunks are floored to whole KV blocks),
  allocates blocks per chunk (`PagedKVCache.extend`, all-or-nothing) and
  publishes each consumed chunk into the radix index immediately; its
  block-table row is masked to the trash block for decode dispatches
  (the scan writes KV unconditionally for every row). Mid-prefill
  preemption publishes the consumed prefix and re-admits through the
  normal prefill path, where the radix match re-hits it —
  token-identical, no swap state. First chunks and continuations are
  two plans feeding ONE paged wave (`_paged_wave`): admission plans a
  radix match, a budget cut and `pager.admit`; continuation plans a
  budget cut and `pager.extend`, preempting victims while the pool
  cannot cover the wave. The wave prefills each row's chunk against the
  blocks its table starts with (a prefix hit, or the slot's own
  consumed blocks), rolls a faulted dispatch back row by row, and
  harvests. Continuations dispatch first, so a step runs at most two
  paged waves. Every wave draws its shape from one lattice — pow2 width
  up to `n_slots`, pow2 chunk length capped by the budget, pow2 prefix
  blocks — so compile count is bounded and independent of arrival
  pattern without padding single-request chunks to the full slot set.
  Greedy output is bit-identical to unbudgeted serving.
- **Settling a slot.** Wherever tokens are appended to a running slot
  (a prefill wave's first token, a decode chunk, a speculative round),
  one path (`_settle`) stamps `t_last`, streams them, and on a stop or a
  cancel publishes the slot's KV, releases it and finishes the request.
- **Streaming + cancellation.** `submit(on_token=...)` fires the
  callback per token at chunk harvest (prefill first-token, decode
  chunk, speculative round); `stream()` wraps submit + step into a
  generator. `cancel(rid)` — or the callback raising `StopStream` —
  tears a request down mid-stream: slot, KV blocks, and adapter pin
  released, published prefix blocks kept for other requests,
  `finish_reason="cancelled"` with the partial tokens retained.
  `t_first` is stamped at actual first-token *emission* (the TTFT base).
- **Execution deadlines.** Beyond `deadline_s` (queue wait),
  `submit(ttft_deadline_s=, itl_deadline_s=)` bound time-to-first-token
  and the inter-token gap *while running*: a request that blows either
  finishes with `finish_reason="expired"`, keeps its partial tokens,
  and frees every resource — checked each step against the injectable
  clock, wherever the request sits (queued, mid-prefill, or decoding).
- **Speculative decoding (`speculate=True`).** The quantization ladder
  doubles as a draft model: `core.quantization.derive_draft_params`
  re-quantizes the raw weights to `draft_bits` (affine/codebook, or the
  shift-add reparameterization via `draft_mode="shiftadd"`) once at
  init, and each round the draft proposes up to `spec_k` greedy tokens
  from its own private dense cache, the serving-precision target
  verifies all of them in ONE teacher-forced chunked-scan dispatch
  (`repro.serve.decode.verify_steps`), and the engine emits the longest
  agreeing prefix plus the target's correction token
  (`repro.serve.speculative`). Output is bit-identical to target-only
  greedy by construction — acceptance only moves throughput. Rollback
  of optimistically written KV is a host cursor reset (dense) or
  `PagedKVCache.truncate` (paged, whole trailing blocks back to the
  pool, published prefixes untouched). Requires `greedy=True` and an
  attention family; preempted speculating slots restore by recompute
  (the fast swap path would miss the draft cache).
- **Tracing.** Every step, prefill and decode dispatch, host pull of
  device results, pager plan, harvest and preemption opens a
  `jax.profiler.TraceAnnotation` (`engine.step`, `engine.admit`,
  `engine.prefill`, `engine.sync`, `engine.prefill_sync`, `engine.plan`,
  `engine.decode`, `engine.emit`, `engine.preempt`) whose keyword stats
  carry the work done (`rows`, `tokens`, `hit`, `steps`, `cow`), the
  cached positions a prefill wave's rows attend (`prefix`), the keys a
  decode dispatch's rows attend over its steps (`keys`, from the
  positions and budgets the host holds at dispatch) and the request ids
  (`rids`, space-separated). Every jitted program is named
  (`jit_prefill`, `jit_paged_prefill`, `jit_decode_chunk`,
  `jit_copy_blocks`, `jit_write_wave`, `jit_sample_tokens`,
  `jit_spec_draft`, `jit_spec_verify`). Without a profiler session a
  span costs about a microsecond and records nothing.
- **Stats.** `engine.stats` tracks admitted/finished/truncated requests,
  queue wait (`queue_wait_s`, the sum of each request's `t_admit -
  t_submit`), decode steps/tokens, prefill waves/tokens/compiles (plus
  wall time),
  LoRA-carrying requests, mean slot occupancy, — in paged mode —
  `prefix_hit_tokens` / `blocks_in_use` / `cow_copies`, and — under
  speculation — drafted/accepted token counts with `acceptance_rate`
  and `accepted_tokens_per_step` (emitted per slot-round, > 1 means
  drafting beats one-token-per-step);
  `stats.as_dict()` feeds `benchmarks/serve_bench.py` and the chip
  benchmark's `bench/run.py`.

`generate()` returns token lists for all submitted prompts; requests
still in flight when `max_steps` runs out come back with their partial
tokens and `truncated=True` (`return_requests=True` exposes the flags).
"""

from __future__ import annotations

import contextlib
import dataclasses
import functools
import math
import time
from typing import List, NamedTuple, Optional

import numpy as np

import jax
import jax.numpy as jnp
from jax.profiler import TraceAnnotation

from repro.core.axllm_linear import deploy_quantize
from repro.core.quantization import QuantConfig, derive_draft_params
from repro.dist import sharding as shd
from repro.models.model import ModelAPI, get_model
from repro.serve.adapters import AdapterRegistry
from repro.serve.decode import decode_steps, verify_steps
from repro.serve.paged_cache import TRASH_BLOCK, PagedKVCache
from repro.serve.scheduler import WaitQueue, pick_victim, prefill_chunk
from repro.serve.speculative import accept_length, round_k


class StopStream(Exception):
    """Raise from an ``on_token`` callback to cancel the stream.

    The engine catches it at the emission site and tears the request
    down exactly like :meth:`ServeEngine.cancel`: slot freed, KV blocks
    released (published prefixes survive in the radix index), adapter
    pin dropped, ``finish_reason="cancelled"``. Tokens appended before
    the raise stay on the request.
    """


@dataclasses.dataclass
class SwapState:
    """Host-side remainder of a preempted slot's KV (paged mode).

    Full blocks are published into the radix index at swap-out (base
    requests), so the swap state only carries what the index cannot:
    the partial tail block's KV rows, copied to host. A restore first
    tries the fast path (uncapped index lookup + tail scatter back into
    a fresh block — no recompute); if any full block was LRU-evicted
    meanwhile it falls back to recomputing the whole KV through the
    normal prefill path, which is what dense mode and LoRA requests
    (whose adapter-specific KV is never indexed) always do.
    """
    seq_len: int                      # KV positions covered at swap-out
    full_blocks: int                  # seq_len // block_size
    tail: Optional[dict] = None      # pool-leaf name -> host [L, bs, ...]


@dataclasses.dataclass
class Request:
    """One serving request: prompt in, generated ``tokens`` out.

    adapter: name of a registered LoRA adapter to decode with (None =
    base model). The engine acquires the adapter at ``submit`` and
    releases it when the request finishes, so a named adapter cannot be
    evicted out from under an in-flight request.
    """
    rid: int
    prompt: np.ndarray            # [S] int32 (post long-prompt policy)
    max_new: int = 32
    tokens: list = dataclasses.field(default_factory=list)
    done: bool = False
    truncated: bool = False           # generation cut short (cache/steps)
    prompt_truncated: bool = False    # prompt clipped by long_prompt policy
    adapter: Optional[str] = None     # LoRA adapter name (None = base)
    priority: int = 0                 # larger = admitted first, may preempt
    deadline_s: Optional[float] = None    # max queue wait before expiry
    finish_reason: Optional[str] = None   # eos / max_new / cache_full /
                                          # rejected / expired / cancelled
    t_submit: float = 0.0             # engine-clock submit time
    t_admit: Optional[float] = None   # first seated (not a restore)
    t_first: Optional[float] = None   # first-token *emission* time (TTFT)
    t_last: Optional[float] = None    # last-token time (ITL base)
    preemptions: int = 0              # times swapped out of a slot
    _swap: Optional[SwapState] = None     # host tail KV while preempted
    # streaming: per-token callback fired at chunk harvest; raising
    # StopStream from it cancels the request mid-stream
    on_token: Optional[object] = None
    # execution deadlines (beyond deadline_s's queue-wait bound)
    ttft_deadline_s: Optional[float] = None   # submit -> first emission
    itl_deadline_s: Optional[float] = None    # max gap between tokens
    # chunked prefill: a seated slot may hold only a prefix of its prompt
    prefilling: bool = False          # seated but prompt not fully consumed
    prefill_cursor: int = 0           # admission-seq tokens consumed so far
    _emitted: int = 0                 # tokens already streamed to on_token
    _admitted: bool = False           # counted in stats.admitted (vs restore)


@dataclasses.dataclass
class EngineStats:
    admitted: int = 0
    finished: int = 0
    truncated: int = 0
    steps: int = 0                    # device decode steps executed
    decode_tokens: int = 0            # valid tokens harvested
    decode_chunks: int = 0            # host round-trips (dispatches)
    prefill_waves: int = 0
    prefill_tokens: int = 0
    prefill_compiles: int = 0
    prefill_wall_s: float = 0.0       # host wall time inside prefill waves
    queue_wait_s: float = 0.0         # sum of t_admit - t_submit
    lora_requests: int = 0            # admitted requests carrying an adapter
    occupancy_sum: float = 0.0        # sum over steps of active/n_slots
    # paged-KV mode (prefix reuse): prompt tokens whose KV came from the
    # radix index instead of being recomputed, live pool blocks, and
    # copy-on-write block copies performed before decode chunks
    prefix_hit_tokens: int = 0
    blocks_in_use: int = 0
    cow_copies: int = 0
    # robustness: admission-control and preemption outcomes
    rejected: int = 0                 # shed by the admission policy
    expired: int = 0                  # deadline passed (queued or mid-run)
    preempted: int = 0                # swap-outs of running slots
    restored: int = 0                 # re-admissions after preemption
    fast_restores: int = 0            # restores that skipped recompute
    # streaming + chunked prefill
    cancelled: int = 0                # torn down by cancel()/StopStream
    prefill_chunks: int = 0           # budgeted prefill chunks executed
    preempted_prefill: int = 0        # preemptions of mid-prefill slots
    # speculative decoding (speculate=True): draft/verify round outcomes
    spec_rounds: int = 0              # engine-level draft+verify rounds
    spec_slot_rounds: int = 0         # sum over rounds of speculating slots
    drafted_tokens: int = 0           # draft proposals checked by the target
    accepted_draft_tokens: int = 0    # proposals the target agreed with
    spec_emitted_tokens: int = 0      # tokens appended by spec rounds

    @property
    def mean_occupancy(self) -> float:
        return self.occupancy_sum / self.steps if self.steps else 0.0

    @property
    def tokens_per_step(self) -> float:
        return self.decode_tokens / self.steps if self.steps else 0.0

    @property
    def acceptance_rate(self) -> float:
        """Fraction of draft proposals the target confirmed."""
        return (self.accepted_draft_tokens / self.drafted_tokens
                if self.drafted_tokens else 0.0)

    @property
    def accepted_tokens_per_step(self) -> float:
        """Tokens emitted per slot-round (one draft+verify round of one
        slot). Always >= 1 when rounds ran — each round emits at least
        the target's own token — and > 1 iff speculation accepted
        anything, which is the serve-bench gate for the feature paying
        for itself."""
        return (self.spec_emitted_tokens / self.spec_slot_rounds
                if self.spec_slot_rounds else 0.0)

    def as_dict(self) -> dict:
        d = dataclasses.asdict(self)
        d["mean_occupancy"] = self.mean_occupancy
        d["tokens_per_step"] = self.tokens_per_step
        d["acceptance_rate"] = self.acceptance_rate
        d["accepted_tokens_per_step"] = self.accepted_tokens_per_step
        return d


def sample_tokens(logits, rng, *, greedy: bool, vocab_size: int):
    """On-device sampling: greedy/sampled is jit-static, and the sampled
    path threads a freshly split PRNG key per call instead of re-seeding
    from host state. Returns (tokens [B] int32, advanced key)."""
    if logits.ndim == 3:              # [B, S, V]: sample the last position
        logits = logits[:, -1, :]
    logits = logits[..., :vocab_size]
    if greedy:
        return jnp.argmax(logits, -1).astype(jnp.int32), rng
    rng, k = jax.random.split(rng)
    return jax.random.categorical(k, logits).astype(jnp.int32), rng


def write_wave(cache, wave_cache, src, dst, *, cache_spec):
    """Copy wave rows ``src`` into engine slots ``dst`` on each leaf's
    declared batch axis (``api.cache_spec``)."""
    def put(full, one, ax):
        vals = jnp.take(one, src, axis=ax)
        idx = (slice(None),) * ax + (dst,)
        return full.at[idx].set(vals.astype(full.dtype))
    return jax.tree_util.tree_map(put, cache, wave_cache, cache_spec)


def copy_blocks(cache, src, dst, *, pool_leaves):
    """Copy pool blocks ``src`` onto ``dst`` on every pool leaf — the
    device half of copy-on-write (one batched dispatch per chunk)."""
    new = dict(cache)
    for name in pool_leaves:
        new[name] = cache[name].at[:, dst].set(cache[name][:, src])
    return new


def _bind(fn, **kw):
    """``functools.partial`` that keeps ``fn``'s name: a jitted function's
    name is its program's (``jit_<name>``) in profiler traces."""
    return functools.update_wrapper(functools.partial(fn, **kw), fn)


def _rids(reqs) -> str:
    """Request ids of a dispatch as one span stat (spaces: a comma would
    split the profiler's stat encoding)."""
    return " ".join(str(r.rid) for r in reqs)


def _attended(pos: int, steps: int) -> int:
    """Keys one row attends over ``steps`` decode steps from cache
    position ``pos``: step j writes position pos + j and attends the
    pos + j + 1 keys up to it.

    >>> _attended(10, 3)            # 11 + 12 + 13
    36
    """
    return steps * (2 * pos + steps + 1) // 2


def _pow2_bucket(n: int, lo: int, hi: int) -> int:
    """Smallest power of two >= n, floored at lo, capped at hi.

    >>> _pow2_bucket(5, 1, 16)
    8
    >>> _pow2_bucket(3, 8, 64)      # floored at lo
    8
    >>> _pow2_bucket(100, 8, 64)    # capped at hi
    64
    """
    b = lo
    while b < n:
        b *= 2
    return min(b, hi)


class _Row(NamedTuple):
    """One row of a paged prefill wave: ``req`` in ``slot`` prefills
    ``seq[cursor:cursor + take]`` against the ``cursor // block`` blocks
    its slot's table starts with."""
    slot: int
    req: Request
    seq: np.ndarray
    cursor: int
    take: int


class ServeEngine:
    """Continuous-batching scheduler over ``n_slots`` request slots.

    Construction deploys ``params`` for serving: ``quantize=True``
    converts weight matrices to ``quant_bits`` AxLLM codes
    (`deploy_quantize`; ``quant_bits=None`` falls back to
    ``cfg.quant_bits``, ``quant_mode`` picks affine vs codebook
    alphabets), ``fuse_qkv`` rewrites them through
    ``api.fuse_params`` (wqkv / gate_up), and ``adapters`` attaches an
    :class:`~repro.serve.adapters.AdapterRegistry` for multi-LoRA
    serving (attention families only). ``decode_chunk`` sets the
    on-device scan length per decode dispatch; ``eos_id`` /
    ``long_prompt`` / ``max_len`` define the stop conditions (see the
    module docstring for the full scheduler contract).

    ``paged=True`` swaps the dense per-slot cache for the block-paged
    pool with radix-tree prefix reuse: ``kv_block_size`` tokens per
    block (power of two), ``num_blocks`` pool blocks (default
    ``2 * n_slots * ceil(max_len / kv_block_size) + 2`` — a full dense
    equivalent per slot, the trash block, a copy-on-write spare, and as
    much again for retained prefixes), ``prefix_cache=False`` keeps the
    paging but disables the radix index.

    ``mesh`` (a `jax.sharding.Mesh`, e.g. from
    :func:`repro.launch.mesh.make_host_mesh`) turns on tensor-parallel
    serving: quantized params are placed column-parallel (wqkv/gate_up)
    / row-parallel (wo/down) over the mesh's "model" axis, the KV cache
    (dense or paged pool) shards along kv-heads when they divide the
    axis — otherwise along the sequence dim, which routes decode through
    the fused shard_map kernel ``decode_attention_seqsharded`` — and
    every prefill/decode dispatch traces under the mesh context so GSPMD
    partitions the whole hot path. A mesh of total size 1 compiles to
    exactly the single-device program. Tokens are identical to unmeshed
    serving across quantize/reuse/fused/LoRA/paged modes
    (tests/test_sharded_serve.py).

    Serve with ``submit(prompt, max_new, adapter=...)`` + ``step()`` /
    ``run()``, or the one-shot ``generate(prompts, ...)``.
    """

    def __init__(self, cfg, params, *, n_slots: int = 4, max_len: int = 512,
                 quantize: bool = False, quant_bits: Optional[int] = None,
                 quant_mode: str = "affine",
                 impl: str = "auto", greedy: bool = True, seed: int = 0,
                 eos_id: Optional[int] = None,
                 long_prompt: str = "truncate",
                 decode_chunk: Optional[int] = None,
                 fuse_qkv: Optional[bool] = None,
                 adapters: Optional[AdapterRegistry] = None,
                 paged: bool = False, kv_block_size: int = 16,
                 num_blocks: Optional[int] = None,
                 prefix_cache: bool = True,
                 mesh=None,
                 max_queue: Optional[int] = None,
                 admission: str = "block",
                 clock=None,
                 fault_hook=None,
                 speculate: bool = False, spec_k: int = 4,
                 draft_bits: int = 4, draft_mode: str = "affine",
                 prefill_budget: Optional[int] = None):
        if cfg.is_encoder_decoder:
            raise NotImplementedError(
                "ServeEngine drives token-only prefill; encoder-decoder "
                "serving needs a frames ingress (future PR)")
        if long_prompt not in ("truncate", "reject"):
            raise ValueError(f"long_prompt must be 'truncate' or 'reject', "
                             f"got {long_prompt!r}")
        if max_len < 2:
            raise ValueError("max_len must be >= 2 (prompt + 1 decode step)")
        self.cfg = cfg
        self.api: ModelAPI = get_model(cfg, impl=impl)
        raw_params = params               # pre-quantization, for the draft
        if quantize:
            bits = cfg.quant_bits if quant_bits is None else quant_bits
            params = deploy_quantize(
                params, QuantConfig(bits=bits, mode=quant_mode,
                                    granularity="per_channel"))
        fuse = cfg.fuse_qkv if fuse_qkv is None else fuse_qkv
        if fuse:
            if self.api.fuse_params is None:
                raise ValueError(f"family {cfg.family!r} has no fused-"
                                 f"projection rewrite")
            params = self.api.fuse_params(params)
        self.params = params
        self.n_slots = n_slots
        self.max_len = max_len
        self.greedy = greedy
        self.eos_id = eos_id if eos_id is not None else cfg.eos_id
        self.long_prompt = long_prompt
        dc = cfg.decode_chunk if decode_chunk is None else decode_chunk
        if dc < 1:
            raise ValueError(f"decode_chunk must be >= 1, got {dc}")
        self.decode_chunk = dc
        self.registry = adapters
        if adapters is not None:
            self._validate_adapters(adapters)
        # per-slot LoRA row into registry.stacked; -1 = base-only. Threaded
        # through every prefill wave and decode chunk as a [B] jit argument.
        self.adapter_slots = np.full((n_slots,), -1, np.int32)
        self.rng = jax.random.PRNGKey(seed)
        self.paged = paged
        self.kv_block_size = kv_block_size
        self.prefix_cache = prefix_cache
        self.prefill_budget = prefill_budget
        if prefill_budget is not None:
            if not paged:
                raise ValueError(
                    "prefill_budget requires paged=True: chunked prefill "
                    "allocates and publishes KV one block at a time, which "
                    "the dense per-slot cache cannot express")
            if speculate:
                raise ValueError(
                    "prefill_budget is incompatible with speculate=True: "
                    "the draft cache is dense and prefills whole sequences "
                    "in one wave, so a mid-prefill slot would enter a "
                    "speculative round with no draft KV behind its cursor — "
                    "serve chunked prefill without speculation (or "
                    "speculation without a budget)")
            if prefill_budget < kv_block_size:
                raise ValueError(
                    f"prefill_budget={prefill_budget} is below one KV block "
                    f"(kv_block_size={kv_block_size}): a non-final chunk is "
                    "floored to whole blocks, so no chunk could ever make "
                    "progress")
        # per-step chunked-prefill ledger (reset at the top of _step)
        self._prefill_left = prefill_budget
        self._prefill_progress = False
        if paged:
            if self.api.init_paged_cache is None:
                raise ValueError(
                    f"family {cfg.family!r} has no paged KV cache path: "
                    "recurrent/enc-dec state folding exposes no "
                    "per-position KV to page — serve it with paged=False "
                    "(attention families only)")
            self.max_blocks = math.ceil(max_len / kv_block_size)
            self.num_blocks = num_blocks if num_blocks is not None \
                else 2 * n_slots * self.max_blocks + 2
            self.pager = PagedKVCache(
                n_slots=n_slots, n_blocks=self.num_blocks,
                block_size=kv_block_size,
                max_blocks_per_slot=self.max_blocks,
                prefix_cache=prefix_cache)
            self.cache = self.api.init_paged_cache(
                n_slots, self.num_blocks, kv_block_size, self.max_blocks)
            self._pool_leaves = [
                k for k, ax in self.api.paged_cache_spec.items() if ax == 1]
            self._copier = jax.jit(
                _bind(copy_blocks, pool_leaves=tuple(self._pool_leaves)),
                donate_argnums=(0,))
        else:
            self.pager = None
            self.cache = self.api.init_cache(n_slots, max_len)
        self._validate_cache_spec()
        self.speculate = speculate
        self.spec_k = spec_k
        self.draft_bits = draft_bits
        self.draft_mode = draft_mode
        self.draft_params = None
        self.draft_cache = None
        if speculate:
            if not greedy:
                raise ValueError(
                    "speculate=True requires greedy=True: the accept rule "
                    "compares the target's deterministic argmax against "
                    "the draft's — sampled verification needs a "
                    "rejection-sampling scheme this engine does not "
                    "implement")
            if self.api.init_paged_cache is None:
                raise ValueError(
                    f"family {cfg.family!r} has no speculative path: "
                    "rollback needs position-addressable KV (truncate a "
                    "cursor / block table); recurrent state folding "
                    "cannot rewind k rejected positions (attention "
                    "families only)")
            if spec_k < 1:
                raise ValueError(f"spec_k must be >= 1, got {spec_k}")
            # the draft is derived from the ORIGINAL params: deriving it
            # from already-quantized target weights would compound two
            # quantization errors and crater the acceptance rate
            draft = derive_draft_params(raw_params, bits=draft_bits,
                                        mode=draft_mode)
            if fuse:
                draft = self.api.fuse_params(draft)
            self.draft_params = draft
            # the draft cache is ALWAYS dense, even when the target pages:
            # draft KV is private scratch (never shared, never published,
            # never swapped), so block bookkeeping would buy nothing
            self.draft_cache = self.api.init_cache(n_slots, max_len)
        self.mesh = mesh
        self._rules = None
        if mesh is not None:
            self._rules = shd.serve_rules_for(
                mesh, getattr(cfg, "n_kv_heads", 1) or 1)
            self._place_on_mesh()
        self.slots: List[Optional[Request]] = [None] * n_slots
        # bounded priority wait queue; max_queue=None + "block" reproduces
        # the pre-robustness unbounded FIFO for closed-loop callers
        self.queue = WaitQueue(max_queue, admission)
        # injectable clock (deadlines/TTFT) and fault hook (chaos harness:
        # called with "prefill"/"decode" right before each jit dispatch)
        self._clock = time.monotonic if clock is None else clock
        self.fault_hook = fault_hook
        self.finished: List[Request] = []
        self._rid = 0
        self.stats = EngineStats()
        self._chunk_fns = {}          # (n, greedy) -> jit scan-decode fn
        self._spec_fns = {}           # k -> (jit draft scan, jit verify scan)
        self._prefill_cache = {}      # (wave_bucket, padded_len) -> jit fn
        self._writer = jax.jit(
            _bind(write_wave, cache_spec=self.api.cache_spec),
            donate_argnums=(0,))
        self._sampler = jax.jit(sample_tokens,
                                static_argnames=("greedy", "vocab_size"))

    def _validate_cache_spec(self):
        if self.paged:
            spec = self.api.paged_cache_spec
            # pool leaves carry the block axis (shared, no batch dim);
            # pos/block_tables stay slot-leading
            for name, ax in spec.items():
                want = self.num_blocks if ax == 1 else self.n_slots
                got = self.cache[name].shape[ax]
                if got != want:
                    raise ValueError(
                        f"paged_cache_spec says axis {ax} of {name!r} is "
                        f"the {'block' if ax == 1 else 'slot'} axis but "
                        f"shape {self.cache[name].shape} has {got} != "
                        f"{want} there")
            return
        spec = self.api.cache_spec
        if spec is None:
            raise ValueError("ModelAPI.cache_spec missing: the engine needs "
                             "the batch axis of every cache leaf")

        def check(leaf, ax):
            if leaf.shape[ax] != self.n_slots:
                raise ValueError(
                    f"cache_spec says batch axis {ax} but leaf shape "
                    f"{leaf.shape} has {leaf.shape[ax]} != n_slots="
                    f"{self.n_slots} there")
            return leaf

        jax.tree_util.tree_map(check, self.cache, spec)

    def _mesh_ctx(self):
        """Sharding context for jit trace/dispatch sites: binds the
        engine's (mesh, rules) so `shard()` constraints and the
        seq-sharded decode routing see the serving layout. No-op without
        a mesh — the single-device program is untouched."""
        if self.mesh is None:
            return contextlib.nullcontext()
        return shd.activate(self.mesh, self._rules)

    def _place_on_mesh(self):
        """Commit params / KV cache / stacked LoRA tensors to the mesh.

        Params use `param_specs` inference (column-parallel wqkv/gate_up,
        row-parallel wo/down — one all-reduce per block under GSPMD);
        the cache uses `cache_specs` (dense: kv-heads or sequence dim per
        the rule set) or `paged_cache_specs` (pool sharded along heads
        only; the pager's block address space stays whole per shard, so
        block tables and copy-on-write copies are shard-oblivious).
        Stacked adapters place with replicated A / out-sharded B."""
        mesh, rules = self.mesh, self._rules
        pspecs = shd.param_specs(self.params, mesh, rules)
        self.params = jax.tree_util.tree_map(jax.device_put, self.params,
                                             pspecs)
        if self.paged:
            cspecs = shd.paged_cache_specs(self.cache, mesh, rules)
        else:
            cspecs = shd.cache_specs(self.cache, mesh, self.n_slots,
                                     self.max_len, rules=rules)
        self.cache = jax.tree_util.tree_map(jax.device_put, self.cache,
                                            cspecs)
        if self.registry is not None:
            self.registry.place(
                shd.adapter_specs(self.registry.stacked, mesh, rules))
        if self.speculate:
            # the draft rides the same layout rules: same param paths
            # (column/row-parallel projections) and a dense cache placed
            # exactly like a dense target cache would be
            dspecs = shd.param_specs(self.draft_params, mesh, rules)
            self.draft_params = jax.tree_util.tree_map(
                jax.device_put, self.draft_params, dspecs)
            dcspecs = shd.cache_specs(self.draft_cache, mesh, self.n_slots,
                                      self.max_len, rules=rules)
            self.draft_cache = jax.tree_util.tree_map(
                jax.device_put, self.draft_cache, dcspecs)

    def _constrain_wave(self, wave_cache, batch: int):
        """Pin a prefill wave cache (traced, inside jit) to the engine
        cache's layout, so the slot-scatter in `_write_wave` moves shards
        instead of rematerializing the wave on one device. Identity
        without a mesh."""
        if self.mesh is None:
            return wave_cache
        specs = shd.cache_specs(wave_cache, self.mesh, batch, self.max_len,
                                rules=self._rules)
        return jax.tree_util.tree_map(jax.lax.with_sharding_constraint,
                                      wave_cache, specs)

    def _validate_adapters(self, reg: AdapterRegistry):
        """Adapter-aware deployment validation: the family must expose the
        LoRA delta-pipeline hooks and the registry must have been built
        against a dimensionally identical config (the stacked A/B tensors
        scan with this model's layers)."""
        if not self.api.supports_lora:
            raise ValueError(
                f"family {self.cfg.family!r} has no multi-LoRA serving "
                "path: its recurrent state folding offers no per-slot "
                "projection hook for the delta pipeline (attention "
                "families only)")
        from repro.serve.adapters import target_dims
        if reg.cfg.n_layers != self.cfg.n_layers:
            raise ValueError(
                f"adapter registry built for n_layers={reg.cfg.n_layers} "
                f"but engine serves n_layers={self.cfg.n_layers}")
        for t in reg.targets:
            if target_dims(reg.cfg, t) != target_dims(self.cfg, t):
                raise ValueError(
                    f"adapter registry target {t!r} dims "
                    f"{target_dims(reg.cfg, t)} != model dims "
                    f"{target_dims(self.cfg, t)}")

    # -- request management ---------------------------------------------------
    def _now(self) -> float:
        return self._clock()

    def submit(self, prompt, max_new: int = 32,
               adapter: Optional[str] = None, priority: int = 0,
               deadline_s: Optional[float] = None,
               on_token=None,
               ttft_deadline_s: Optional[float] = None,
               itl_deadline_s: Optional[float] = None) -> int:
        """Queue a prompt ([S] ints) for generation; returns a request id.

        adapter: name of a registered LoRA adapter to serve this request
        with (requires the engine's ``adapters=AdapterRegistry``; unknown
        names raise KeyError here, not mid-stream). The adapter is pinned
        until the request finishes.

        priority: larger admits first; a strictly-higher-priority arrival
        may preempt a running lower-priority slot (swap-out/restore).
        deadline_s: max seconds the request may *wait in the queue*; past
        it the request finishes with ``finish_reason="expired"`` and no
        tokens. When the queue is at ``max_queue`` the engine's admission
        policy decides: "block" drives ``step()`` until a position frees,
        "reject" / "evict" shed a request (``finish_reason="rejected"``)
        without raising — read the outcome off the finished list/stats.

        on_token: streaming callback ``f(request, token)`` fired for each
        token as the engine harvests it (chunk boundaries, not at finish).
        Raising :class:`StopStream` from it cancels the request mid-stream
        — slot, KV blocks, and adapter pin released, tokens appended so
        far kept, ``finish_reason="cancelled"``. Any other exception
        propagates out of ``step()``.
        ttft_deadline_s / itl_deadline_s: *execution* deadlines enforced
        mid-run (``deadline_s`` only bounds queue wait): a request that
        has not emitted its first token ``ttft_deadline_s`` seconds after
        submit, or whose gap since the last harvested token exceeds
        ``itl_deadline_s``, finishes with ``finish_reason="expired"``
        keeping its partial tokens; slot and blocks are freed."""
        if adapter is not None and self.registry is None:
            raise ValueError(
                "submit(adapter=...) needs an engine built with "
                "adapters=AdapterRegistry")
        prompt = np.asarray(prompt, np.int32).reshape(-1)
        if prompt.size == 0:
            raise ValueError("empty prompt")
        cap = self.max_len - 1            # leave >= 1 decode position
        prompt_truncated = False
        if prompt.size > cap:
            if self.long_prompt == "reject":
                raise ValueError(
                    f"prompt length {prompt.size} exceeds max_len-1={cap}; "
                    f"resubmit shorter or use long_prompt='truncate'")
            prompt = prompt[-cap:]        # keep the most recent context
            prompt_truncated = True
        if adapter is not None:
            self.registry.acquire(adapter)    # KeyError on unknown name
        req = Request(self._rid, prompt, max_new,
                      prompt_truncated=prompt_truncated, adapter=adapter,
                      priority=priority, deadline_s=deadline_s,
                      t_submit=self._now(), on_token=on_token,
                      ttft_deadline_s=ttft_deadline_s,
                      itl_deadline_s=itl_deadline_s)
        self._rid += 1
        dec = self.queue.offer(req)
        while dec.must_block:
            # backpressure: drain the engine until a queue position frees
            if not self.step():
                raise RuntimeError(
                    "admission blocked with a drained engine: the wait "
                    "queue is full but nothing in it can make progress")
            dec = self.queue.offer(req)
        if dec.evicted is not None:
            self._finish(dec.evicted, "rejected")
        if not dec.admitted:
            self._finish(req, "rejected")
        return req.rid

    def _free_slots(self):
        return [i for i, s in enumerate(self.slots) if s is None]

    # -- admission sequences ---------------------------------------------------
    def _admission_seq(self, r: Request) -> np.ndarray:
        """Tokens a (re-)admission feeds through prefill: the prompt plus
        everything generated so far. Fresh requests (no tokens yet)
        prefill just the prompt; a recompute-restored request re-enters
        with its full generated prefix, so prefill's last-position logits
        sample exactly the token uninterrupted decode would have."""
        if not r.tokens:
            return r.prompt
        return np.concatenate([r.prompt, np.asarray(r.tokens, np.int32)])

    def _kv_seq(self, r: Request) -> np.ndarray:
        """Tokens whose KV a running slot currently holds: prompt ++
        tokens[:-1] (the last sampled token's KV is written by the NEXT
        decode step). Keys the radix-index publish at finish/swap-out."""
        return np.concatenate([r.prompt, np.asarray(r.tokens[:-1],
                                                    np.int32)])

    # -- preemption (swap-out) and restore -------------------------------------
    def _preempt_slot(self, i: int):
        """Swap a running request out of slot ``i`` without losing work.

        Paged mode releases the slot's blocks *through the radix index*:
        full blocks are published keyed by the KV sequence (so a later
        fast restore — or any other request sharing the prefix — finds
        them), and the partial tail block's rows are copied to a host
        swap buffer. Dense mode just abandons the slot rows (restore
        recomputes). The request re-enters the queue with its original
        rid, i.e. ahead of its priority class."""
        r = self.slots[i]
        with TraceAnnotation("engine.preempt", rid=r.rid):
            if self.paged and r.prefilling:
                # a mid-prefill victim's consumed prefix is whole blocks (the
                # cursor is block-aligned): publish them and drop the slot —
                # no host tail to save, no SwapState. Re-admission goes back
                # through the normal prefill path, where the radix match
                # re-hits the published prefix, so the restore is
                # token-identical without carrying any device state.
                if r.adapter is None:
                    done = self._admission_seq(r)[:r.prefill_cursor]
                    self.pager.insert(done, self.pager.slot_blocks(i))
                r.prefilling = False
                r.prefill_cursor = 0
                self.pager.release_slot(i)
                self.stats.blocks_in_use = self.pager.blocks_in_use
                self.stats.preempted_prefill += 1
            elif self.paged:
                seq = self._kv_seq(r)
                bs = self.kv_block_size
                full = len(seq) // bs
                blocks = self.pager.slot_blocks(i)
                if r.adapter is None and full:
                    self.pager.insert(seq, blocks[:full])
                tail = None
                if len(seq) % bs and full < len(blocks):
                    tb = blocks[full]
                    with TraceAnnotation("engine.sync", what="swap"):
                        tail = {name: np.asarray(self.cache[name][:, tb])
                                for name in self._pool_leaves}
                r._swap = SwapState(seq_len=len(seq), full_blocks=full,
                                    tail=tail)
                self.pager.release_slot(i)
                self.stats.blocks_in_use = self.pager.blocks_in_use
            self.slots[i] = None
            self.adapter_slots[i] = -1
            r.preemptions += 1
            self.stats.preempted += 1
            self.queue.push_front(r)

    def _try_fast_restore(self, r: Request, slot: int) -> bool:
        """Re-seat a swapped-out request without recompute: every full KV
        block must still be in the radix index (uncapped ``lookup``) and
        the partial tail, if any, in the host swap buffer. On success the
        slot re-enters decode directly — no prefill dispatch. Returns
        False (recompute path) if anything was evicted meanwhile."""
        sw = r._swap
        if sw is None or not self.paged:
            return False
        if self.speculate:
            # the draft cache is not swapped out (private scratch), so a
            # fast restore would resume with stale draft KV; the recompute
            # path rebuilds target AND draft token-identically instead
            return False
        if r.adapter is not None and sw.full_blocks:
            return False               # LoRA KV is never in the index
        hit = self.pager.lookup(self._kv_seq(r)) if sw.full_blocks else []
        if len(hit) < sw.full_blocks:
            return False                # prefix (partly) evicted
        hit = hit[:sw.full_blocks]
        tail_len = sw.seq_len % self.kv_block_size
        if tail_len and sw.tail is None:
            return False
        if not self.pager.admit(slot, hit, 1 if tail_len else 0):
            return False                # pool dry even after eviction
        if tail_len:
            tb = int(self.pager.tables[slot, sw.full_blocks])
            for name in self._pool_leaves:
                self.cache[name] = self.cache[name].at[:, tb].set(
                    jnp.asarray(sw.tail[name], self.cache[name].dtype))
        r._swap = None
        self.slots[slot] = r
        self.adapter_slots[slot] = self._adapter_row(r)
        self.stats.restored += 1
        self.stats.fast_restores += 1
        return True

    def _priority_preempt(self):
        """Make room for strictly-higher-priority queued requests: for
        each waiting request beyond what free slots absorb, preempt the
        lowest-priority running slot strictly below it (never an equal —
        two peers must not thrash)."""
        if not self.queue:
            return
        nfree = len(self._free_slots())
        waiting = sorted(self.queue,
                         key=lambda q: (-q.priority, q.rid))[nfree:]
        for req in waiting:
            victim = pick_victim(self.slots, below_priority=req.priority)
            if victim is None:
                break
            self._preempt_slot(victim)

    # -- execution deadlines (TTFT / inter-token) -------------------------------
    def _deadline_passed(self, r: Request, now: float) -> bool:
        if r.ttft_deadline_s is not None and r.t_first is None \
                and now - r.t_submit > r.ttft_deadline_s:
            return True
        if r.itl_deadline_s is not None and r.t_last is not None \
                and now - r.t_last > r.itl_deadline_s:
            return True
        return False

    def _expire_deadlines(self):
        """Enforce per-request TTFT and inter-token deadlines mid-run.

        ``deadline_s`` (queue-wait) is checked by ``WaitQueue.expire``;
        this sweep covers the *execution* deadlines everywhere a request
        can be: still queued (a preempted request counts), mid-prefill,
        or decoding in a slot. An expired runner keeps its partial tokens
        (``finish_reason="expired"``), publishes its reusable KV prefix
        and frees slot/blocks/pin — the books stay balanced."""
        now = self._now()
        dead = [r for r in self.queue if self._deadline_passed(r, now)]
        for r in dead:
            self.queue.remove(r)
            self._finish(r, "expired")
        for i, r in enumerate(self.slots):
            if r is not None and self._deadline_passed(r, now):
                self._teardown_slot(i)
                self._finish(r, "expired")

    def _teardown_slot(self, i: int):
        """Release slot ``i``'s resources without finishing its request:
        publish the reusable KV prefix (full blocks of the sequence the
        slot actually holds — ``_kv_seq`` for a decoding slot, the
        block-aligned prefix cursor for a mid-prefill one), release the
        slot's pool blocks, and clear the slot row. Callers own the
        ``_finish`` bookkeeping."""
        r = self.slots[i]
        if self.paged:
            if r.adapter is None:
                seq = (self._admission_seq(r)[:r.prefill_cursor]
                       if r.prefilling else self._kv_seq(r))
                self.pager.insert(seq, self.pager.slot_blocks(i))
            self.pager.release_slot(i)
            self.stats.blocks_in_use = self.pager.blocks_in_use
        self.slots[i] = None
        self.adapter_slots[i] = -1

    def _settle(self, i: int, now: float) -> bool:
        """Settle slot ``i`` after tokens were appended to its request:
        stamp ``t_last``, stream them, and when a stop condition or a
        cancel ends the request, tear the slot down (publish, release,
        clear) and finish it. Returns True when the slot was freed."""
        r = self.slots[i]
        r.t_last = now
        want_cancel = self._emit(r, now)
        reason = self._stop_reason(r)
        if reason is None and want_cancel:
            reason = "cancelled"
        if reason is None:
            return False
        self._teardown_slot(i)
        self._finish(r, reason)
        return True

    # -- streaming (per-token emission + cancellation) ---------------------------
    def _emit(self, r: Request, now: float) -> bool:
        """Stream tokens appended since the last harvest to ``on_token``.

        Stamps ``t_first`` at the first *actual emission* (the TTFT base
        — previously over-stated by stamping at wave granularity) and
        advances the per-request emission cursor. Returns True when the
        callback raised :class:`StopStream`: the caller must tear the
        request down as cancelled unless a stop reason already finished
        it. Any other callback exception propagates."""
        new = r.tokens[r._emitted:]
        if not new:
            return False
        if r.t_first is None:
            r.t_first = now
        if r.on_token is None:
            r._emitted = len(r.tokens)
            return False
        for t in new:
            r._emitted += 1
            try:
                r.on_token(r, int(t))
            except StopStream:
                # the client consumed exactly ``_emitted`` tokens; drop
                # the rest of this harvest so the cancelled request's
                # token list matches what was actually streamed
                del r.tokens[r._emitted:]
                return True
        return False

    def cancel(self, rid: int) -> bool:
        """Tear down a queued or in-flight request mid-stream.

        The request finishes with ``finish_reason="cancelled"`` keeping
        the tokens emitted so far; its slot, KV blocks (published full
        prefix blocks stay in the radix index for other requests), and
        adapter pin are all released. Returns True when the request was
        live and is now cancelled; False when it already finished
        (cancel lost the race — the result stands). Unknown rids raise
        KeyError."""
        for i, s in enumerate(self.slots):
            if s is not None and s.rid == rid:
                self._teardown_slot(i)
                self._finish(s, "cancelled")
                return True
        for r in list(self.queue):
            if r.rid == rid:
                self.queue.remove(r)
                self._finish(r, "cancelled")
                return True
        if any(r.rid == rid for r in self.finished):
            return False
        raise KeyError(f"request {rid} not found")

    def stream(self, prompt, max_new: int = 32, **kw):
        """Generator yielding tokens for one request as they are produced.

        Submits the prompt and drives ``step()`` internally, yielding
        each harvested token. Closing the generator early (``break``,
        ``.close()``, GC) cancels the request and releases every
        resource it held — the teardown path a disappearing client
        needs. Extra keyword arguments pass through to :meth:`submit`;
        a caller ``on_token`` is composed in front of the stream's own
        buffering (and may still raise :class:`StopStream`)."""
        buf: List[int] = []
        user_cb = kw.pop("on_token", None)

        def tap(req, tok):
            if user_cb is not None:
                user_cb(req, tok)       # StopStream propagates to the engine
            buf.append(tok)

        rid = self.submit(prompt, max_new, on_token=tap, **kw)
        try:
            while True:
                while buf:
                    yield buf.pop(0)
                if any(r.rid == rid for r in self.finished):
                    return
                if not self.step():
                    return              # drained with the request resolved
        finally:
            if not any(r.rid == rid for r in self.finished):
                self.cancel(rid)

    # -- prefill waves ---------------------------------------------------------
    def _admit(self):
        with TraceAnnotation("engine.admit"):
            for r in self.queue.expire(self._now()):
                self._finish(r, "expired")
            self._expire_deadlines()
            self._priority_preempt()
            self._continue_prefill()
            free = self._free_slots()
            if not free or not self.queue:
                return
            take = self.queue.take(len(free))
            pending = []
            for r in take:
                if self.paged and r._swap is not None and free \
                        and self._try_fast_restore(r, free[0]):
                    free.pop(0)
                    continue
                pending.append(r)
            if not pending:
                return
            if self.api.ragged_prefill:
                groups = [pending]
            else:
                by_len = {}
                for r in pending:
                    n = len(self._admission_seq(r))
                    by_len.setdefault(n, []).append(r)
                groups = list(by_len.values())
            t0 = time.perf_counter()
            gi = -1
            try:
                for gi, group in enumerate(groups):
                    if self.paged:
                        self._admit_paged(group, free)
                    else:
                        self._prefill_group(group, free)
                with TraceAnnotation("engine.prefill_sync"):
                    jax.block_until_ready(
                        self.cache["k"] if "k" in self.cache
                        else jax.tree_util.tree_leaves(self.cache)[0])
            except Exception:
                # the failing group requeued itself (its prefill handler
                # owns rollback); untouched later groups must requeue here
                # or they'd be lost with their adapter pins held forever
                for group in groups[gi + 1:]:
                    for r in group:
                        self.queue.push_front(r)
                raise
            finally:
                self.stats.prefill_wall_s += time.perf_counter() - t0

    def _adapter_row(self, r: Request) -> int:
        """Row of ``r``'s adapter in ``registry.stacked`` (-1: base)."""
        return -1 if r.adapter is None else self.registry.index_of(r.adapter)

    def _lora_args(self, aidx) -> tuple:
        """Trailing ``(stacked, aidx)`` arguments of every jitted program:
        empty without a registry, so the program is the base model's."""
        if self.registry is None:
            return ()
        return self.registry.stacked, jnp.asarray(aidx)

    def _with_lora(self, fn, stacked, aidx):
        """``api.prefill`` / ``api.decode`` with the gathered LoRA delta
        bound when a program was given adapter arguments (traced: the
        stacked A/B pytree and the rows' adapter indices are jit
        arguments, so hot add/evict never invalidates a compile)."""
        if stacked is None:
            return fn
        return functools.partial(fn, adapters=stacked, adapter_idx=aidx,
                                 lora_scaling=self.registry.scaling)

    def _get_prefill(self, wave_bucket: int, padded_len: int):
        """Jitted dense prefill for one (wave, padded_len) bucket."""
        key = (wave_bucket, padded_len)
        if key not in self._prefill_cache:
            api, max_len = self.api, self.max_len
            ragged = api.ragged_prefill

            def prefill(params, toks, lengths, stacked=None, aidx=None):
                cache = api.init_cache(toks.shape[0], max_len)
                kw = {"lengths": lengths} if ragged else {}
                logits, wave_cache = self._with_lora(api.prefill, stacked,
                                                     aidx)(
                    params, {"tokens": toks}, cache, **kw)
                return logits, self._constrain_wave(wave_cache,
                                                    toks.shape[0])

            self._prefill_cache[key] = jax.jit(prefill)
            self.stats.prefill_compiles += 1
        return self._prefill_cache[key]

    def _prefill_group(self, group: List[Request], free: List[int]):
        """Dense admission wave: prefill whole sequences, then write the
        rows that stay seated into their slots' cache rows."""
        t_seat = self._now()
        w = len(group)
        wb = _pow2_bucket(w, 1, self.n_slots)
        seqs = [self._admission_seq(r) for r in group]
        lens = [len(s) for s in seqs]
        if self.api.ragged_prefill:
            pl = _pow2_bucket(max(lens), min(8, self.max_len), self.max_len)
        else:
            pl = lens[0]                  # equal-length group, exact
        toks = np.zeros((wb, pl), np.int32)
        lengths = np.ones((wb,), np.int32)
        aidx = np.full((wb,), -1, np.int32)
        for i, (r, seq) in enumerate(zip(group, seqs)):
            toks[i, : len(seq)] = seq
            lengths[i] = len(seq)
            aidx[i] = self._adapter_row(r)
        fn = self._get_prefill(wb, pl)
        try:
            with TraceAnnotation("engine.prefill", rows=w, tokens=sum(lens),
                                 hit=0, prefix=0, rids=_rids(group),
                                 kind="first"):
                if self.fault_hook is not None:
                    self.fault_hook("prefill")
                logits, wave_cache = fn(self.params, jnp.asarray(toks),
                                        jnp.asarray(lengths),
                                        *self._lora_args(aidx))
        except Exception:
            # nothing was mutated yet (no slot/cache writes): requeue the
            # whole group so no request — or its adapter pin — is lost
            for r in group:
                self.queue.push_front(r)
            raise
        first = self._sample(logits)
        now = self._now()
        src, dst = [], []
        with TraceAnnotation("engine.emit"):
            for i, r in enumerate(group):
                r.tokens.append(int(first[i]))
                self._count_admission(r, t_seat)
                r._swap = None
                self.stats.prefill_tokens += int(lengths[i])
                slot = free[0]
                self.slots[slot] = r
                self.adapter_slots[slot] = aidx[i]
                if self._settle(slot, now):
                    continue                # EOS/max_new on the first token
                free.pop(0)
                src.append(i)
                dst.append(slot)
        if src:
            self.cache = self._writer(self.cache, wave_cache,
                                      np.asarray(src, np.int32),
                                      np.asarray(dst, np.int32))
            if self.speculate:
                self._draft_prefill([(slot, seqs[i])
                                     for i, slot in zip(src, dst)])
        self.stats.prefill_waves += 1

    # -- paged prefill (block pool + prefix reuse) -----------------------------
    def _get_paged_prefill(self, wave_bucket: int, padded_len: int,
                           n_prefix_blocks: int):
        """Jitted paged prefill for one (wave, suffix_pad, prefix_blocks)
        bucket: gather the rows' cached prefix KV out of the pool through
        their prefix block tables, run the suffix-only prefill wave, and
        scatter the new suffix KV into the rows' freshly allocated blocks
        — one dispatch, pool donated. ``n_prefix_blocks == 0`` is the
        no-hit fast path (no gather, plain ragged prefill)."""
        key = ("paged", wave_bucket, padded_len, n_prefix_blocks)
        if key not in self._prefill_cache:
            api, bs = self.api, self.kv_block_size
            quant_kv = self.cfg.quant_kv
            pool_leaves = self._pool_leaves
            n_suffix_blocks = padded_len // bs

            def paged_prefill(cache, params, toks, lengths, prefix_len, pbt,
                              sbt, stacked=None, aidx=None):
                wave = api.init_cache(toks.shape[0], padded_len)
                kw = {"lengths": lengths}
                if n_prefix_blocks:
                    def gather(name):
                        g = jnp.take(cache[name], pbt, axis=1)
                        return g.reshape(g.shape[0], g.shape[1],
                                         n_prefix_blocks * bs, *g.shape[4:])
                    prefix = {"k": gather("k"), "v": gather("v"),
                              "len": prefix_len}
                    if quant_kv:
                        prefix["k_scale"] = gather("k_scale")
                        prefix["v_scale"] = gather("v_scale")
                    kw["prefix"] = prefix
                logits, wave_cache = self._with_lora(api.prefill, stacked,
                                                     aidx)(
                    params, {"tokens": toks}, wave, **kw)
                wave_cache = self._constrain_wave(wave_cache, toks.shape[0])
                new_cache = dict(cache)
                for name in pool_leaves:
                    w = wave_cache[name]          # [L, wb, pl, hk, x]
                    w = w.reshape(w.shape[0], w.shape[1], n_suffix_blocks,
                                  bs, *w.shape[3:])
                    new_cache[name] = cache[name].at[:, sbt].set(
                        w.astype(cache[name].dtype))
                return logits, new_cache

            self._prefill_cache[key] = jax.jit(paged_prefill,
                                               donate_argnums=(0,))
            self.stats.prefill_compiles += 1
        return self._prefill_cache[key]

    def _admit_paged(self, group: List[Request], free: List[int]):
        """Plan a paged admission wave and run it: match each sequence's
        longest cached full-block prefix in the radix index, cut the
        un-cached rest to the step's budget, and take its blocks.

        Admission is atomic per request (``pager.admit``, plan-then-
        commit): a request the pool cannot hold — even after LRU
        eviction — is *deferred* back to the queue with zero blocks
        held, never half-admitted. Deferral, not preemption: blocks come
        back when a running slot finishes, and preempting here would
        thrash (the victim would immediately compete for the same
        blocks)."""
        t_seat = self._now()
        pgr, bs = self.pager, self.kv_block_size
        rows = []
        for r in group:
            seq = self._admission_seq(r)
            # LoRA requests bypass the prefix index: adapters targeting
            # wk/wv make the KV adapter-specific, so sharing it across
            # adapters (or with the base model) would be silently wrong
            hit, cursor = pgr.match(seq) if r.adapter is None else ([], 0)
            take = len(seq) - cursor
            if self.prefill_budget is not None:
                take = prefill_chunk(take, self._prefill_left, bs)
                if take == 0:
                    self.queue.push_front(r)   # step's budget spent
                    continue
            # slots are assigned up front: block ownership needs a table
            if not pgr.admit(free[0], hit, math.ceil(take / bs)):
                self.queue.push_front(r)     # defer: pool dry right now
                continue
            if self.prefill_budget is not None:
                self._prefill_left -= take
            rows.append(_Row(free.pop(0), r, seq, cursor, take))
        self._paged_wave("first", rows, t_seat)

    def _continue_prefill(self):
        """Advance every mid-prefill slot by one budgeted chunk.

        Runs at the top of admission, before new requests compete for
        the step's prefill budget: in-flight prompts finish sooner,
        which frees slots faster. Block allocation is all-or-nothing per
        slot (``pager.extend``); when the pool cannot cover the wave's
        plan, victims are preempted (mid-prefill slots included) until it
        can or one slot remains, and a slot the pool still cannot extend
        keeps its seat and defers its chunk to the next step."""
        if self.prefill_budget is None:
            return
        pgr, bs = self.pager, self.kv_block_size
        rows = []
        for i, r in enumerate(self.slots):
            if r is None or not r.prefilling:
                continue
            seq = self._admission_seq(r)
            take = prefill_chunk(len(seq) - r.prefill_cursor,
                                 self._prefill_left, bs)
            if take == 0:
                continue                # step budget exhausted
            self._prefill_left -= take
            rows.append(_Row(i, r, seq, r.prefill_cursor, take))
        if not rows:
            return
        t0 = time.perf_counter()
        try:
            while not pgr.can_allocate(
                    sum(math.ceil(row.take / bs) for row in rows)):
                if sum(s is not None for s in self.slots) <= 1:
                    break               # per-slot extend() defers below
                victim = pick_victim(self.slots)
                self._preempt_slot(victim)
                rows = [row for row in rows if row.slot != victim]
                if not rows:
                    return
            self._paged_wave("continue", [
                row for row in rows
                if pgr.extend(row.slot, math.ceil(row.take / bs))])
        finally:
            self.stats.prefill_wall_s += time.perf_counter() - t0

    def _paged_wave(self, kind: str, rows: List[_Row], t_seat: float = 0.0):
        """One paged prefill wave over planned rows whose blocks the plan
        already took. For ``kind="first"`` a row's prefix blocks are a
        prefix hit and its request is being seated (at ``t_seat``); for
        ``"continue"`` they are the seated slot's own consumed blocks.

        Every wave draws its shape from one lattice — pow2 width up to
        ``n_slots``, pow2 chunk length capped by the budget, pow2 prefix
        blocks — so the compile count is bounded whatever the arrival
        pattern, without padding a lone chunk to the full slot set.

        A fault during the dispatch rolls every row back: a new row's
        blocks return to the pool and the request (pin intact) requeues;
        a seated row truncates to its cursor, so a retried step re-plans
        the same chunk. After the dispatch each row advances its
        block-aligned cursor and publishes the consumed blocks; a row
        whose prompt ended takes the first token and settles, and a row
        still mid-prompt discards its logits (greedy output stays
        bit-identical to an unbudgeted prefill)."""
        if not rows:
            return
        pgr, bs = self.pager, self.kv_block_size
        new = kind == "first"
        max_ctx = self.max_blocks * bs
        wb = _pow2_bucket(len(rows), 1, self.n_slots)
        pl = _pow2_bucket(max(row.take for row in rows), bs, _pow2_bucket(
            self.prefill_budget or max_ctx, bs, max_ctx))
        nh_max = max(row.cursor for row in rows) // bs
        npb = _pow2_bucket(nh_max, 1, self.max_blocks) if nh_max else 0
        toks = np.zeros((wb, pl), np.int32)
        lengths = np.ones((wb,), np.int32)
        prefix_len = np.zeros((wb,), np.int32)
        pbt = np.zeros((wb, max(npb, 1)), np.int32)
        sbt = np.zeros((wb, pl // bs), np.int32)
        aidx = np.full((wb,), -1, np.int32)
        for j, (slot, r, seq, cursor, take) in enumerate(rows):
            nh, nsb = cursor // bs, math.ceil(take / bs)
            toks[j, :take] = seq[cursor: cursor + take]
            lengths[j] = take
            prefix_len[j] = cursor
            pbt[j, :nh] = pgr.tables[slot, :nh]
            sbt[j, :nsb] = pgr.tables[slot, nh: nh + nsb]
            aidx[j] = self._adapter_row(r)
        fn = self._get_paged_prefill(wb, pl, npb)
        cached = sum(row.cursor for row in rows)
        try:
            with TraceAnnotation("engine.prefill", rows=len(rows),
                                 tokens=sum(row.take for row in rows),
                                 hit=cached if new else 0, prefix=cached,
                                 rids=_rids(row.req for row in rows),
                                 kind=kind):
                if self.fault_hook is not None:
                    self.fault_hook("prefill")
                logits, self.cache = fn(
                    self.cache, self.params, jnp.asarray(toks),
                    jnp.asarray(lengths), jnp.asarray(prefix_len),
                    jnp.asarray(pbt), jnp.asarray(sbt),
                    *self._lora_args(aidx))
        except Exception:
            for slot, r, _, cursor, _ in rows:
                if new:
                    pgr.release_slot(slot)
                    self.queue.push_front(r)
                else:
                    pgr.truncate(slot, cursor)
            self.stats.blocks_in_use = pgr.blocks_in_use
            raise
        first = self._sample(logits)
        now = self._now()
        with TraceAnnotation("engine.emit"):
            for j, (slot, r, seq, cursor, take) in enumerate(rows):
                if new:
                    self._count_admission(r, t_seat)
                    r._swap = None
                    self.stats.prefix_hit_tokens += cursor
                    self.slots[slot] = r
                    self.adapter_slots[slot] = aidx[j]
                self.stats.prefill_tokens += take
                if self.prefill_budget is not None:
                    self.stats.prefill_chunks += 1
                    self._prefill_progress = True
                r.prefill_cursor = cursor + take
                r.prefilling = r.prefill_cursor < len(seq)
                if not r.prefilling:
                    r.tokens.append(int(first[j]))
                    if self._settle(slot, now):
                        continue        # its teardown published the blocks
                # publish the consumed whole blocks now: later waves, and a
                # preempted slot's re-admission, reuse them while this slot
                # still runs (base model only: LoRA KV is adapter-specific)
                if r.adapter is None:
                    pgr.insert(seq[:r.prefill_cursor], pgr.slot_blocks(slot))
        if self.speculate:
            self._draft_prefill([(slot, seq) for slot, r, seq, _, _ in rows
                                 if self.slots[slot] is r])
        self.stats.prefill_waves += 1
        self.stats.blocks_in_use = pgr.blocks_in_use

    def _count_admission(self, r: Request, t_seat: float):
        """Admission bookkeeping of a prefilled row: a request seated for
        the first time is counted admitted and stamped ``t_admit`` (the
        wave's start; its queue wait is summed); one seated before is a
        recompute restore."""
        if r._admitted:
            self.stats.restored += 1
            return
        r._admitted = True
        r.t_admit = t_seat
        self.stats.admitted += 1
        self.stats.queue_wait_s += t_seat - r.t_submit
        if r.adapter is not None:
            self.stats.lora_requests += 1

    def _draft_prefill(self, seated):
        """Draft-side prefill of the ``(slot, seq)`` rows an admission wave
        seated, after the target wave committed. The draft cache is dense,
        so the FULL sequence of each row prefills through the dense path
        (a paged prefix hit saves target compute only; the draft is the
        cheap model by construction) and each speculative round starts
        with draft KV covering exactly the target's positions. Its logits
        are unused: the first token always comes from the target."""
        if not seated:
            return
        wb = _pow2_bucket(len(seated), 1, self.n_slots)
        pl = _pow2_bucket(max(len(seq) for _, seq in seated),
                          min(8, self.max_len), self.max_len)
        toks = np.zeros((wb, pl), np.int32)
        lengths = np.ones((wb,), np.int32)
        aidx = np.full((wb,), -1, np.int32)
        for j, (slot, seq) in enumerate(seated):
            toks[j, : len(seq)] = seq
            lengths[j] = len(seq)
            aidx[j] = self.adapter_slots[slot]
        fn = self._get_prefill(wb, pl)
        with TraceAnnotation("engine.prefill", rows=len(seated),
                             tokens=int(lengths[:len(seated)].sum()), hit=0,
                             prefix=0,
                             rids=_rids(self.slots[s] for s, _ in seated),
                             kind="draft"):
            _, dwave = fn(self.draft_params, jnp.asarray(toks),
                          jnp.asarray(lengths), *self._lora_args(aidx))
        self.draft_cache = self._writer(
            self.draft_cache, dwave, np.arange(len(seated), dtype=np.int32),
            np.asarray([slot for slot, _ in seated], np.int32))

    # -- sampling --------------------------------------------------------------
    def _sample(self, logits):
        toks, self.rng = self._sampler(jnp.asarray(logits), self.rng,
                                       greedy=self.greedy,
                                       vocab_size=self.cfg.vocab_size)
        with TraceAnnotation("engine.sync", what="prefill"):
            return np.asarray(toks)

    # -- stop conditions -------------------------------------------------------
    def _stop_reason(self, r: Request) -> Optional[str]:
        if self.eos_id is not None and r.tokens[-1] == self.eos_id:
            return "eos"
        if len(r.tokens) >= r.max_new:
            return "max_new"
        # next decode would write at pos = prompt + generated - 1
        if len(r.prompt) + len(r.tokens) - 1 >= self.max_len:
            r.truncated = True
            return "cache_full"
        return None

    def _finish(self, r: Request, reason: str):
        """Terminal bookkeeping for every outcome. ``finished`` (the list)
        holds all of them; ``stats.finished`` counts only generation
        outcomes (eos/max_new/cache_full) — rejected requests produced no
        tokens, and expired/cancelled ones may carry a partial stream;
        all three are tallied separately."""
        r.done = True
        r.finish_reason = reason
        r._swap = None
        r.prefilling = False
        if r.adapter is not None:
            self.registry.release(r.adapter)   # unpin: evict becomes legal
        self.finished.append(r)
        if reason == "rejected":
            self.stats.rejected += 1
            return
        if reason == "expired":
            self.stats.expired += 1
            return
        if reason == "cancelled":
            self.stats.cancelled += 1
            return
        self.stats.finished += 1
        if r.truncated:
            self.stats.truncated += 1

    # -- decode ----------------------------------------------------------------
    def _get_chunk_fn(self, n: int):
        """Jitted scan-decode for chunk length n (cache donated). With an
        adapter registry the trailing ``stacked, aidx`` arguments (the
        stacked A/B pytree and the per-slot [B] adapter-index row) run
        the gathered LoRA delta pipeline alongside the untouched base
        path every scan step."""
        key = (n, self.greedy)
        if key not in self._chunk_fns:
            api, cfg = self.api, self.cfg
            eos_id, max_len, greedy = self.eos_id, self.max_len, self.greedy

            def decode_chunk(params, last, cache, rng, stop, gen, max_new,
                             stacked=None, aidx=None):
                return decode_steps(
                    self._with_lora(api.decode, stacked, aidx), params,
                    last, cache, rng, stop, gen, max_new, n=n,
                    vocab_size=cfg.vocab_size, max_len=max_len,
                    eos_id=eos_id, greedy=greedy)

            self._chunk_fns[key] = jax.jit(decode_chunk, donate_argnums=(2,))
        return self._chunk_fns[key]

    # -- speculative decode ----------------------------------------------------
    def _get_spec_fns(self, k: int):
        """Jitted (draft, verify) pair for draft length ``k``.

        The draft scan is ``decode_steps`` with every stop condition
        defused (no eos, budget/pos bounds vacuous): proposals past a
        real stop are garbage the host's per-token ``_stop_reason``
        discards while appending, and a free-running scan is what makes
        a retried round bit-deterministic. It runs k+1 steps — one more
        than the proposals used — so draft KV lands at the same
        ``pos .. pos+k`` the verify scan writes, keeping the two caches
        position-aligned even on an all-accept round. The verify scan is
        :func:`repro.serve.decode.verify_steps` over the target. Both
        donate their cache. ``k`` is compile-time (bucketed by
        ``round_k``), mirroring ``_get_chunk_fn``'s per-length cache."""
        if k not in self._spec_fns:
            api, cfg = self.api, self.cfg
            vs = cfg.vocab_size
            no_stop_len = self.max_len + 2    # pos bound can never fire

            def spec_draft(dparams, last, dcache, rng, stop, stacked=None,
                           aidx=None):
                b = last.shape[0]
                return decode_steps(
                    self._with_lora(api.decode, stacked, aidx), dparams,
                    last, dcache, rng, stop, jnp.zeros((b,), jnp.int32),
                    jnp.full((b,), 1 << 30, jnp.int32),
                    n=k + 1, vocab_size=vs, max_len=no_stop_len,
                    eos_id=None, greedy=True)

            def spec_verify(params, last, drafts, cache, stacked=None,
                            aidx=None):
                return verify_steps(
                    self._with_lora(api.decode, stacked, aidx), params,
                    last, drafts, cache, vocab_size=vs)

            self._spec_fns[k] = (jax.jit(spec_draft, donate_argnums=(2,)),
                                 jax.jit(spec_verify, donate_argnums=(3,)))
        return self._spec_fns[k]

    def _spec_step(self, active, max_n: Optional[int]) -> bool:
        """One speculative round over the active slots: draft k proposals
        with the low-precision model, verify all of them in ONE
        teacher-forced target dispatch, append the longest agreeing
        prefix plus the target's correction token, and roll the KV tail
        written for rejected positions back (cursor reset / block
        truncation). Greedy output is bit-identical to `_step`'s
        target-only decode — every appended token is the target's own
        argmax (tests/test_speculative.py).

        Fault retry contract: both caches' ``pos`` cursors are host-set
        from request state at the top of every round, and the draft scan
        is deterministic (greedy, stop-free), so a round that faults at
        the "draft" or "verify" hook re-runs bit-identically — the
        positions past the cursor that a partial round already wrote are
        simply rewritten with the same values."""
        positions = {i: len(self.slots[i].prompt)
                     + len(self.slots[i].tokens) - 1 for i in active}

        def pick_k():
            return round_k(
                self.spec_k, max_len=self.max_len,
                positions=[positions[i] for i in active],
                budgets=[self.slots[i].max_new - len(self.slots[i].tokens)
                         for i in active],
                max_n=max_n)

        k = pick_k()
        cow = []
        with TraceAnnotation("engine.plan") as plan:
            if self.paged:
                # plan -> commit for the whole k+1 verify window, preempting
                # while it cannot fit (mirrors `_step`; a single slot always
                # fits because k is clamped to the slot's own remaining room)
                while len(active) > 1:
                    need = 0
                    for i in active:
                        a, c = self.pager.plan_decode(i, positions[i], k + 1)
                        need += a + c
                    if self.pager.can_allocate(need):
                        break
                    self._preempt_slot(pick_victim(self.slots))
                    active = [i for i, s in enumerate(self.slots)
                              if s is not None]
                    k = pick_k()
                pos_host = np.zeros((self.n_slots,), np.int32)
                for i in active:
                    pos_host[i] = positions[i]
                    cow += self.pager.prepare_decode(i, positions[i], k + 1)
                if cow:
                    pad = _pow2_bucket(len(cow), 1, 1 << 30) - len(cow)
                    pairs = cow + [(0, 0)] * pad
                    src = np.asarray([c[0] for c in pairs], np.int32)
                    dst = np.asarray([c[1] for c in pairs], np.int32)
                    self.cache = self._copier(self.cache, src, dst)
                    self.stats.cow_copies += len(cow)
                self.cache["pos"] = jnp.asarray(pos_host)
                self.cache["block_tables"] = jnp.asarray(self.pager.tables)
                self.stats.blocks_in_use = self.pager.blocks_in_use
            else:
                # dense rollback is this line: the verify scan advanced the
                # device cursor to pos+k+1 last round, resetting it to the
                # accepted length makes the stale tail dead weight the next
                # window overwrites
                pos_host = np.zeros((self.n_slots,), np.int32)
                for i in active:
                    pos_host[i] = positions[i]
                self.cache["pos"] = jnp.asarray(pos_host)
            dpos = np.zeros((self.n_slots,), np.int32)
            for i in active:
                dpos[i] = positions[i]
            self.draft_cache["pos"] = jnp.asarray(dpos)
            last = np.zeros((self.n_slots,), np.int32)
            stop = np.ones((self.n_slots,), bool)
            for i in active:
                last[i] = self.slots[i].tokens[-1]
                stop[i] = False
            plan.set_metadata(cow=len(cow))
        draft_fn, verify_fn = self._get_spec_fns(k)
        rids = _rids(self.slots[i] for i in active)
        keys = sum(_attended(positions[i], k + 1) for i in active)
        if k:
            with TraceAnnotation("engine.decode", phase="draft", steps=k + 1,
                                 rows=len(active), keys=keys, rids=rids):
                if self.fault_hook is not None:
                    self.fault_hook("draft")
                dout = draft_fn(self.draft_params, jnp.asarray(last),
                                self.draft_cache, self.rng,
                                jnp.asarray(stop),
                                *self._lora_args(self.adapter_slots))
            self.draft_cache = dout.cache
            with TraceAnnotation("engine.sync", what="draft"):
                drafts = np.asarray(dout.tokens)[:k]
        else:
            drafts = np.zeros((0, self.n_slots), np.int32)
        with TraceAnnotation("engine.decode", phase="verify", steps=k + 1,
                             rows=len(active), keys=keys, rids=rids):
            if self.fault_hook is not None:
                self.fault_hook("verify")
            targets_dev, self.cache = verify_fn(
                self.params, jnp.asarray(last), jnp.asarray(drafts),
                self.cache, *self._lora_args(self.adapter_slots))
        with TraceAnnotation("engine.sync", what="verify"):
            targets = np.asarray(targets_dev)          # [k+1, B]
        now = self._now()
        emitted = 0
        with TraceAnnotation("engine.emit"):
            for i in active:
                r = self.slots[i]
                m = accept_length(drafts[:, i], targets[:, i])
                got = 0
                for t in range(m + 1):
                    # stops are re-derived per appended token: an EOS / budget
                    # / cache-full landing mid-acceptance discards the rest
                    r.tokens.append(int(targets[t, i]))
                    got += 1
                    if self._stop_reason(r) is not None:
                        break
                emitted += got
                self.stats.spec_slot_rounds += 1
                self.stats.drafted_tokens += k
                # of the kept tokens, all but a final correction/bonus token
                # were draft proposals
                self.stats.accepted_draft_tokens += min(got, m)
                if self.paged:
                    # rollback: keep exactly the KV the kept tokens stand on
                    # (prompt ++ tokens[:-1]); whole tail blocks written for
                    # rejected positions return to the pool
                    self.pager.truncate(i, positions[i] + got)
                self._settle(i, now)
        self.stats.spec_rounds += 1
        self.stats.spec_emitted_tokens += emitted
        self.stats.decode_tokens += emitted
        self.stats.steps += k + 1                  # target decode steps
        self.stats.decode_chunks += 2 if k else 1  # dispatches this round
        self.stats.occupancy_sum += (k + 1) * len(active) / self.n_slots
        if self.paged:
            self.stats.blocks_in_use = self.pager.blocks_in_use
        return True

    def step(self, max_n: Optional[int] = None) -> bool:
        """Admit a prefill wave, then run ONE chunked decode dispatch of up
        to min(decode_chunk, max_n, largest per-slot remaining budget)
        on-device steps. With an adapter registry the per-slot [n_slots]
        adapter-index row rides along so mixed base/LoRA slots decode in
        the same scan. Returns False when no work is left."""
        with TraceAnnotation("engine.step",
                             active=sum(s is not None for s in self.slots)), \
                self._mesh_ctx():
            return self._step(max_n)

    def _chunk_len(self, active, max_n: Optional[int]) -> int:
        """Decode chunk length: largest per-slot remaining budget, clamped
        to decode_chunk and the caller's step budget."""
        remaining = 1
        for i in active:
            r = self.slots[i]
            # slot i can emit at most this many more tokens (max_new and
            # cache-capacity bounds; the scan wastes nothing past the wave)
            rem = min(r.max_new - len(r.tokens),
                      self.max_len - (len(r.prompt) + len(r.tokens) - 1))
            remaining = max(remaining, rem)
        return max(1, min(self.decode_chunk, remaining,
                          max_n if max_n is not None else remaining))

    def _window(self, r: Request, n: int):
        """(cache position of ``r``'s next decode input, steps of an
        n-step chunk that ``r`` can use before its budget or the cache
        ends it; at least 1, the step the scan runs anyway)."""
        pos = len(r.prompt) + len(r.tokens) - 1
        return pos, max(1, min(n, r.max_new - len(r.tokens),
                               self.max_len - pos))

    def _step(self, max_n: Optional[int] = None) -> bool:
        if self.prefill_budget is not None:
            # per-STEP prefill-token ledger: first chunks and
            # continuations both draw from it, so no single engine step
            # ever does more prefill work than the budget
            self._prefill_left = self.prefill_budget
            self._prefill_progress = False
        self._admit()
        active = [i for i, s in enumerate(self.slots) if s is not None]
        while not active and self.queue:
            if self.prefill_budget is not None and self._prefill_progress:
                # the step's budget went to prefills that finished on their
                # first token: that is this step's work, and the next step
                # admits the queue with a fresh budget
                return True
            # a whole wave can finish at prefill (EOS/max_new on the first
            # token); keep admitting so queued work is never stranded
            before = (len(self.queue), len(self.finished),
                      self.stats.restored)
            self._admit()
            active = [i for i, s in enumerate(self.slots) if s is not None]
            if not active and before == (len(self.queue),
                                         len(self.finished),
                                         self.stats.restored):
                # every slot is free yet nothing admits, finishes, or
                # expires: the pool can never fit the queued requests —
                # a sizing bug, not a transient overload
                raise RuntimeError(
                    f"admission stalled: {len(self.queue)} queued "
                    f"request(s) cannot fit an empty engine "
                    f"(num_blocks={getattr(self, 'num_blocks', None)})")
        if not active:
            return False
        # mid-prefill slots hold blocks but have no token to decode yet;
        # they sit out the decode dispatch (their block-table rows are
        # masked to trash below so the scan's unconditional KV writes
        # cannot touch their real blocks)
        decode_active = [i for i in active if not self.slots[i].prefilling]
        if not decode_active:
            if self._prefill_progress:
                return True             # prefill-only step: work happened
            # nothing decodable and no chunk ran (budget spent before
            # these slots, or the pool deferred every extension): preempt
            # one victim so the freed blocks guarantee the next step
            # makes progress instead of spinning
            victim = pick_victim(self.slots)
            if victim is not None:
                self._preempt_slot(victim)
                return True
            return False
        if self.speculate:
            return self._spec_step(decode_active, max_n)
        n = self._chunk_len(decode_active, max_n)
        cow = []
        with TraceAnnotation("engine.plan") as plan:
            if self.paged:
                # plan -> commit: reserve the whole write window's block
                # budget before touching the pool, preempting the lowest-
                # priority slot while the window cannot fit. A single slot
                # always fits (pool >= per-slot max + trash), so this
                # terminates with at least one runner.
                while True:
                    need = 0
                    for i in decode_active:
                        a, c = self.pager.plan_decode(
                            i, *self._window(self.slots[i], n))
                        need += a + c
                    if self.pager.can_allocate(need):
                        break
                    if sum(s is not None for s in self.slots) <= 1:
                        break
                    self._preempt_slot(pick_victim(self.slots))
                    decode_active = [i for i, s in enumerate(self.slots)
                                     if s is not None and not s.prefilling]
                    if not decode_active:
                        # the last decoder was the victim; the preemption
                        # itself is this step's progress
                        return True
                    n = self._chunk_len(decode_active, max_n)
            last = np.zeros((self.n_slots,), np.int32)
            gen = np.zeros((self.n_slots,), np.int32)
            budget = np.zeros((self.n_slots,), np.int32)
            stop = np.ones((self.n_slots,), bool)
            keys = 0
            for i in decode_active:
                r = self.slots[i]
                last[i] = r.tokens[-1]
                gen[i] = len(r.tokens)
                budget[i] = r.max_new
                stop[i] = False
                keys += _attended(*self._window(r, n))
            if self.paged:
                # make every active slot's write window [pos, pos+n) backed by
                # uniquely owned blocks: append fresh blocks past the table end
                # and copy-on-write any shared block, in ONE batched device
                # copy. Planned above, so allocation cannot fail halfway; a
                # re-run after a decode-phase fault is a no-op (idempotent).
                pos_host = np.zeros((self.n_slots,), np.int32)
                for i in decode_active:
                    pos0, steps = self._window(self.slots[i], n)
                    pos_host[i] = pos0
                    cow += self.pager.prepare_decode(i, pos0, steps)
                if cow:
                    # pad to a power-of-two count (trash onto trash) so the
                    # jitted copier compiles once per bucket, not per count
                    pad = _pow2_bucket(len(cow), 1, 1 << 30) - len(cow)
                    pairs = cow + [(0, 0)] * pad
                    src = np.asarray([c[0] for c in pairs], np.int32)
                    dst = np.asarray([c[1] for c in pairs], np.int32)
                    self.cache = self._copier(self.cache, src, dst)
                    self.stats.cow_copies += len(cow)
                self.cache["pos"] = jnp.asarray(pos_host)
                # the decode scan writes KV for EVERY row, every scan step
                # (stopped rows freeze their token but not the cache write at
                # pos). Free slots' table rows are already all-trash; a mid-
                # prefill slot's row holds REAL blocks at index 0, which a
                # write at pos=0 would corrupt — mask those rows to trash in
                # the dispatched copy (the pager's own tables are untouched)
                tables = self.pager.tables
                if any(s is not None and s.prefilling for s in self.slots):
                    tables = tables.copy()
                    for i, s in enumerate(self.slots):
                        if s is not None and s.prefilling:
                            tables[i, :] = TRASH_BLOCK
                self.cache["block_tables"] = jnp.asarray(tables)
                self.stats.blocks_in_use = self.pager.blocks_in_use
            plan.set_metadata(cow=len(cow))
        fn = self._get_chunk_fn(n)
        with TraceAnnotation("engine.decode", steps=n,
                             rows=len(decode_active), keys=keys,
                             rids=_rids(self.slots[i] for i in decode_active)):
            if self.fault_hook is not None:
                # after the (idempotent) pager commit, before the dispatch:
                # a fault here leaves the step cleanly retryable
                self.fault_hook("decode")
            out = fn(self.params, jnp.asarray(last), self.cache, self.rng,
                     jnp.asarray(stop), jnp.asarray(gen), jnp.asarray(budget),
                     *self._lora_args(self.adapter_slots))
        self.cache, self.rng = out.cache, out.rng
        with TraceAnnotation("engine.sync", what="decode"):
            toks = np.asarray(out.tokens)
            valid = np.asarray(out.valid)
        self.stats.steps += n
        self.stats.decode_chunks += 1
        self.stats.decode_tokens += int(valid.sum())
        self.stats.occupancy_sum += float(valid.sum()) / self.n_slots
        now = self._now()
        with TraceAnnotation("engine.emit"):
            for i in decode_active:
                r = self.slots[i]
                # a decoding row's first step is always valid: got >= 1
                for t in range(n):
                    if not valid[t, i]:
                        break
                    r.tokens.append(int(toks[t, i]))
                self._settle(i, now)
        if self.paged:
            self.stats.blocks_in_use = self.pager.blocks_in_use
        return True

    def run(self, max_steps: int = 10000):
        """Serve until drained or `max_steps` device decode steps ran."""
        while (self.queue or any(s is not None for s in self.slots)) \
                and max_steps > 0:
            before = self.stats.steps
            if not self.step(max_n=max_steps):
                break
            max_steps -= self.stats.steps - before
        return self.finished

    def _baked_settings(self) -> tuple:
        """What the jitted closures bake in of this engine's settings."""
        spec = self.speculate
        return (self.cfg, self.eos_id, self.max_len, self.greedy,
                self.n_slots, self.registry is None,
                getattr(self.registry, "scaling", None), self.paged,
                self.kv_block_size if self.paged else None,
                getattr(self, "num_blocks", None) if self.paged else None,
                self.mesh, spec, self.spec_k if spec else None,
                self.draft_bits if spec else None,
                self.draft_mode if spec else None, self.prefill_budget)

    def adopt_compiled(self, other: "ServeEngine"):
        """Inherit another engine's jitted callables (benchmark warmup:
        the timed engine starts compile-free). The adopted closures bake
        the source engine's config and stop semantics, so mismatched
        engines are rejected rather than silently decoding wrong tokens."""
        mine, theirs = self._baked_settings(), other._baked_settings()
        if mine != theirs:
            raise ValueError(
                "adopt_compiled: engines differ in (cfg, eos_id, max_len, "
                "greedy, n_slots, paged layout, mesh, speculation): "
                f"{mine} vs {theirs}")
        self._chunk_fns = other._chunk_fns
        self._spec_fns = other._spec_fns
        self._prefill_cache = other._prefill_cache
        self._writer = other._writer
        self._sampler = other._sampler
        return self

    def generate(self, prompts, max_new: int = 32, max_steps: int = 10000,
                 return_requests: bool = False, adapters=None):
        """Serve `prompts`; returns one token list per prompt (in order).

        adapters: optional per-prompt list of registered LoRA adapter
        names (None entries decode with the base model) — a mixed batch
        of base and N distinct adapters runs in the same waves/chunks.

        Requests still in flight after `max_steps` are cancelled: they come
        back with partial tokens and `truncated=True`, and their slots/queue
        entries are released so a later `generate()` starts clean instead of
        resuming (and mutating) already-returned results.
        `return_requests=True` returns the Request objects (tokens +
        truncated/prompt_truncated flags)."""
        if adapters is None:
            adapters = [None] * len(prompts)
        if len(adapters) != len(prompts):
            raise ValueError(f"adapters list length {len(adapters)} != "
                             f"{len(prompts)} prompts")
        start = len(self.finished)
        ids = []
        try:
            for p, a in zip(prompts, adapters):
                ids.append(self.submit(p, max_new, adapter=a))
            self.run(max_steps)
        except Exception:
            # leave the engine clean behind the propagating error: every
            # still-queued/running request from this call releases its
            # slot, pool blocks and adapter pins (the pin-leak fix)
            resolved = {r.rid for r in self.finished[start:]}
            for rid in ids:
                if rid not in resolved:
                    self._cancel(rid)
            raise
        want = set(ids)
        new = self.finished[start:]
        by_id = {r.rid: r for r in new}
        out = []
        for rid in ids:
            r = by_id.get(rid)
            if r is None:
                r = self._cancel(rid)
            out.append(r)
        # results are handed to the caller — drop them from the engine log so
        # a long-lived engine doesn't accumulate every request ever served
        del self.finished[start:]
        self.finished.extend(r for r in new if r.rid not in want)
        return out if return_requests else [r.tokens for r in out]

    def _cancel(self, rid: int) -> Request:
        """Evict an in-flight/queued request, returning it flagged truncated."""
        for i, s in enumerate(self.slots):
            if s is not None and s.rid == rid:
                self.slots[i] = None
                self.adapter_slots[i] = -1
                if self.paged:
                    self.pager.release_slot(i)
                if s.adapter is not None:
                    self.registry.release(s.adapter)
                s.truncated = True
                s.finish_reason = "cancelled"
                s._swap = None
                self.stats.truncated += 1
                return s
        for r in self.queue:
            if r.rid == rid:
                self.queue.remove(r)
                if r.adapter is not None:
                    self.registry.release(r.adapter)
                r.truncated = True
                r.finish_reason = "cancelled"
                r._swap = None
                self.stats.truncated += 1
                return r
        raise KeyError(f"request {rid} not found")
