"""Serving launcher: loads a checkpoint (or fresh weights), deploys through
the AxLLM quantized path, and serves a synthetic mixed-length request stream
through the continuous-batching engine.

  PYTHONPATH=src python -m repro.launch.serve --arch repro-100m \
      --requests 16 --max-new 32 [--no-quantize] [--kv-int8] \
      [--eos-id 0] [--long-prompt reject] [--lora 2] [--stats]

Flags of note:
  --decode-chunk N  on-device decode steps per dispatch (default cfg value,
                    8; 1 reproduces the per-token host round-trip loop)
  --paged           serve through the block-paged KV pool with radix-tree
                    prefix reuse (attention families; shared prompt heads
                    prefill once — see --kv-block-size/--prefix-cache)
  --kv-block-size N tokens per KV pool block (power of two, default 16)
  --prefix-cache    radix prefix index on the paged pool (default on;
                    --no-prefix-cache keeps paging but disables reuse)
  --num-blocks N    KV pool size in blocks (default: 2x dense equivalent)
  --fuse-qkv        rewrite deployed params to fused wqkv/gate_up
                    projections (one activation pass per block)
  --reuse           run quantized matmuls through the reuse (LUT) kernel
                    path (impl="reuse": Result-Cache gather on TPU, jnp
                    oracle elsewhere — token-identical to the multiply path)
  --quant-bits N    serve-path weight code width (default cfg.quant_bits)
  --quant-mode M    'affine' (symmetric uniform, default) or 'codebook'
                    (NF4 for 4-bit) deploy-quantization alphabet
  --eos-id N        per-slot stop token (overrides cfg.eos_id; -1 disables)
  --long-prompt P   'truncate' (keep the prompt tail, default) or 'reject'
                    prompts longer than max_len-1
  --prompt-lens L   comma list of prompt lengths cycled over the stream
                    (mixed lengths exercise the ragged prefill waves)
  --lora N          register N synthetic LoRA adapters and cycle requests
                    over base + adapters (the dual-pipeline serving path;
                    see also --lora-rank/--lora-alpha/--lora-targets/
                    --max-loras)
  --mesh-shape S    tensor-parallel serving mesh: a model-axis size ("8")
                    or "DATAxMODEL" ("2x4"); default "1" serves
                    single-device. Sizes > 1 on CPU force host devices
                    (see launch/mesh.py); sharded decode is
                    token-identical to single-device
  --arrival-rate A  open-loop arrivals ('poisson:<r>' / 'fixed:<r>'
                    requests/s) instead of submitting everything up front;
                    pairs with --admission/--max-queue/--priority/
                    --deadline-s for overload behavior
  --prefill-budget N  chunked prefill: cap prompt tokens prefilled per
                    engine step (paged only) so long prompts interleave
                    with running decodes instead of stalling them
  --stream          streaming output: tokens emitted via submit(on_token=)
                    at chunk-harvest time; prints per-stream counts
  --ttft-deadline-s / --itl-deadline-s
                    mid-run execution deadlines (time-to-first-token /
                    inter-token); a stream that blows one finishes as
                    'expired' with its resources freed
  --stats           print the engine's scheduler stats as JSON
                    (admitted/finished/truncated, tokens/step, occupancy)

The full flags table is documented in docs/ARCHITECTURE.md (CI's docs job
fails when this parser and that table drift apart).
"""

from __future__ import annotations

import argparse
import json
import math
import time

import jax
import numpy as np

from repro.configs import apply_overrides, get_config
from repro.launch.compile_cache import enable_compile_cache
from repro.models.model import get_model
from repro.serve.engine import ServeEngine
from repro.train import checkpoint as C


def make_synthetic_adapters(cfg, n: int, rank: int = 8, alpha: float = 16.0,
                            targets=("wq", "wv"), max_loras=None, seed=0):
    """Build an AdapterRegistry with ``n`` random (non-zero-B) adapters.

    Stands in for trained adapters in the launcher/benchmark: each
    adapter's B matrices are small random values so the delta pipeline
    measurably changes outputs without wrecking the base distribution.
    Returns (registry, [adapter names]).
    """
    import jax.numpy as jnp

    from repro.core.axllm_linear import LoRAConfig
    from repro.serve.adapters import AdapterRegistry, target_dims

    lcfg = LoRAConfig(rank=rank, alpha=alpha, targets=tuple(targets))
    reg = AdapterRegistry(cfg, lcfg,
                          max_loras=max_loras or max(4, n))
    rng = np.random.default_rng(seed)
    names = []
    for i in range(n):
        ad = {}
        for t in lcfg.targets:
            n_in, n_out = target_dims(cfg, t)
            ad[t] = {
                "lora_a": jnp.asarray(
                    rng.normal(size=(cfg.n_layers, n_in, rank))
                    / np.sqrt(rank), jnp.float32),
                "lora_b": jnp.asarray(
                    rng.normal(size=(cfg.n_layers, rank, n_out)) * 0.05,
                    jnp.float32),
            }
        name = f"adapter{i}"
        reg.add(name, ad)
        names.append(name)
    return reg, names


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="repro-100m")
    ap.add_argument("--ckpt", default="")
    ap.add_argument("--requests", type=int, default=8)
    ap.add_argument("--max-new", type=int, default=16)
    ap.add_argument("--slots", type=int, default=4)
    ap.add_argument("--max-len", type=int, default=256)
    ap.add_argument("--no-quantize", action="store_true")
    ap.add_argument("--reuse", action="store_true",
                    help="dispatch quantized matmuls through the reuse "
                         "(LUT) kernel path instead of multiply-dequant")
    ap.add_argument("--quant-bits", type=int, default=None,
                    help="weight code width for deploy quantization "
                         "(default: cfg.quant_bits)")
    ap.add_argument("--quant-mode", choices=("affine", "codebook"),
                    default="affine",
                    help="deploy-quantization alphabet (codebook = NF4 "
                         "for 4-bit)")
    ap.add_argument("--kv-int8", action="store_true")
    ap.add_argument("--decode-chunk", type=int, default=None,
                    help="on-device decode steps per dispatch (default: "
                         "cfg.decode_chunk)")
    ap.add_argument("--fuse-qkv", action=argparse.BooleanOptionalAction,
                    default=None,
                    help="fused wqkv/gate_up projections (--no-fuse-qkv "
                         "overrides a config that enables them)")
    ap.add_argument("--paged", action="store_true",
                    help="block-paged KV cache with radix-tree prefix "
                         "reuse (attention families only)")
    ap.add_argument("--kv-block-size", type=int, default=16,
                    help="tokens per KV pool block (power of two)")
    ap.add_argument("--prefix-cache", action=argparse.BooleanOptionalAction,
                    default=True,
                    help="radix prefix index on the paged pool (disable "
                         "to page without reuse)")
    ap.add_argument("--num-blocks", type=int, default=None,
                    help="KV pool blocks (default: 2x the dense-equivalent "
                         "capacity plus trash and CoW spare)")
    ap.add_argument("--eos-id", type=int, default=None,
                    help="stop token id (-1: disable even if cfg sets one)")
    ap.add_argument("--long-prompt", choices=("truncate", "reject"),
                    default="truncate")
    ap.add_argument("--prompt-lens", default="8,12,31",
                    help="comma list of prompt lengths cycled over requests")
    ap.add_argument("--lora", type=int, default=0,
                    help="register N synthetic LoRA adapters and cycle "
                         "requests over base + adapters (0: base only)")
    ap.add_argument("--lora-rank", type=int, default=8,
                    help="adapter rank (all registered adapters share it)")
    ap.add_argument("--lora-alpha", type=float, default=16.0,
                    help="adapter alpha (scaling = alpha / rank)")
    ap.add_argument("--lora-targets", default="wq,wv",
                    help="comma list of attention projections the adapters "
                         "target (subset of wq,wk,wv,wo)")
    ap.add_argument("--max-loras", type=int, default=None,
                    help="registry capacity (default: max(4, --lora))")
    ap.add_argument("--mesh-shape", default="1",
                    help="tensor-parallel serving mesh: model-axis size "
                         "('8') or 'DATAxMODEL' ('2x4'); '1' (default) "
                         "serves single-device")
    ap.add_argument("--arrival-rate", default=None,
                    help="open-loop arrivals: 'poisson:<rate>' or "
                         "'fixed:<rate>' requests/s submitted on their own "
                         "clock (default: closed-loop, all requests "
                         "submitted up front)")
    ap.add_argument("--admission", choices=("block", "reject", "evict"),
                    default="block",
                    help="policy when the wait queue is full: block the "
                         "submitter, reject the newcomer, or evict the "
                         "lowest-priority queued request")
    ap.add_argument("--max-queue", type=int, default=None,
                    help="wait-queue bound that arms --admission "
                         "(default: unbounded)")
    ap.add_argument("--priority", default="0",
                    help="comma list of priorities cycled over requests "
                         "(higher preempts lower under overload)")
    ap.add_argument("--deadline-s", type=float, default=None,
                    help="queue-wait deadline per request; requests not "
                         "admitted in time finish as 'expired'")
    ap.add_argument("--prefill-budget", type=int, default=None,
                    help="chunked prefill: max prompt tokens prefilled per "
                         "engine step (paged only; bounds step time so "
                         "long prompts interleave with decode)")
    ap.add_argument("--stream", action="store_true",
                    help="streaming output: emit tokens through "
                         "submit(on_token=) at chunk-harvest time and "
                         "report per-stream counts")
    ap.add_argument("--ttft-deadline-s", type=float, default=None,
                    help="execution deadline on time-to-first-token; a "
                         "request that blows it finishes as 'expired'")
    ap.add_argument("--itl-deadline-s", type=float, default=None,
                    help="execution deadline on inter-token latency; a "
                         "stream that stalls longer finishes as 'expired'")
    ap.add_argument("--speculate", action="store_true",
                    help="self-speculative decoding: a low-bit draft of the "
                         "same model proposes --spec-k tokens per round, the "
                         "serving-precision target verifies them in one "
                         "chunked dispatch (bit-identical to target-only "
                         "greedy)")
    ap.add_argument("--spec-k", type=int, default=4,
                    help="draft tokens proposed per speculation round")
    ap.add_argument("--draft-bits", type=int, default=4,
                    help="draft quantization width (default int4)")
    ap.add_argument("--draft-mode",
                    choices=("affine", "codebook", "shiftadd"),
                    default="affine",
                    help="draft weight reconstruction: affine/codebook "
                         "low-bit quantization or the shift-add binary "
                         "reparameterization")
    ap.add_argument("--stats", action="store_true",
                    help="print scheduler stats JSON after the run")
    ap.add_argument("--set", action="append", default=[])
    args = ap.parse_args(argv)
    enable_compile_cache()

    # mesh construction precedes the first jax computation: on CPU the
    # host-device forcing flag only takes effect before backend init
    from repro.launch.mesh import make_serve_mesh, parse_mesh_shape
    mesh = None
    if math.prod(parse_mesh_shape(args.mesh_shape)) > 1:
        mesh = make_serve_mesh(args.mesh_shape)

    cfg = get_config(args.arch)
    overrides = dict(kv.split("=", 1) for kv in args.set)
    if args.kv_int8:
        overrides["quant_kv"] = "true"
    if overrides:
        cfg = apply_overrides(cfg, overrides)

    api = get_model(cfg)
    params = api.init(jax.random.PRNGKey(0))
    if args.ckpt and C.latest_step(args.ckpt) is not None:
        from repro.optim import adamw
        opt = adamw.init(params, adamw.AdamWConfig())
        (params, _), step = C.restore(args.ckpt, (params, opt))
        print(f"restored step {step} from {args.ckpt}")

    eos_id = args.eos_id
    if eos_id is not None and eos_id < 0:
        eos_id = None
        cfg = apply_overrides(cfg, {"eos_id": "none"})

    registry = None
    adapter_cycle = [None]
    if args.lora > 0:
        registry, names = make_synthetic_adapters(
            cfg, n=args.lora, rank=args.lora_rank, alpha=args.lora_alpha,
            targets=tuple(t for t in args.lora_targets.split(",") if t),
            max_loras=args.max_loras)
        adapter_cycle = [None] + names
        print(f"registered {len(names)} LoRA adapters "
              f"(rank {args.lora_rank}, targets {args.lora_targets}); "
              f"requests cycle over base + {names}")

    eng = ServeEngine(cfg, params, n_slots=args.slots,
                      max_len=args.max_len,
                      quantize=not args.no_quantize,
                      quant_bits=args.quant_bits,
                      quant_mode=args.quant_mode,
                      impl="reuse" if args.reuse else "auto",
                      eos_id=eos_id, long_prompt=args.long_prompt,
                      decode_chunk=args.decode_chunk,
                      fuse_qkv=args.fuse_qkv, adapters=registry,
                      paged=args.paged, kv_block_size=args.kv_block_size,
                      num_blocks=args.num_blocks,
                      prefix_cache=args.prefix_cache, mesh=mesh,
                      max_queue=args.max_queue, admission=args.admission,
                      speculate=args.speculate, spec_k=args.spec_k,
                      draft_bits=args.draft_bits,
                      draft_mode=args.draft_mode,
                      prefill_budget=args.prefill_budget)
    rng = np.random.default_rng(0)
    lens = [int(x) for x in args.prompt_lens.split(",") if x]
    prompts = [rng.integers(0, cfg.vocab_size,
                            size=lens[i % len(lens)]).astype(np.int32)
               for i in range(args.requests)]
    adapters = [adapter_cycle[i % len(adapter_cycle)]
                for i in range(args.requests)]
    prios = [int(x) for x in args.priority.split(",") if x] or [0]
    streamed = {"tokens": 0, "streams": set()}
    on_token = None
    if args.stream:
        def on_token(req, tok):
            streamed["tokens"] += 1
            streamed["streams"].add(req.rid)
    per_req = dict(on_token=on_token,
                   ttft_deadline_s=args.ttft_deadline_s,
                   itl_deadline_s=args.itl_deadline_s)
    t0 = time.time()
    if args.arrival_rate:
        # open-loop: requests land on their own clock; the engine keeps
        # stepping between arrivals and sheds per --admission/--deadline-s
        from repro.serve.scheduler import arrival_times
        at = arrival_times(args.arrival_rate, len(prompts))
        i = 0
        while True:
            now = time.time() - t0
            while i < len(prompts) and at[i] <= now:
                eng.submit(prompts[i], max_new=args.max_new,
                           adapter=adapters[i],
                           priority=prios[i % len(prios)],
                           deadline_s=args.deadline_s, **per_req)
                i += 1
            if eng.step():
                continue
            if i >= len(prompts):
                break
            time.sleep(min(0.002, max(0.0, at[i] - (time.time() - t0))))
        reqs = list(eng.finished)
    elif args.stream or args.ttft_deadline_s is not None \
            or args.itl_deadline_s is not None:
        # closed-loop but per-request streaming/deadline state: submit
        # explicitly instead of going through generate()
        for i, p in enumerate(prompts):
            eng.submit(p, max_new=args.max_new, adapter=adapters[i],
                       priority=prios[i % len(prios)],
                       deadline_s=args.deadline_s, **per_req)
        while eng.step():
            pass
        reqs = list(eng.finished)
    else:
        reqs = eng.generate(prompts, max_new=args.max_new,
                            return_requests=True, adapters=adapters)
    dt = time.time() - t0
    toks = sum(len(r.tokens) for r in reqs)
    bits = cfg.quant_bits if args.quant_bits is None else args.quant_bits
    mode = "bf16" if args.no_quantize else (
        f"axllm-{args.quant_mode}{bits}"
        + ("+reuse" if args.reuse else ""))
    lora_tag = f", {eng.stats.lora_requests} LoRA requests" if args.lora \
        else ""
    mesh_tag = f", mesh {args.mesh_shape}" if mesh is not None else ""
    devs = jax.devices()
    print(f"[{mode}] {len(reqs)} requests, {toks} tokens, "
          f"{toks/dt:.1f} tok/s, occupancy "
          f"{eng.stats.mean_occupancy:.2f}{lora_tag}{mesh_tag} "
          f"(device {devs[0].platform}/{devs[0].device_kind} x{len(devs)})")
    if args.arrival_rate:
        st = eng.stats
        print(f"  open-loop [{args.arrival_rate}, admission="
              f"{args.admission}]: rejected={st.rejected} "
              f"expired={st.expired} preempted={st.preempted} "
              f"restored={st.restored} ({st.fast_restores} fast)")
    if args.stream:
        st = eng.stats
        print(f"  streaming: {streamed['tokens']} tokens emitted across "
              f"{len(streamed['streams'])} streams at chunk harvest "
              f"(cancelled={st.cancelled}, expired={st.expired})")
    if args.prefill_budget:
        st = eng.stats
        print(f"  chunked prefill [budget={args.prefill_budget}]: "
              f"{st.prefill_chunks} chunks over {st.prefill_waves} waves, "
              f"{st.preempted_prefill} mid-prefill preemptions")
    if args.speculate:
        st = eng.stats
        print(f"  speculative [k={args.spec_k}, "
              f"{args.draft_mode}{args.draft_bits} draft]: "
              f"{st.accepted_draft_tokens}/{st.drafted_tokens} drafts "
              f"accepted ({st.acceptance_rate:.2f}), "
              f"{st.accepted_tokens_per_step:.2f} tokens/slot-round "
              f"over {st.spec_rounds} rounds")
    if args.paged:
        print(f"  paged: {eng.stats.prefix_hit_tokens} prefix-hit tokens, "
              f"{eng.stats.blocks_in_use} blocks cached, "
              f"{eng.stats.cow_copies} CoW copies "
              f"(block={args.kv_block_size}, "
              f"prefix_cache={'on' if args.prefix_cache else 'off'})")
    for r in reqs[:3]:
        tag = " [truncated]" if r.truncated else ""
        ad = f" [{r.adapter}]" if r.adapter else ""
        print(f"  -> {r.tokens[:12]}{tag}{ad}")
    if args.stats:
        print(json.dumps(eng.stats.as_dict(), indent=2, sort_keys=True))


if __name__ == "__main__":
    main()
