"""Chip smoke: repro-100m at its published widths, served on a TPU.

    python chip_smoke.py             # one chip: kernels, then engine modes
    python chip_smoke.py --chips 4   # tensor-parallel engine on a 4-chip host

Runs everything in this one process, from seeded random weights (no
checkpoint, nothing under results/). Phases, one line each:

  a. device   the first device must be a TPU; anything else exits non-zero
  b. kernels  every Pallas kernel of the serving path, compiled (never
              interpret mode) at the shapes repro-100m's prefill and decode
              produce, against its jnp oracle run on the chip at float32
              precision
  c. engine   ServeEngine answers 8 mixed-length requests in five modes
              (bf16, int8, int8 paged with a shared prompt head, int8 with
              two LoRA adapters, int8 through the reuse kernel); the int8
              stream is also served with impl="ref" and the agreement shown

With ``--chips 4`` only the tensor-parallel path runs: the engine on a
(1, 4) mesh — repro-100m's 4 KV heads, one per chip — int8 dense and paged,
against the same engine unmeshed on device 0.

The last line of standard output is the JSON verdict
``{"ok": true, "device": {...}}``; any failure raises before it is printed.
Every time printed is host wall clock: informational, not a device metric.
"""

from __future__ import annotations

import argparse
import functools
import json
import os
import sys
import time

ROOT = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.join(ROOT, "src"))

MAX_NEW = 16
N_SLOTS = 4
MAX_LEN = 256
PROMPT_LENS = (5, 12, 31, 64)        # a handful of lengths bounds compiles
N_REQUESTS = 8


def say(phase: str, msg: str):
    print(f"[{phase}] {msg}", flush=True)


def check(cond, msg: str):
    if not cond:
        raise AssertionError(msg)


class CompileLog:
    """Totals of JAX's compile events: backend compile seconds (XLA and
    Mosaic) and persistent-cache hits/misses."""

    def __init__(self):
        import jax.monitoring as mon
        self.compile_s = 0.0
        self.hits = self.misses = 0

        def on_duration(name, secs, **_):
            if name == "/jax/core/compile/backend_compile_duration":
                self.compile_s += secs

        def on_event(name, **_):
            if name == "/jax/compilation_cache/cache_hits":
                self.hits += 1
            elif name == "/jax/compilation_cache/cache_misses":
                self.misses += 1

        mon.register_event_duration_secs_listener(on_duration)
        mon.register_event_listener(on_event)

    def line(self) -> str:
        return (f"backend compile {self.compile_s:.1f} s, persistent cache "
                f"{self.hits} hits / {self.misses} misses")


# ---------------------------------------------------------------------------
# a. device
# ---------------------------------------------------------------------------

def phase_device(chips: int):
    import jax
    devs = jax.devices()
    d = devs[0]
    say("a", f"device {d.platform} kind={d.device_kind!r} count={len(devs)}")
    check(d.platform == "tpu", f"no TPU: JAX's first device is {d.platform}")
    check(len(devs) >= chips,
          f"--chips {chips} needs {chips} TPU devices, found {len(devs)}")
    return devs


# ---------------------------------------------------------------------------
# b. kernels at real widths
# ---------------------------------------------------------------------------

def _max_err(got, want) -> float:
    """max |got - want| / max |want| (scale-free across kernels)."""
    import numpy as np
    got = np.asarray(got, np.float64)
    want = np.asarray(want, np.float64)
    return float(np.abs(got - want).max() / max(np.abs(want).max(), 1e-30))


def _has_kernel(fn, *args) -> bool:
    import jax
    return "tpu_custom_call" in jax.jit(fn).lower(*args).as_text()


def phase_kernels(cfg, seed: int):
    import jax
    import jax.numpy as jnp
    import numpy as np

    from repro.core.quantization import QuantConfig, quantize
    from repro.kernels import ops, ref

    check(ops._use_pallas("auto"), "impl='auto' does not pick Pallas here")
    rng = np.random.default_rng(seed)
    d, dff = cfg.d_model, cfg.d_ff
    h, hk, hd = cfg.n_heads, cfg.n_kv_heads, cfg.resolved_head_dim

    def normal(shape, dtype=jnp.bfloat16):
        return jnp.asarray(rng.standard_normal(shape), dtype)

    def oracle(fn, *args, **kw):
        # the oracle is the float32 reference: XLA's default TPU matmul
        # precision would round its f32 operands to bf16
        with jax.default_matmul_precision("highest"):
            return jax.jit(functools.partial(fn, **kw))(*args)

    def report(name, got, want, tol, why):
        err = _max_err(got, want)
        say("b", f"{name}: max err {err:.2e} (tol {tol:g}: {why})")
        check(np.isfinite(err) and err <= tol, f"{name} outside tolerance")

    # quantized matmuls: decode m = n_slots rows, prefill m = 4 x 32 rows;
    # (K, N) of q/o (d x d), k/v (d x hk*hd), gate/up (d x d_ff), down
    mm_tol, mm_why = 1e-3, ("inputs exact in both; f32 sums over K <= "
                            f"{dff} in another order")
    shapes = [(N_SLOTS, d, d), (N_SLOTS, d, hk * hd), (N_SLOTS, d, dff),
              (N_SLOTS, dff, d), (128, d, h * hd), (128, dff, d)]
    for m, k, n in shapes:
        x = normal((m, k))
        w = normal((k, n), jnp.float32) * 0.05
        for tag, qcfg in (("int8", QuantConfig(8, "affine", "per_channel")),
                          ("nf4-packed", QuantConfig(4, "codebook",
                                                     "per_channel",
                                                     pack=True))):
            qt = quantize(w, qcfg)
            check(qt.packed == (tag == "nf4-packed"), f"{tag} packing")
            got = jax.jit(lambda x, qt: ops.axllm_matmul(
                x, qt, impl="pallas", out_dtype=jnp.float32))(x, qt)
            report(f"axllm_matmul {tag} m{m} {k}x{n}", got,
                   oracle(ref.axllm_matmul_ref, x, qt), mm_tol, mm_why)
    for m, k, n in shapes[:1] + shapes[4:5]:
        x = normal((m, k))
        qt = quantize(normal((k, n), jnp.float32) * 0.05,
                      QuantConfig(8, "affine", "per_channel"))
        got, mults = jax.jit(lambda x, qt: ops.reuse_matmul(
            x, qt, impl="pallas", out_dtype=jnp.float32,
            with_stats=True))(x, qt)
        report(f"reuse_matmul int8 m{m} {k}x{n}", got,
               oracle(ref.reuse_matmul_ref, x, qt), mm_tol, mm_why)
        bn = ops.pick_blocks(m, k, n)[2]
        want = ref.reuse_mult_count(qt, bn)
        say("b", f"reuse_matmul m{m} {k}x{n}: measured multiplies "
                 f"{int(mults)} == predicted {want}")
        check(int(mults) == want, "reuse multiply count")

    # attention: prefill waves of 4 rows at padded lengths 32 and 256;
    # decode over a max_len cache and a paged pool of 16-token blocks
    at_tol, at_why = 1e-2, ("bf16 output rounding (2^-9 relative) plus "
                            "online-softmax reassociation")
    for s in (32, MAX_LEN):
        q, k, v = normal((4, s, h, hd)), normal((4, s, hk, hd)), \
            normal((4, s, hk, hd))
        got = jax.jit(lambda q, k, v: ops.flash_attention(
            q, k, v, impl="pallas"))(q, k, v)
        report(f"flash_attention b4 s{s}", got,
               oracle(ref.attention_ref, q, k, v, causal=True),
               at_tol, at_why)
    q = normal((N_SLOTS, h, hd))
    length = jnp.asarray([1, 17, 200, MAX_LEN], jnp.int32)
    kc, vc = normal((N_SLOTS, MAX_LEN, hk, hd)), normal((N_SLOTS, MAX_LEN,
                                                          hk, hd))
    # per-(position, head) int8 KV exactly as the serving cache holds it
    from repro.models.attention import _quantize_kv
    (kq, ks), (vq, vs) = _quantize_kv(kc), _quantize_kv(vc)
    for tag, args, kw in (("bf16", (q, kc, vc, length), {}),
                          ("int8-KV", (q, kq, vq, length),
                           dict(k_scale=ks, v_scale=vs))):
        got = jax.jit(lambda *a, **k_: ops.decode_attention(
            *a, impl="pallas", **k_))(*args, **kw)
        report(f"decode_attention {tag} b{N_SLOTS} s{MAX_LEN}", got,
               oracle(ref.decode_attention_ref, *args, **kw),
               at_tol, at_why)
    bs = 16
    mb = MAX_LEN // bs
    nb = 2 * N_SLOTS * mb + 2
    tables = jnp.asarray(rng.permutation(np.arange(1, nb))[:N_SLOTS * mb]
                         .reshape(N_SLOTS, mb), jnp.int32)
    kp, vp = normal((nb, bs, hk, hd)), normal((nb, bs, hk, hd))
    (kpq, kps), (vpq, vps) = _quantize_kv(kp), _quantize_kv(vp)
    for tag, args, kw in (("bf16", (q, kp, vp, length), {}),
                          ("int8-KV", (q, kpq, vpq, length),
                           dict(k_scale=kps, v_scale=vps))):
        got = jax.jit(lambda q, k, v, ln, **k_: ops.decode_attention(
            q, k, v, ln, block_tables=tables, impl="pallas", **k_))(
                *args, **kw)
        want = oracle(lambda q, k, v, ln, **k_: ref.paged_decode_attention_ref(
            q, k, v, tables, ln, **k_), *args, **kw)
        report(f"paged_decode_attention {tag} b{N_SLOTS} blocks{nb}x{bs}",
               got, want, at_tol, at_why)

    w = normal((dff, d), jnp.float32)
    codes, scale = jax.jit(lambda w: ops.quantize_channels(
        w, impl="pallas"))(w)
    rc, rs = oracle(ref.quantize_ref, w)
    diff = np.abs(np.asarray(codes, np.int32) - np.asarray(rc, np.int32))
    say("b", f"quantize_channels {dff}x{d}: scale err "
             f"{_max_err(scale, rs):.2e}, codes off by one at "
             f"{int((diff > 0).sum())} of {diff.size} (tol: none off by "
             "more than one — a rounding tie may break either way)")
    check(diff.max() <= 1 and _max_err(scale, rs) <= 1e-6,
          "quantize_channels")

    # impl="auto" resolves to the compiled kernels on this backend
    qt = quantize(normal((d, d), jnp.float32),
                  QuantConfig(8, "affine", "per_channel"))
    auto = {
        "axllm_matmul": _has_kernel(lambda x: ops.axllm_matmul(x, qt),
                                    normal((N_SLOTS, d))),
        "flash_attention": _has_kernel(
            lambda q, k, v: ops.flash_attention(q, k, v),
            normal((4, 32, h, hd)), normal((4, 32, hk, hd)),
            normal((4, 32, hk, hd))),
        "decode_attention": _has_kernel(
            lambda q, k, v: ops.decode_attention(q, k, v, length), q, kc, vc),
        "paged_decode_attention": _has_kernel(
            lambda q, k, v: ops.decode_attention(q, k, v, length,
                                                 block_tables=tables),
            q, kp, vp),
        "reuse_matmul": _has_kernel(
            lambda x: ops.axllm_matmul(x, qt, impl="reuse"),
            normal((N_SLOTS, d))),
    }
    say("b", "impl='auto'/'reuse' lower to Pallas kernels: "
             + ", ".join(f"{k}={v}" for k, v in auto.items()))
    check(all(auto.values()), "impl='auto' skipped a kernel")


# ---------------------------------------------------------------------------
# c. engine modes
# ---------------------------------------------------------------------------

def _prompts(cfg, seed: int, shared_head: bool = False):
    import numpy as np
    rng = np.random.default_rng(seed)
    prompts = [rng.integers(0, cfg.vocab_size, size=PROMPT_LENS[
        i % len(PROMPT_LENS)]).astype(np.int32) for i in range(N_REQUESTS)]
    if shared_head:
        # requests 3 and 7 (64 tokens, admitted in different waves) open
        # with the same 32 tokens: two full 16-token blocks to hit
        prompts[7][:32] = prompts[3][:32]
    return prompts


def _serve(cfg, params, prompts, names=None, **kw):
    from repro.serve.engine import ServeEngine
    eng = ServeEngine(cfg, params, n_slots=N_SLOTS, max_len=MAX_LEN, **kw)
    t0 = time.perf_counter()
    reqs = eng.generate(prompts, max_new=MAX_NEW, return_requests=True,
                        adapters=names)
    wall = time.perf_counter() - t0
    check(len(reqs) == len(prompts), "lost requests")
    for r in reqs:
        check(r.finish_reason == "max_new" and len(r.tokens) == MAX_NEW,
              f"request {r.rid}: {r.finish_reason}, {len(r.tokens)} tokens")
        check(all(0 <= t < cfg.vocab_size for t in r.tokens),
              f"request {r.rid}: token out of range")
    return eng, [r.tokens for r in reqs], wall


def _kernels_in_decode(eng) -> int:
    import jax
    import jax.numpy as jnp
    tok = jnp.zeros((eng.n_slots,), jnp.int32)
    with eng._mesh_ctx():
        text = jax.jit(eng.api.decode).lower(eng.params, tok,
                                             eng.cache).as_text()
    return text.count("tpu_custom_call")


def phase_engine(cfg, params, seed: int):
    from repro.launch.serve import make_synthetic_adapters

    prompts = _prompts(cfg, seed)
    registry, names = make_synthetic_adapters(cfg, 2, seed=seed)
    cycle = [None] + names
    modes = [
        ("bf16", {}, dict(quantize=False)),
        ("int8", {}, dict(quantize=True)),
        ("int8-paged", dict(shared_head=True),
         dict(quantize=True, paged=True)),
        ("int8-lora2", {}, dict(quantize=True, adapters=registry)),
        ("int8-reuse", {}, dict(quantize=True, impl="reuse")),
    ]
    streams = {}
    for name, pkw, kw in modes:
        ps = _prompts(cfg, seed, **pkw)
        ads = [cycle[i % len(cycle)] for i in range(len(ps))] \
            if "adapters" in kw else None
        eng, toks, wall = _serve(cfg, params, ps, names=ads, **kw)
        streams[name] = toks
        st = eng.stats
        extra = ""
        if kw.get("paged"):
            check(st.prefix_hit_tokens > 0, "paged: no radix prefix hit")
            extra = (f", prefix-hit tokens {st.prefix_hit_tokens} (suffix "
                     "prefill runs ops.prefix_attention: a jnp path XLA "
                     "compiles for the chip, not a Pallas kernel and not a "
                     "fallback)")
        if "adapters" in kw:
            check(st.lora_requests > 0, "no LoRA request served")
            extra = f", LoRA requests {st.lora_requests}"
        n_kernels = _kernels_in_decode(eng)
        check(n_kernels > 0, f"{name}: decode step has no Pallas kernel")
        say("c", f"{name}: {len(toks)}/{len(ps)} requests finished, "
                 f"{sum(map(len, toks))} tokens in range, "
                 f"{n_kernels} Pallas calls in the decode step{extra}; "
                 f"wall {wall:.2f} s (informational)")
    _, ref_toks, _ = _serve(cfg, params, prompts, quantize=True, impl="ref")
    same = sum(a == b for a, b in zip(streams["int8"], ref_toks))
    agree = [_common_prefix(a, b) for a, b in zip(streams["int8"], ref_toks)]
    say("c", f"int8 kernels vs impl='ref' on the chip: {same}/{len(agree)} "
             f"streams identical, agreeing prefix lengths {agree} of "
             f"{MAX_NEW} (random weights give near-tied logits; "
             "informational)")


def _common_prefix(a, b) -> int:
    n = 0
    for x, y in zip(a, b):
        if x != y:
            break
        n += 1
    return n


# ---------------------------------------------------------------------------
# --chips 4: tensor-parallel engine vs the same engine on device 0
# ---------------------------------------------------------------------------

def phase_mesh(cfg, params, seed: int, chips: int):
    import jax
    import jax.numpy as jnp
    import numpy as np

    from repro.launch.mesh import make_serve_mesh

    mesh = make_serve_mesh(str(chips))
    check(mesh.devices.size == chips and all(
        dv.platform == "tpu" for dv in mesh.devices.flat),
        f"mesh is not {chips} TPU chips")
    for name, kw, shared in (("int8", dict(quantize=True), False),
                             ("int8-paged", dict(quantize=True, paged=True),
                              True)):
        ps = _prompts(cfg, seed, shared_head=shared)
        eng1, base, w1 = _serve(cfg, params, ps, **kw)
        engm, got, wm = _serve(cfg, params, ps, mesh=mesh, **kw)
        kv = engm.cache["k"].sharding.spec
        say("mesh", f"{name}: KV cache spec {kv}, decode step has "
                    f"{_kernels_in_decode(engm)} Pallas calls per shard; "
                    f"wall {wm:.2f} s meshed vs {w1:.2f} s on device 0 "
                    "(informational)")
        if got == base:
            say("mesh", f"{name}: {len(got)} streams token-identical to "
                        "the unmeshed engine")
            continue
        # an all-reduce in another order may flip a near-tied argmax:
        # then hold the prefill logits to a tolerance and show where
        # the streams part
        toks = jnp.asarray(np.stack([p[:PROMPT_LENS[0]] for p in ps]))

        def logits(eng):
            with eng._mesh_ctx():
                return jax.jit(lambda p, t: eng.api.prefill(
                    p, {"tokens": t}, eng.api.init_cache(t.shape[0],
                                                         MAX_LEN))[0])(
                    eng.params, toks)

        err = _max_err(logits(engm), logits(eng1))
        parts = [_common_prefix(a, b) for a, b in zip(got, base)]
        say("mesh", f"{name}: streams diverge after {parts} of {MAX_NEW} "
                    f"tokens; prefill logits max err {err:.2e} (tol 2e-2: "
                    "bf16 activations re-rounded after a 4-way f32 "
                    "all-reduce)")
        check(err <= 2e-2, f"{name}: meshed prefill logits off")


# ---------------------------------------------------------------------------

def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="4: run only the tensor-parallel phase on a "
                         "four-chip host")
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)
    try:
        import repro  # noqa: F401
    except ImportError:
        sys.exit("chip_smoke.py: the repro package (src/) is not beside "
                 "this script")

    import jax

    from repro.configs import get_config
    from repro.launch.compile_cache import enable_compile_cache
    from repro.models.model import get_model

    t0 = time.perf_counter()
    cache_dir = enable_compile_cache()
    log = CompileLog()
    devs = phase_device(args.chips)
    say("a", f"compile cache {cache_dir}")
    cfg = get_config("repro-100m")
    params = get_model(cfg).init(jax.random.PRNGKey(args.seed))
    say("a", f"{cfg.name}: {cfg.n_layers} x d{cfg.d_model}, "
             f"{cfg.n_heads} q / {cfg.n_kv_heads} kv heads, hd "
             f"{cfg.resolved_head_dim}, d_ff {cfg.d_ff}, vocab "
             f"{cfg.vocab_size}, {cfg.dtype} params from seed {args.seed}")
    if args.chips == 1:
        phase_kernels(cfg, args.seed)
        say("b", log.line())
        phase_engine(cfg, params, args.seed)
    else:
        phase_mesh(cfg, params, args.seed, args.chips)
    stats = devs[0].memory_stats() or {}
    say("done", f"{log.line()}; peak device memory "
                f"{stats.get('peak_bytes_in_use', 'not reported')} bytes; "
                f"wall {time.perf_counter() - t0:.1f} s (informational)")
    print(json.dumps({"ok": True, "device": {
        "platform": devs[0].platform, "kind": devs[0].device_kind,
        "count": len(devs)}}), flush=True)


if __name__ == "__main__":
    main()
