"""Kernel-level microbench on the XLA fallback path (CPU container; the
Pallas kernels target TPU and are validated in interpret mode). Measures the
byte-traffic effect of the AxLLM representation (int8/int4 vs bf16 matmul),
the fused-QKV projection vs three separate matmuls, chunked scan-decode vs
the per-token dispatch loop, sweeps the decode-shape block table
(validating every (bm, bk, bn) choice in Pallas interpret mode), and
records the predicted-vs-achieved computation-reuse rows (simulator
analytic vs the reuse kernel's own multiply counter — see _reuse_rows).
Every row carries {impl, backend, units} provenance (benchmarks.common.row)
so tools/check_bench.py never compares a CPU ref timing against a Pallas
kernel result.

benchmarks/run.py persists these rows to BENCH_kernel.json at the repo root
so the kernel perf trajectory accumulates per-commit."""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from benchmarks.common import Row, row, timeit
from repro.core.quantization import QuantConfig, qconcat, quantize
from repro.kernels import ops


def _matmul_rows(rows, rng):
    m, k, n = 8, 4096, 4096          # decode-like skinny matmul
    x = jnp.asarray(rng.standard_normal((m, k)), jnp.float32)
    w = jnp.asarray(rng.standard_normal((k, n)), jnp.float32)
    qt8 = quantize(w, QuantConfig(8, "affine", "per_channel"))
    qt4 = quantize(w, QuantConfig(4, "affine", "per_channel", pack=True))

    f_fp = jax.jit(lambda a, b: a @ b)
    f_q8 = jax.jit(lambda a, q: ops.axllm_matmul(a, q, impl="ref"))

    t_fp = timeit(f_fp, x, w)
    t_q8 = timeit(f_q8, x, qt8)
    t_q4 = timeit(f_q8, x, qt4)
    bytes_fp = k * n * 4
    bytes_q8 = k * n + n * 4
    bytes_q4 = k * n // 2 + n * 4
    rows.append(row("kernel/matmul_f32", t_fp,
                    f"weight_bytes={bytes_fp}", impl="jnp"))
    rows.append(row("kernel/matmul_axllm_int8", t_q8,
                    f"weight_bytes={bytes_q8} ({bytes_fp/bytes_q8:.1f}x "
                    f"less)", impl="ref"))
    rows.append(row("kernel/matmul_axllm_int4", t_q4,
                    f"weight_bytes={bytes_q4} ({bytes_fp/bytes_q4:.1f}x "
                    f"less)", impl="ref"))


def _fused_qkv_rows(rows, rng):
    """One [K, (H+2Hk)·hd] fused matmul vs three separate Q/K/V matmuls
    (GQA shapes: the K/V projections are narrower than Q)."""
    m, k = 8, 2048
    n_q, n_kv = 2048, 512
    qcfg = QuantConfig(8, "affine", "per_channel")
    x = jnp.asarray(rng.standard_normal((m, k)), jnp.float32)
    wq = quantize(jnp.asarray(rng.standard_normal((k, n_q)), jnp.float32),
                  qcfg)
    wk = quantize(jnp.asarray(rng.standard_normal((k, n_kv)), jnp.float32),
                  qcfg)
    wv = quantize(jnp.asarray(rng.standard_normal((k, n_kv)), jnp.float32),
                  qcfg)
    wqkv = qconcat([wq, wk, wv])

    f3 = jax.jit(lambda a, q1, q2, q3: (
        ops.axllm_matmul(a, q1, impl="ref"),
        ops.axllm_matmul(a, q2, impl="ref"),
        ops.axllm_matmul(a, q3, impl="ref")))
    f1 = jax.jit(lambda a, q: ops.axllm_matmul(a, q, impl="ref"))
    t3 = timeit(f3, x, wq, wk, wv)
    t1 = timeit(f1, x, wqkv)
    rows.append(row("kernel/qkv_3matmuls", t3,
                    "3 launches; 3 codebook loads", impl="ref"))
    rows.append(row("kernel/qkv_fused", t1,
                    f"1 launch; {t3/max(t1, 1e-9):.2f}x vs separate",
                    impl="ref"))


def _chunked_decode_rows(rows):
    """Per-token dispatch loop (host sync + sample every step) vs one
    on-device decode_steps scan — the serve engine's hot-loop choice."""
    from repro.configs.base import ModelConfig
    from repro.models.model import get_model
    from repro.serve.decode import decode_steps

    cfg = ModelConfig(name="kb-decode", family="dense", n_layers=2,
                      d_model=64, n_heads=4, n_kv_heads=2, d_ff=128,
                      vocab_size=256, head_dim=16, vocab_pad_multiple=64,
                      dtype="float32")
    api = get_model(cfg)
    params = api.init(jax.random.PRNGKey(0))
    b, steps = 4, 16
    cache = api.init_cache(b, 64)
    toks = jnp.ones((b, 8), jnp.int32)
    logits, cache = jax.jit(
        lambda p, t, c: api.prefill(p, {"tokens": t}, c))(params, toks, cache)
    last = jnp.argmax(logits[:, : cfg.vocab_size], -1).astype(jnp.int32)
    dec = jax.jit(api.decode)

    def per_token(params, last, cache):
        for _ in range(steps):
            lg, cache = dec(params, last, cache)
            # host round-trip: sample in NumPy like the old engine loop
            last = jnp.asarray(
                np.argmax(np.asarray(lg[:, : cfg.vocab_size]), -1),
                jnp.int32)
        return last

    chunk = jax.jit(lambda p, l, c, r: decode_steps(
        api.decode, p, l, c, r, jnp.zeros((b,), bool),
        jnp.ones((b,), jnp.int32), jnp.full((b,), steps + 1, jnp.int32),
        n=steps, vocab_size=cfg.vocab_size, max_len=64).tokens)

    rng = jax.random.PRNGKey(0)
    t_loop = timeit(per_token, params, last, cache) / steps
    t_scan = timeit(chunk, params, last, cache, rng) / steps
    rows.append(row("kernel/decode_per_token", t_loop,
                    f"{steps} dispatches + host sampling", impl="auto"))
    rows.append(row("kernel/decode_chunked_scan", t_scan,
                    f"1 dispatch; {t_loop/max(t_scan, 1e-9):.2f}x vs "
                    f"per-token", impl="auto"))


def _block_table_rows(rows, rng):
    """Decode-shape block-table sweep: every picked (bm, bk, bn) is
    validated against the jnp oracle in Pallas interpret mode, and the
    no-pad fast path (pad_m == 0 for m in 8..64 multiples of 8) is
    asserted rather than trusted."""
    k, n = 256, 256
    qcfg = QuantConfig(8, "affine", "per_channel")
    w = quantize(jnp.asarray(rng.standard_normal((k, n)), jnp.float32), qcfg)
    for m in (1, 4, 8, 16, 24, 32, 48, 64, 100, 128):
        bm, bk, bn, pad_m = ops.pick_blocks(m, k, n)
        x = jnp.asarray(rng.standard_normal((m, k)), jnp.float32)
        y_ref = ops.axllm_matmul(x, w, impl="ref")
        y_pal = ops.axllm_matmul(x, w, impl="pallas_interpret")
        np.testing.assert_allclose(np.asarray(y_pal), np.asarray(y_ref),
                                   rtol=2e-5, atol=2e-4)
        if 8 <= m < 128 and m % 8 == 0:
            assert pad_m == 0, f"m={m} should hit the no-pad fast path"
        t = timeit(jax.jit(lambda a: ops.axllm_matmul(a, w, impl="ref")), x)
        rows.append(row(f"kernel/blocks_m{m}", t,
                        f"bm={bm};bk={bk};bn={bn};pad_m={pad_m};"
                        f"interpret=ok", impl="ref"))


def _reuse_rows(rows, rng):
    """Predicted vs achieved computation reuse (paper §III.b) — the first
    place the simulator's model and the kernel's measurement meet.

    *Predicted* is ``core.reuse.reuse_rate`` on the quantized codes at the
    kernel's own column-segment width (the same analytic that feeds the
    Fig. 8 table and ``simulator.simulate_matrix``). *Achieved* is
    ``1 - mults / (K*N)`` where ``mults`` is the multiply count the reuse
    kernel itself tallies while running in interpret mode — distinct
    alphabet cells per (k-row, bn segment). The two are computed by
    disjoint code paths (numpy bincount vs in-kernel one-hot reduction)
    and must agree to |diff| <= 1e-6 (gated in
    benchmarks/kernel_floors.json at 0.01 for runner safety). Also times
    the reuse jnp oracle against the multiply-dequant ref like-for-like
    (same backend/units; impl differs by construction)."""
    from repro.core.reuse import rc_alphabet, reuse_rate

    m, k, n = 8, 1024, 1024
    x = jnp.asarray(rng.standard_normal((m, k)), jnp.float32)
    w = jnp.asarray(rng.standard_normal((k, n)), jnp.float32)
    for bits, mode in ((8, "affine"), (4, "codebook")):
        tag = f"{mode}{bits}"
        qt = quantize(w, QuantConfig(bits, mode, "per_channel"))
        _, fold = rc_alphabet(bits, mode)
        _, _, bn, _ = ops.pick_blocks(m, k, n)
        # pass the QTensor, not qt.codes: int4 codes are packed two-per-
        # byte and the analytics must see decoded signed codes
        predicted = reuse_rate(qt, segment=bn, fold_sign=fold)
        _, mults = ops.reuse_matmul(x, qt, impl="reuse_interpret",
                                    with_stats=True)
        achieved = 1.0 - int(mults) / (k * n)
        rows.append(row(f"kernel/reuse_predicted_{tag}", predicted,
                        f"segment={bn};fold_sign={fold}", impl="sim",
                        units="reuse_rate"))
        rows.append(row(f"kernel/reuse_achieved_{tag}", achieved,
                        f"mults={int(mults)}/{k*n}; "
                        f"|pred-ach|={abs(predicted-achieved):.2e}",
                        impl="reuse_interpret", units="reuse_rate"))

    qt8 = quantize(w, QuantConfig(8, "affine", "per_channel"))
    f_mul = jax.jit(lambda a, q: ops.axllm_matmul(a, q, impl="ref"))
    f_reu = jax.jit(lambda a, q: ops.axllm_matmul(a, q, impl="reuse_ref"))
    t_mul = timeit(f_mul, x, qt8)
    t_reu = timeit(f_reu, x, qt8)
    rows.append(row("kernel/matmul_multiply_ref_int8", t_mul,
                    "dequant+MAC every code", impl="ref"))
    rows.append(row("kernel/matmul_reuse_ref_int8", t_reu,
                    "LUT build + gather (XLA oracle of the reuse kernel)",
                    impl="reuse_ref"))


def run() -> list:
    rows: list = []
    rng = np.random.default_rng(0)
    _matmul_rows(rows, rng)
    _fused_qkv_rows(rows, rng)
    _chunked_decode_rows(rows)
    _block_table_rows(rows, rng)
    _reuse_rows(rows, rng)

    # decode attention: bf16 KV vs int8 KV (bytes halve)
    b, s, h, hk, d = 4, 8192, 8, 2, 128
    q = jnp.asarray(rng.standard_normal((b, h, d)), jnp.float32)
    kc = jnp.asarray(rng.standard_normal((b, s, hk, d)), jnp.float32)
    vc = jnp.asarray(rng.standard_normal((b, s, hk, d)), jnp.float32)
    sc = jnp.maximum(jnp.abs(kc).max(-1, keepdims=True), 1e-8) / 127
    kq = jnp.clip(jnp.round(kc / sc), -127, 127).astype(jnp.int8)
    vq = jnp.clip(jnp.round(vc / sc), -127, 127).astype(jnp.int8)
    length = jnp.full((b,), s, jnp.int32)
    f_fp = jax.jit(lambda *a: ops.decode_attention(*a, impl="ref"))
    f_q = jax.jit(lambda q_, k_, v_, l_, ks_, vs_: ops.decode_attention(
        q_, k_, v_, l_, k_scale=ks_, v_scale=vs_, impl="ref"))
    t1 = timeit(f_fp, q, kc, vc, length)
    t2 = timeit(f_q, q, kq, vq, length, sc, sc)
    rows.append(row("kernel/decode_attn_f32kv", t1,
                    f"kv_bytes={2*b*s*hk*d*4}", impl="ref"))
    rows.append(row("kernel/decode_attn_int8kv", t2,
                    f"kv_bytes={2*b*s*hk*(d+4)} (≈4x less than f32)",
                    impl="ref"))
    return rows
