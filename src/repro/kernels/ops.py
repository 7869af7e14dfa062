"""Public kernel entry points: Pallas-on-TPU, jnp-oracle elsewhere.

Every op takes `impl` in {"auto", "pallas", "ref", "pallas_interpret"}:
  auto             -> pallas on TPU backends, ref otherwise (CPU dry-run path)
  pallas_interpret -> pallas kernel body executed in Python (tests on CPU)

The quantized matmul additionally accepts the reuse (LUT) impls
{"reuse", "reuse_interpret", "reuse_ref"}, which route through the
codebook-LUT kernel of :mod:`repro.kernels.reuse_matmul` (gather instead of
multiply for repeated codes — the paper's Result Cache on device):
  reuse            -> reuse kernel on TPU, reuse jnp oracle otherwise
  reuse_interpret  -> reuse kernel body executed in Python (tests on CPU)
  reuse_ref        -> reuse jnp oracle (same product association, jit-safe)
Non-matmul ops treat "reuse" as "auto" and the other two as "ref" — the
reuse mode changes how quantized weights are multiplied, not how attention
or KV quantization dispatch.

The wrapper layer owns all shape plumbing the kernels require: scale-semantics
normalization (affine kernels consume scale/qmax), padding M to block
multiples, and flattening leading batch dims.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from repro.core.quantization import QTensor, layer_slice
from repro.dist import sharding as shd
from repro.kernels import ref as _ref
from repro.kernels import axllm_matmul as _amm
from repro.kernels import reuse_matmul as _rmm


def set_analysis_mode(on: bool) -> None:
    """Roofline aux lowering: unroll inner attention chunk loops so HLO cost
    analysis counts them fully (see ref.ANALYSIS_UNROLL)."""
    _ref.ANALYSIS_UNROLL = on


def _on_tpu() -> bool:
    return jax.default_backend() == "tpu"


REUSE_IMPLS = ("reuse", "reuse_interpret", "reuse_ref")


def _base_impl(impl: str) -> str:
    """What non-matmul ops see: reuse modes only redirect the quantized
    matmul, so "reuse" degrades to "auto" and the interpret/ref variants to
    the oracle path (interpreting every attention kernel alongside a
    reuse-matmul test would add wall time without covering anything new)."""
    if impl == "reuse":
        return "auto"
    if impl in ("reuse_interpret", "reuse_ref"):
        return "ref"
    return impl


def _use_pallas(impl: str) -> bool:
    if impl == "auto":
        return _on_tpu()
    return impl.startswith("pallas")


def _interpret(impl: str) -> bool:
    return impl == "pallas_interpret"


# ---------------------------------------------------------------------------
# Kernels under a mesh
# ---------------------------------------------------------------------------
# GSPMD cannot partition a Mosaic custom call, so while a sharding context is
# active (tensor-parallel serving, dist.sharding.activate) every Pallas call
# runs once per shard inside a shard_map on its local blocks. The in_specs
# come from the same logical names and rules that placed the operands, so
# XLA inserts no resharding on the serving layout; any other layout is
# resharded to the specs, never computed wrong.

def _per_shard(fn, args, in_specs, out_specs):
    mesh, _ = shd._current()
    return jax.shard_map(fn, mesh=mesh, in_specs=tuple(in_specs),
                         out_specs=out_specs, check_vma=False)(*args)


def _spec(shape, names):
    mesh, rules = shd._current()
    return shd.resolve_spec(shape, names, mesh, rules)


def _attention_call(fn, q, kv, extra, q_names, kv_names, extra_names):
    """fn(q, *kv, *extra) -> q-shaped output, per shard under a mesh.

    ``kv`` operands (keys, values, their scales) share ``kv_names``;
    ``extra`` operands (lengths, block tables) take ``extra_names``. Query
    heads and KV heads shard together or not at all: the kernel maps query
    head h to KV head h // rep on its local blocks."""
    if shd._current() is None:
        return fn(q, *kv, *extra)
    qs = _spec(q.shape, q_names)
    ks = _spec(kv[0].shape, kv_names)
    hq, hk = q_names.index("heads"), kv_names.index("kv_heads")
    if qs[hq] != ks[hk]:
        qs = P(*(None if i == hq else e for i, e in enumerate(qs)))
        ks = P(*(None if i == hk else e for i, e in enumerate(ks)))
    specs = [qs] + [ks] * len(kv) + [_spec(a.shape, n)
                                     for a, n in zip(extra, extra_names)]
    return _per_shard(fn, (q, *kv, *extra), specs, qs)


def _matmul_call(fn, x2, qt: QTensor, row_parallel: bool):
    """fn(x2, qt) -> [m, N] f32, per shard under a mesh.

    Column-parallel weights (codes [K, N] sharded on N) keep x whole and
    return N-sharded output; row-parallel ones (``row_parallel``: wo/down,
    see dist.sharding._param_names) take K-sharded x and all-reduce the
    partial products. Layouts that do not divide run replicated. A stack
    of layers' codes (``qt.layer`` set) keeps its leading axis whole on
    every shard, and the layer index goes to each shard's kernel."""
    if shd._current() is None:
        return fn(x2, qt)
    codes, scale = qt.codes, qt.scale
    stack = (None,) * (codes.ndim - 2)
    if row_parallel:
        cs = _spec(codes.shape, stack + ("mlp", None))
        ax = cs[-2]
        ss = P()
        if qt.granularity == "per_group":
            ss = _spec(scale.shape, ("mlp",) + (None,) * (scale.ndim - 1))
            if ss[0] != ax:
                cs, ax, ss = P(), None, P()
        xs, out_spec = P(None, ax), P()
    else:
        cs = _spec(codes.shape, stack + (None, "mlp"))
        ax = cs[-1]
        ss = P(*((None,) * (scale.ndim - 1)
                 + ((ax,) if scale.shape[-1] > 1 else (None,))))
        xs, out_spec = P(), P(None, ax)

    layer = () if qt.layer is None else (jnp.asarray(qt.layer, jnp.int32),)

    def local(x_l, codes_l, scale_l, *layer_l):
        n_l = qt.shape[-1] * codes_l.shape[-1] // codes.shape[-1]
        qt_l = QTensor(codes=codes_l, scale=scale_l, codebook=qt.codebook,
                       bits=qt.bits, mode=qt.mode,
                       granularity=qt.granularity,
                       group_size=qt.group_size, packed=qt.packed,
                       shape=(codes_l.shape[-2], n_l),
                       layer=layer_l[0] if layer_l else None)
        y = fn(x_l, qt_l)
        return y if (not row_parallel or ax is None) \
            else jax.lax.psum(y, ax)

    return _per_shard(local, (x2, codes, scale, *layer),
                      (xs, cs, ss) + (P(),) * len(layer), out_spec)


# ---------------------------------------------------------------------------
# AxLLM quantized matmul
# ---------------------------------------------------------------------------

def _kernel_scale(qt: QTensor) -> jax.Array:
    """Scale in the form the kernel consumes: [1, N] or [K/g, N] f32,
    folding the /qmax of affine dequantization."""
    n = qt.shape[-1]
    if qt.granularity == "per_group":
        s = qt.scale.reshape(-1, n)
    else:
        s = qt.scale.reshape(1, n) if qt.scale.size == n else jnp.broadcast_to(
            qt.scale.reshape(1, 1), (1, n))
    if qt.mode == "affine":
        qmax = (1 << (qt.bits - 1)) - 1
        s = s / qmax
    return s.astype(jnp.float32)


def _divisor_block(dim: int, target: int) -> int:
    """Largest power-of-two block <= target that divides dim (fallback:
    the dim itself, i.e. a single block)."""
    for b in (512, 256, 128, 64, 32, 16, 8):
        if b <= target and b <= dim and dim % b == 0:
            return b
    return dim


def _lane_block(dim: int, target: int, ragged: bool) -> int:
    """Lane-dim block: the largest of 512/256 (<= target) that divides dim;
    else, with ``ragged``, the whole dim when it fits in one block and the
    target otherwise, leaving a ragged last block for the weight kernel to
    mask; else 128 when it divides, else the whole dim. Mosaic takes a
    lane-dim block that is a multiple of 128 or spans the array
    (tensor-parallel shards such as 768 / 4 = 192 take the whole dim)."""
    b = next((b for b in (512, 256) if b <= target and dim % b == 0), None)
    if b is not None:
        return b
    if ragged:
        return dim if dim <= target else target
    return 128 if dim % 128 == 0 else dim


def pick_blocks(m: int, k: int, n: int, group_size: int = 128,
                per_group: bool = False, ragged: bool = True):
    """Block-size table for the fused dequant-matmul and the reuse (LUT)
    matmul: (bm, bk, bn, pad_m).

    The pad decision is part of the table: decode shapes (m < 128) pick the
    largest SKINNY_BM entry that divides m exactly, so m ∈ {8,16,...,64}
    (n_slots · decode tokens) hits a no-pad fast path instead of being
    silently re-padded on every call. Skinny launches widen bn to 512 (vs
    the 256 default) to keep the MXU fed from the N grid dimension — the
    per-tile VMEM footprint stays far under budget because the x tile
    shrinks with bm. Prefill shapes take the largest of 512/256/128 rows
    that divides m, so a chunk of up to 512 tokens fetches each code tile
    from HBM once rather than once per 128 rows (about 3 MB of VMEM
    buffers at 512 rows). bk and bn are lane blocks (:func:`_lane_block`):
    with ``ragged`` (the weight kernel on unpacked codes) a dim with no
    factor of 256, such as GLM-4's d_ff 13696 = 107·128, still takes the
    full-width tile and a masked last block; without it (the reuse kernel,
    packed codes) every block divides its dim. Per-group scales keep bk a
    divisor of k.

    >>> pick_blocks(16, 128, 256)       # skinny decode shape: no pad
    (16, 128, 256, 0)
    >>> pick_blocks(9, 128, 256)        # odd m falls back to bm=8 + pad
    (8, 128, 256, 7)
    >>> pick_blocks(4, 768, 2048)       # repro-100m decode, 4 slots
    (8, 256, 512, 4)
    >>> pick_blocks(512, 4096, 12800)   # a 512-token prefill chunk
    (512, 512, 256, 0)
    >>> pick_blocks(200, 768, 768)      # ragged prefill pads to 128 rows
    (128, 256, 256, 56)
    >>> pick_blocks(8, 4096, 13696)     # GLM-4 gate/up in decode
    (8, 512, 512, 0)
    >>> pick_blocks(8, 13696, 4096, ragged=False)
    (8, 128, 512, 0)
    """
    if m >= 128:
        bm = next((b for b in (512, 256, 128) if m % b == 0), 128)
    else:
        bm = next((b for b in _amm.SKINNY_BM if m % b == 0), 8)
    bk = _lane_block(k, 512, ragged and not per_group)
    bn = _lane_block(n, 512 if bm <= 32 else 256, ragged)
    if per_group:
        g_bk = (bk // group_size) * group_size
        if g_bk <= 0 or k % g_bk:
            g_bk = group_size
        bk = g_bk
    return bm, bk, bn, (-m) % bm


def axllm_matmul(x: jax.Array, qt: QTensor, *, impl: str = "auto",
                 out_dtype=None, row_parallel: bool = False) -> jax.Array:
    """y = x @ deq(qt). x: [..., K]; qt: [K, N]. Returns [..., N].

    ``impl`` in ``REUSE_IMPLS`` routes through the reuse (LUT) kernel —
    same result, gather-instead-of-multiply arithmetic (see
    :func:`reuse_matmul` for the stats-bearing entry point).
    ``row_parallel`` says the weight is placed contraction-sharded under a
    mesh (see :func:`_matmul_call`); it changes no result.

    A ``qt`` with ``layer`` set holds a scanned model's whole [L, K, N]
    codes: the Pallas kernel reads that layer's tiles in place, per shard
    too; the oracle and the reuse kernel take ``codes[layer]`` first.
    """
    out_dtype = out_dtype or x.dtype
    if not _use_pallas(impl):
        qt = layer_slice(qt)
    if impl in REUSE_IMPLS:
        y, _ = reuse_matmul(x, qt, impl=impl, out_dtype=out_dtype,
                            row_parallel=row_parallel)
        return y
    if not _use_pallas(impl):
        lead = x.shape[:-1]
        y = _ref.axllm_matmul_ref(x.reshape(-1, x.shape[-1]), qt, out_dtype)
        return y.reshape(*lead, -1)

    from repro.core.quantization import resolve_codebook

    def kernel(x2, qt):
        kdim, n = qt.shape[-2], qt.shape[-1]
        m = x2.shape[0]
        bm, bk, bn, pad_m = pick_blocks(m, kdim, n, qt.group_size,
                                        qt.granularity == "per_group",
                                        ragged=not qt.packed)
        if pad_m:
            x2 = jnp.pad(x2, ((0, pad_m), (0, 0)))
        y = _amm.axllm_matmul_pallas(
            x2, qt.codes, _kernel_scale(qt), resolve_codebook(qt), qt.layer,
            bits=qt.bits, packed=qt.packed, group_size=qt.group_size,
            blocks=(bm, bk, bn), interpret=_interpret(impl))
        return y[:m]

    lead = x.shape[:-1]
    y = _matmul_call(kernel, x.reshape(-1, qt.shape[-2]), qt, row_parallel)
    return y.reshape(*lead, qt.shape[-1]).astype(out_dtype)


def reuse_matmul(x: jax.Array, qt: QTensor, *, impl: str = "auto",
                 out_dtype=None, with_stats: bool = False,
                 row_parallel: bool = False):
    """Reuse (LUT) matmul: ``(y, mults)`` = x @ deq(qt) by gathering cached
    alphabet products instead of multiplying every code (paper §III.b).

    x: [..., K]; qt: [K, N]. ``y`` is [..., N]. ``mults`` is the
    *per-activation-row* multiply count — the distinct alphabet cells per
    (k-row, bn-wide column segment), summed — i.e. what a Result Cache
    executes for ONE input row; the baseline pays K*N. It is
    activation-independent, so the achieved multiply-reduction is
    ``1 - mults / (K * N)`` regardless of the batch. ``mults`` is a traced
    int32 scalar on the kernel paths and a host int on the ref path;
    ``with_stats=False`` (the serving default) returns ``mults=None`` —
    the ref-path count needs concrete codes and must stay out of jit.
    Counting is not available under a mesh.

    impl: "auto"/"reuse" -> kernel on TPU, jnp oracle otherwise;
    "reuse_interpret"/"pallas_interpret" -> kernel body in Python;
    "reuse_ref"/"ref" -> jnp oracle; "pallas" -> kernel.
    """
    from repro.core.reuse import rc_alphabet
    out_dtype = out_dtype or x.dtype
    kdim, n = qt.shape[-2], qt.shape[-1]
    lead = x.shape[:-1]
    x2 = x.reshape(-1, kdim)

    use_kernel = impl in ("pallas", "pallas_interpret", "reuse_interpret") \
        or (impl in ("auto", "reuse") and _on_tpu())
    if not use_kernel:
        y = _ref.reuse_matmul_ref(x2, qt, jnp.float32)
        mults = None
        if with_stats:
            bn = pick_blocks(x2.shape[0], kdim, n, qt.group_size,
                             qt.granularity == "per_group", ragged=False)[2]
            mults = _ref.reuse_mult_count(qt, bn)
        return y.reshape(*lead, n).astype(out_dtype), mults

    interpret = impl in ("pallas_interpret", "reuse_interpret")
    counts = []

    def kernel(x2, qt):
        levels, fold = rc_alphabet(qt.bits, qt.mode)
        m = x2.shape[0]
        bm, bk, bn, pad_m = pick_blocks(m, qt.shape[-2], qt.shape[-1],
                                        qt.group_size,
                                        qt.granularity == "per_group",
                                        ragged=False)
        if pad_m:
            x2 = jnp.pad(x2, ((0, pad_m), (0, 0)))
        y, c = _rmm.reuse_matmul_pallas(
            x2, qt.codes, _kernel_scale(qt), jnp.asarray(levels),
            packed=qt.packed, fold_sign=fold, group_size=qt.group_size,
            blocks=(bm, bk, bn), count=with_stats, interpret=interpret)
        counts.append(c)
        return y[:m]

    y = _matmul_call(kernel, x2, qt, row_parallel)
    mults = counts[0][0, 0] if with_stats else None
    return y.reshape(*lead, n).astype(out_dtype), mults


def lora_matmul(x: jax.Array, qt: QTensor, a: jax.Array, b: jax.Array,
                scaling: float, *, impl: str = "auto",
                out_dtype=None) -> jax.Array:
    """y = x @ deq(qt) + scaling * (x @ A) @ B (paper Fig. 5 combined path)."""
    out_dtype = out_dtype or x.dtype
    base = axllm_matmul(x, qt, impl=impl, out_dtype=jnp.float32)
    xa = jnp.dot(x.astype(jnp.float32), a.astype(jnp.float32))
    delta = jnp.dot(xa, b.astype(jnp.float32))
    return (base + scaling * delta).astype(out_dtype)


# ---------------------------------------------------------------------------
# Attention
# ---------------------------------------------------------------------------

def flash_attention(q, k, v, *, causal: bool = True,
                    impl: str = "auto") -> jax.Array:
    """q: [B, Sq, H, d]; k, v: [B, Sk, Hk, d] -> [B, Sq, H, d]."""
    impl = _base_impl(impl)
    if _use_pallas(impl):
        from repro.kernels import flash_attention as _fa
        fn = functools.partial(_fa.flash_attention_pallas, causal=causal,
                               interpret=_interpret(impl))
        return _attention_call(fn, q, (k, v), (),
                               ("batch", None, "heads", None),
                               ("batch", None, "kv_heads", None), ())
    # memory-safe oracle (chunked online softmax) once the full [B,H,Sq,Sk]
    # score tensor stops being trivially small
    if q.shape[1] * k.shape[1] > 1024 * 1024:
        return _ref.chunked_attention_ref(q, k, v, causal=causal)
    return _ref.attention_ref(q, k, v, causal=causal)


def decode_attention(q, k_cache, v_cache, length, *, k_scale=None,
                     v_scale=None, block_tables=None, layer=None,
                     impl: str = "auto") -> jax.Array:
    """q: [B, H, d]; caches [B, S, Hk, d] (int8 if scales given); length [B].

    With ``block_tables`` ([B, MB] int32) the caches are a shared *paged
    pool* [NB, bs, Hk, d] instead: each row's logical sequence is the
    concatenation of its table's blocks, and the paged flash-decode kernel
    gathers KV tiles through the table (scalar-prefetch index map) so
    prefix-shared blocks stream from HBM once per referencing row without
    ever being materialized contiguously. With ``layer`` as well (an int32
    scalar) the pools are the stack [L, NB, bs, Hk, d] of every layer's
    pool, and the kernel reads layer ``layer``'s blocks in place; under a
    mesh the leading L axis stays whole on every shard.
    """
    impl = _base_impl(impl)
    if not _use_pallas(impl):
        if block_tables is not None:
            if layer is not None:
                k_cache, v_cache = k_cache[layer], v_cache[layer]
                if k_scale is not None:
                    k_scale, v_scale = k_scale[layer], v_scale[layer]
            return _ref.paged_decode_attention_ref(
                q, k_cache, v_cache, block_tables, length,
                k_scale=k_scale, v_scale=v_scale)
        return _ref.decode_attention_ref(q, k_cache, v_cache, length,
                                         k_scale=k_scale, v_scale=v_scale)
    kv = (k_cache, v_cache) if k_scale is None \
        else (k_cache, v_cache, k_scale, v_scale)
    q_names = ("batch", "heads", None)
    interpret = _interpret(impl)
    if block_tables is not None:
        from repro.kernels import paged_decode_attention as _pda
        extra, extra_names = (block_tables, length), (("batch", None),
                                                      ("batch",))
        if layer is not None:
            extra += (jnp.asarray(layer, jnp.int32),)
            extra_names += ((),)

        n_sc = len(kv) - 2

        def paged(q, k, v, *rest):
            sc, (bt, ln, *ly) = rest[:n_sc], rest[n_sc:]
            return _pda.paged_decode_attention_pallas(
                q, k, v, bt, ln, *ly, k_scale=sc[0] if sc else None,
                v_scale=sc[1] if sc else None, interpret=interpret)

        stack = (None,) * (k_cache.ndim - 2)
        return _attention_call(paged, q, kv, extra, q_names,
                               stack + ("kv_heads", None), extra_names)
    from repro.kernels import decode_attention as _da

    def dense(q, k, v, *rest):
        *sc, ln = rest
        return _da.decode_attention_pallas(
            q, k, v, ln, k_scale=sc[0] if sc else None,
            v_scale=sc[1] if sc else None, interpret=interpret)

    return _attention_call(dense, q, kv, (length,), q_names,
                           ("batch", None, "kv_heads", None), (("batch",),))


def prefix_attention(q, k_prefix, v_prefix, prefix_len, k_suffix, v_suffix,
                     *, impl: str = "auto") -> jax.Array:
    """Suffix-prefill attention against a cached (right-padded) prefix.

    q/k_suffix/v_suffix: [B, S, H|Hk, d]; k/v_prefix: [B, P, Hk, d] with
    per-row valid lengths ``prefix_len`` [B]. There is no Pallas
    suffix-prefill kernel yet — prefill waves are small and XLA fuses the
    jnp oracle fine; the decode hot path is where the paged Pallas kernel
    earns its keep. Dispatch is honest about that: ``auto``/``ref`` run
    the oracle, ``pallas_interpret`` runs it too (the oracle IS the kernel
    body being interpreted — there is no second implementation to check
    against), and an explicit ``impl="pallas"`` raises instead of
    silently substituting the jnp path for a compiled kernel.
    """
    impl = _base_impl(impl)
    if impl == "pallas":
        raise NotImplementedError(
            "prefix_attention has no compiled Pallas kernel yet: "
            "impl='pallas' would silently run the jnp oracle, which is "
            "not what you asked for. Use impl='auto' (oracle on every "
            "backend) or 'pallas_interpret'.")
    return _ref.prefix_attention_ref(q, k_prefix, v_prefix, prefix_len,
                                     k_suffix, v_suffix)


def quantize_channels(w, *, bits: int = 8, impl: str = "auto"):
    """Per-channel absmax quantization (codes, scale) — used for KV-cache
    quantization at serve time."""
    impl = _base_impl(impl)
    if _use_pallas(impl):
        from repro.kernels import quantize as _q
        return _q.quantize_pallas(w, bits=bits, interpret=_interpret(impl))
    return _ref.quantize_ref(w, bits=bits)
