"""Quantization substrate for AxLLM computation reuse.

The paper (§III.b) builds on q-bit quantized weights: with q bits a weight row
can contain at most 2**q distinct values, and the Result Cache (RC) holds the
product of the current input element with each distinct value. Numerically a
quantized weight is ``value = codebook[code] * scale`` — for symmetric ("affine")
quantization the codebook is the identity ramp, so ``value = code * scale``.

This module provides the :class:`QTensor` pytree used across the framework:
codes are stored in int8 (optionally int4, bit-packed two-per-byte so HBM byte
accounting in the dry-run reflects real traffic), scales are per-tensor,
per-channel, or per-group, and an optional non-uniform codebook (NF4-style
quantile levels) supports the 4-bit beyond-paper variant.

Sign folding (paper §V: "we maintain a 128-element reuse cache … map each value
and its negative to the same cell") is an *analytics/hardware* notion: it halves
the RC size because the lane can negate on read. Numerics here keep signed codes;
:mod:`repro.core.reuse` applies the fold when counting unique values.
"""

from __future__ import annotations

import dataclasses
import functools
from typing import Any, Optional

import jax
import jax.numpy as jnp
import numpy as np

Array = Any


@dataclasses.dataclass(frozen=True)
class QuantConfig:
    """Configuration of the quantized representation.

    Attributes:
      bits: code width. 8 (paper's operating point) or 4 (beyond-paper).
      mode: "affine" (symmetric uniform; codebook == identity ramp) or
        "codebook" (non-uniform levels, NF4-style; the RC/codebook is an
        explicit 2**bits-entry table — the literal TPU analogue of the paper's
        Result Cache).
      granularity: "per_tensor" | "per_channel" | "per_group".
        per_channel scales are along the *output* dim of a [in, out] weight.
      group_size: rows per scale group along the input dim (per_group only).
      pack: bit-pack int4 codes two-per-byte (storage dtype uint8). int8 codes
        are never packed.
    """

    bits: int = 8
    mode: str = "affine"
    granularity: str = "per_channel"
    group_size: int = 128
    pack: bool = True

    def __post_init__(self):
        if self.bits not in (4, 8):
            raise ValueError(f"bits must be 4 or 8, got {self.bits}")
        if self.mode not in ("affine", "codebook"):
            raise ValueError(f"unknown mode {self.mode!r}")
        if self.granularity not in ("per_tensor", "per_channel", "per_group"):
            raise ValueError(f"unknown granularity {self.granularity!r}")

    @property
    def qmax(self) -> int:
        return (1 << (self.bits - 1)) - 1  # 127 for int8, 7 for int4

    @property
    def n_levels(self) -> int:
        return 1 << self.bits

    @property
    def rc_entries(self) -> int:
        """Result-Cache entries after sign folding (paper §V: 128 for 8-bit)."""
        return 1 << (self.bits - 1)


@jax.tree_util.register_pytree_node_class
@dataclasses.dataclass
class QTensor:
    """Quantized tensor pytree: ``deq = codebook[codes] * scale`` (or affine).

    codes:    int8 [*leading, in, out]   (or uint8 packed [*, in, out//2] for int4)
    scale:    f32 broadcastable against the dequantized value:
                per_tensor  -> [*, 1, 1]
                per_channel -> [*, 1, out]
                per_group   -> [*, in//g, 1, out]   (dequant reshapes)
    codebook: f32 [2**bits] normalized levels in [-1, 1], or None for affine.
    layer:    None, or an int32 index into the leading (stacked-layers) axis
              of ``codes``: the tensor then stands for that one layer while
              its codes stay whole, so a kernel can read the layer's tiles in
              place (``scale`` is already that layer's). Consumers that need
              a per-layer operand take :func:`layer_slice` first.
    """

    codes: Array
    scale: Array
    codebook: Optional[Array]
    bits: int
    mode: str
    granularity: str
    group_size: int
    packed: bool
    shape: tuple  # logical (unpacked) shape
    layer: Optional[Array] = None

    # -- pytree protocol ----------------------------------------------------
    def tree_flatten(self):
        children = (self.codes, self.scale, self.codebook, self.layer)
        aux = (self.bits, self.mode, self.granularity, self.group_size,
               self.packed, self.shape)
        return children, aux

    @classmethod
    def tree_unflatten(cls, aux, children):
        codes, scale, codebook, layer = children
        bits, mode, granularity, group_size, packed, shape = aux
        return cls(codes, scale, codebook, bits, mode, granularity,
                   group_size, packed, shape, layer)

    # -- convenience ---------------------------------------------------------
    @property
    def dtype(self):
        return self.scale.dtype

    @property
    def nbytes_codes(self) -> int:
        n = int(np.prod(self.shape))
        return n if self.bits == 8 else (n + 1) // 2

    def __repr__(self):  # pragma: no cover - debugging aid
        return (f"QTensor(shape={self.shape}, bits={self.bits}, mode={self.mode},"
                f" granularity={self.granularity}, packed={self.packed})")


# ---------------------------------------------------------------------------
# Codebooks
# ---------------------------------------------------------------------------

def identity_codebook(bits: int) -> jnp.ndarray:
    """Uniform levels code/qmax for code in [-2^(b-1), 2^(b-1)-1]."""
    qmax = (1 << (bits - 1)) - 1
    lo = -(1 << (bits - 1))
    return jnp.arange(lo, qmax + 1, dtype=jnp.float32) / qmax


def nf4_codebook() -> jnp.ndarray:
    """NF4-style non-uniform 16-level codebook (normal-quantile spaced).

    Levels are the quantiles of N(0,1) normalized to [-1, 1]; this matches the
    distribution of trained-LLM weights much better than a uniform ramp and is
    the beyond-paper 4-bit operating point (the RC shrinks to 16 entries).
    """
    from scipy import stats  # available offline in this container

    neg = stats.norm.ppf((np.arange(8) + 0.5) / 16.0)      # 8 negative levels
    pos = -neg[::-1][:7]                                    # 7 positive levels
    levels = np.concatenate([neg, [0.0], pos])              # 16 total, has 0
    levels = levels / np.max(np.abs(levels))
    assert levels.shape == (16,) and np.all(np.isfinite(levels))
    return jnp.asarray(np.sort(levels), dtype=jnp.float32)


def make_codebook(cfg: QuantConfig) -> Optional[jnp.ndarray]:
    if cfg.mode == "affine":
        return None
    return nf4_codebook() if cfg.bits == 4 else identity_codebook(8)


def resolve_codebook(qt: "QTensor") -> Optional[jnp.ndarray]:
    """The codebook is a pure function of (mode, bits) — it is NOT stored as
    a pytree leaf (a shared [2^q] leaf breaks lax.scan over stacked layers)
    but materialized as a constant at use sites."""
    if qt.mode == "affine":
        return None
    return nf4_codebook() if qt.bits == 4 else identity_codebook(8)


# ---------------------------------------------------------------------------
# int4 bit packing (two codes per byte; low nibble = even index)
# ---------------------------------------------------------------------------

def pack_int4(codes: Array) -> Array:
    """[..., out] int8 in [-8, 7] -> [..., out//2] uint8."""
    if codes.shape[-1] % 2:
        raise ValueError("int4 packing requires an even trailing dim")
    u = (codes.astype(jnp.int32) & 0xF).astype(jnp.uint8)
    lo, hi = u[..., 0::2], u[..., 1::2]
    return lo | (hi << 4)


def unpack_int4(packed: Array, out_dim: int) -> Array:
    """[..., out//2] uint8 -> [..., out] int8 in [-8, 7]."""
    lo = (packed & 0xF).astype(jnp.int8)
    hi = ((packed >> 4) & 0xF).astype(jnp.int8)
    lo = jnp.where(lo >= 8, lo - 16, lo)
    hi = jnp.where(hi >= 8, hi - 16, hi)
    out = jnp.stack([lo, hi], axis=-1).reshape(*packed.shape[:-1], -1)
    return out[..., :out_dim]


# ---------------------------------------------------------------------------
# Quantize / dequantize
# ---------------------------------------------------------------------------

def _scale_reduce_axes(w_shape, cfg: QuantConfig):
    # weight layout [..., in, out]; leading dims (stacked layers / experts)
    # always keep their own scales so scan/vmap slicing stays consistent
    nd = len(w_shape)
    if cfg.granularity == "per_tensor":
        return (nd - 2, nd - 1)
    if cfg.granularity == "per_channel":
        return (nd - 2,)  # reduce the in dim only
    return None  # per_group handled separately


def quantize(w: Array, cfg: QuantConfig) -> QTensor:
    """Quantize a weight of shape [..., in, out] per ``cfg``.

    Exactness contract (paper §II "preserves exact arithmetic semantics"):
    dequantize(quantize(w)) is the model's quantized weights; the AxLLM reuse
    mechanism never changes them further. Round-trip error is bounded by
    scale/2 per element for affine mode (property-tested).
    """
    w = jnp.asarray(w, jnp.float32)
    if w.ndim < 2:
        raise ValueError("quantize expects [..., in, out]")
    eps = 1e-8

    if cfg.granularity == "per_group":
        *lead, n_in, n_out = w.shape
        g = cfg.group_size
        if n_in % g:
            raise ValueError(f"in dim {n_in} not divisible by group {g}")
        wg = w.reshape(*lead, n_in // g, g, n_out)
        absmax = jnp.max(jnp.abs(wg), axis=-2, keepdims=True)  # [*,G,1,out]
        scale = jnp.maximum(absmax, eps)
        normed = wg / scale
        scale_store = scale
    else:
        axes = _scale_reduce_axes(w.shape, cfg)
        absmax = jnp.max(jnp.abs(w), axis=axes, keepdims=True)
        scale = jnp.maximum(absmax, eps)
        normed = w / scale
        scale_store = scale

    cb = make_codebook(cfg)  # used for encoding only; not stored as a leaf
    if cfg.mode == "affine":
        codes = jnp.clip(jnp.round(normed * cfg.qmax), -cfg.qmax, cfg.qmax)
        codes = codes.astype(jnp.int8)
    else:
        if cfg.bits == 8:
            # identity codebook: same as affine but stored with explicit table
            codes = jnp.clip(jnp.round(normed * cfg.qmax), -cfg.qmax, cfg.qmax)
            codes = codes.astype(jnp.int8)
        else:
            # nearest level in the 16-entry codebook
            d = jnp.abs(normed[..., None] - cb)          # [..., 16]
            idx = jnp.argmin(d, axis=-1).astype(jnp.int32)
            codes = (idx - 8).astype(jnp.int8)           # recenter to [-8, 7]

    if cfg.granularity == "per_group":
        codes = codes.reshape(*w.shape)

    packed = False
    if cfg.bits == 4 and cfg.pack:
        codes = pack_int4(codes)
        packed = True

    return QTensor(codes=codes, scale=scale_store, codebook=None,
                   bits=cfg.bits, mode=cfg.mode, granularity=cfg.granularity,
                   group_size=cfg.group_size, packed=packed, shape=w.shape)


def layer_slice(qt: QTensor) -> QTensor:
    """The QTensor with ``codes[layer]`` materialized when ``layer`` is set
    (what scanning over the stacked codes would have handed out); the
    tensor itself otherwise."""
    if qt.layer is None:
        return qt
    return dataclasses.replace(qt, codes=qt.codes[qt.layer], layer=None)


def decode_codes(qt: QTensor) -> Array:
    """Return unpacked signed integer codes with qt.shape."""
    if qt.packed:
        return unpack_int4(qt.codes, qt.shape[-1])
    return qt.codes


def lookup(qt: QTensor, codes: Array) -> Array:
    """codebook[codes] in normalized space — the RC-table read, vectorized.

    For affine mode this is ``codes / qmax`` (no gather: the identity codebook
    folds into arithmetic, which is exactly how the TPU kernel implements it).
    """
    if qt.mode == "affine":
        qmax = (1 << (qt.bits - 1)) - 1
        return codes.astype(jnp.float32) / qmax
    cb = resolve_codebook(qt)
    offset = 1 << (qt.bits - 1)
    return jnp.take(cb, codes.astype(jnp.int32) + offset, axis=0)


def dequantize(qt: QTensor, dtype=jnp.float32) -> Array:
    qt = layer_slice(qt)
    codes = decode_codes(qt)
    normed = lookup(qt, codes)
    if qt.granularity == "per_group":
        *lead, n_in, n_out = qt.shape
        g = qt.group_size
        normed = normed.reshape(*lead, n_in // g, g, n_out)
        w = (normed * qt.scale).reshape(*qt.shape)
    else:
        w = normed * qt.scale
    return w.astype(dtype)


# ---------------------------------------------------------------------------
# Concatenation (fused-projection support)
# ---------------------------------------------------------------------------

def qconcat(qts) -> QTensor:
    """Concatenate QTensors along the output (N) axis without requantizing.

    This is the substrate of the fused-QKV / fused-gate-up projections: a
    single ``[K, N1+N2+...]`` AxLLM matmul replaces several ``[K, Ni]``
    matmuls over the same activations (one activation pass, one codebook
    residency in the kernel). Exactness: per-channel scales travel with
    their columns, so ``dequantize(qconcat(a, b)) ==
    concat(dequantize(a), dequantize(b))`` bit-for-bit.

    Inputs must share K (and any leading stacked dims), bits, mode, packing
    and — for per_group — group_size. Mixing per_tensor/per_channel inputs
    is allowed: per_tensor scales broadcast over their columns and the
    result is per_channel. per_group inputs must all be per_group.
    """
    qts = list(qts)
    if len(qts) < 2:
        raise ValueError("qconcat needs at least two QTensors")
    q0 = qts[0]
    for qt in qts[1:]:
        if not isinstance(qt, QTensor):
            raise TypeError(f"qconcat expects QTensors, got {type(qt)}")
        if (qt.bits, qt.mode, qt.packed) != (q0.bits, q0.mode, q0.packed):
            raise ValueError(
                f"qconcat mismatch: ({qt.bits},{qt.mode},{qt.packed}) vs "
                f"({q0.bits},{q0.mode},{q0.packed})")
        if qt.shape[:-1] != q0.shape[:-1]:
            raise ValueError(f"qconcat K/leading mismatch: {qt.shape} vs "
                             f"{q0.shape}")
    grans = {qt.granularity for qt in qts}
    if "per_group" in grans:
        if grans != {"per_group"}:
            raise ValueError("qconcat cannot mix per_group with other "
                             "granularities")
        if len({qt.group_size for qt in qts}) != 1:
            raise ValueError("qconcat per_group inputs need one group_size")
        granularity = "per_group"
        scale = jnp.concatenate([qt.scale for qt in qts], axis=-1)
    else:
        # per_tensor folds into per_channel: broadcast each input's scale
        # over its own columns, then concatenate along the channel dim
        granularity = "per_channel"
        lead = q0.shape[:-2]
        scale = jnp.concatenate(
            [jnp.broadcast_to(qt.scale.astype(jnp.float32),
                              (*lead, 1, qt.shape[-1])) for qt in qts],
            axis=-1)
    if q0.packed and any(qt.shape[-1] % 2 for qt in qts):
        raise ValueError("packed qconcat inputs need even output dims")
    codes = jnp.concatenate([qt.codes for qt in qts], axis=-1)
    out = sum(qt.shape[-1] for qt in qts)
    return QTensor(codes=codes, scale=scale, codebook=None, bits=q0.bits,
                   mode=q0.mode, granularity=granularity,
                   group_size=q0.group_size, packed=q0.packed,
                   shape=(*q0.shape[:-1], out))


# ---------------------------------------------------------------------------
# Pytree-level helpers (deploy-time conversion of a trained model)
# ---------------------------------------------------------------------------

_EXCLUDE_PREFIXES = (
    # norms and their leaves
    "ln", "norm", "scale", "bias",
    # non-matmul / non-reuse surfaces: gathers, routing, convs, recurrences
    "embedding", "router", "lora_", "conv", "a_log", "dt_bias",
    "d_skip", "gate_bias", "if_bias", "pos_embed",
)
_EXCLUDE_EXACT = ("r",)  # sLSTM per-head recurrent stack


def _is_weight_matrix(path: str, x: Any) -> bool:
    """True for weight matrices that are AxLLM reuse surfaces: 2-D (or
    stacked 3-D) matrices consumed by vector-matrix products. Norm scales,
    biases, embeddings (gather), routers, depthwise convs and per-head
    recurrent matrices stay full precision."""
    if not hasattr(x, "ndim") or x.ndim < 2:
        return False
    comps = [c for c in path.split("/") if c]
    for c in comps:
        if c in _EXCLUDE_EXACT:
            return False
        # substring match: catches suffixed names like "wq_bias" too
        if any(p in c for p in _EXCLUDE_PREFIXES):
            return False
    return True


def quantize_tree(params, cfg: QuantConfig, predicate=_is_weight_matrix):
    """Quantize every weight matrix in a param pytree (paper: post-training,
    zero offline setup beyond this conversion; no retraining)."""

    def walk(prefix, node):
        if isinstance(node, dict):
            return {k: walk(f"{prefix}/{k}", v) for k, v in node.items()}
        if predicate(prefix, node):
            return quantize(node, cfg)
        return node

    return walk("", params)


def derive_draft_params(params, *, bits: int = 4, mode: str = "affine",
                        predicate=_is_weight_matrix):
    """Derive a low-precision *draft* model from raw (pre-quantization)
    params for self-speculative decoding.

    The repo's quantization ladder means the draft is the SAME model at a
    cheaper precision — no separate training, no second tokenizer, same
    cache layout — which is all speculative decoding needs from a
    proposer (correctness never depends on it; the target re-verifies
    every token). Modes:

    - ``"affine"`` / ``"codebook"``: :func:`quantize_tree` at ``bits``
      (int4 is the intended draft point; int8 is a sharper, pricier
      draft for bf16 targets).
    - ``"shiftadd"``: the ShiftAddLLM reparameterization (binary planes
      x power-of-two scales, ``repro.core.shiftadd``) reconstructed to
      dense float32 — an *approximate* draft exercising a genuinely
      different numeric path than the affine ladder. ``bits`` is the
      number of binary planes.

    Must be fed the ORIGINAL float params: deriving a draft from
    already-quantized weights would compound two quantization errors.
    """
    if mode in ("affine", "codebook"):
        return quantize_tree(
            params, QuantConfig(bits=bits, mode=mode), predicate=predicate)
    if mode != "shiftadd":
        raise ValueError(f"unknown draft mode {mode!r} "
                         "(expected affine | codebook | shiftadd)")
    # function-local import: shiftadd pulls in the cycle simulator, which
    # this module must not depend on at import time
    from repro.core.shiftadd import binarize, reconstruct

    def reparam(x):
        w = np.asarray(x, np.float64)
        flat = w.reshape((-1,) + w.shape[-2:])   # binarize() is 2-D only
        out = np.empty_like(flat)
        for i in range(flat.shape[0]):
            out[i] = reconstruct(*binarize(flat[i], q=bits))
        return jnp.asarray(out.reshape(w.shape), jnp.float32)

    def walk(prefix, node):
        if isinstance(node, dict):
            return {k: walk(f"{prefix}/{k}", v) for k, v in node.items()}
        if predicate(prefix, node):
            return reparam(node)
        return node

    return walk("", params)


def tree_reuse_surface(params) -> int:
    """Total quantized weight elements (the surface AxLLM's RC acts on)."""
    n = 0
    for leaf in jax.tree_util.tree_leaves(
            params, is_leaf=lambda x: isinstance(x, QTensor)):
        if isinstance(leaf, QTensor):
            n += int(np.prod(leaf.shape))
    return n
