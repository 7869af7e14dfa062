"""Model-facing AxLLM modules: quantized linear + LoRA (paper §III).

These are the integration points every architecture in `repro.models` uses:
a linear layer whose weight may be a plain bf16 array (training / baseline)
or a :class:`QTensor` (AxLLM serving path — codes + codebook, dispatched to
the Pallas fused dequant-matmul on TPU). Swapping a trained model to the
AxLLM path is `quantize_tree(params, qcfg)` — post-training, zero setup,
exactly the paper's deployment story.
"""

from __future__ import annotations

import dataclasses
from typing import Any, Optional

import jax
import jax.numpy as jnp

from repro.core.quantization import QTensor, QuantConfig, quantize
from repro.kernels import ops

Array = Any


def linear(x: Array, w, *, impl: str = "auto", out_dtype=None,
           row_parallel: bool = False) -> Array:
    """x @ w where w is an Array (dense path) or QTensor (AxLLM path).

    ``row_parallel`` marks the block-output projections (wo, down) that
    tensor-parallel serving shards along their contraction dim; it only
    steers how a kernel runs per shard under a mesh."""
    if isinstance(w, QTensor):
        return ops.axllm_matmul(x, w, impl=impl, out_dtype=out_dtype,
                                row_parallel=row_parallel)
    y = jnp.dot(x, w.astype(x.dtype))
    return y if out_dtype is None else y.astype(out_dtype)


def concat_weights(ws) -> Array:
    """Concatenate linear weights along the output dim for a fused
    projection. All-dense concatenates arrays; all-QTensor routes through
    :func:`repro.core.quantization.qconcat` (exact — scales travel with
    their columns). Mixing the two is an error: fuse after
    `deploy_quantize`, not across the quantization boundary."""
    ws = list(ws)
    n_q = sum(isinstance(w, QTensor) for w in ws)
    if n_q == len(ws):
        from repro.core.quantization import qconcat
        return qconcat(ws)
    if n_q:
        raise TypeError("concat_weights: cannot fuse a mix of QTensor and "
                        "dense weights — quantize first, then fuse")
    return jnp.concatenate(ws, axis=-1)


@dataclasses.dataclass(frozen=True)
class LoRAConfig:
    rank: int = 16
    alpha: float = 32.0
    # which weight names get adapters (paper fine-tunes attention projections)
    targets: tuple = ("wq", "wk", "wv", "wo")

    @property
    def scaling(self) -> float:
        return self.alpha / self.rank


def lora_init(rng: jax.Array, n_in: int, n_out: int,
              cfg: LoRAConfig, dtype=jnp.float32) -> dict:
    """A ~ N(0, 1/r) (quantization-friendly: same value locality as W rows,
    which is what Fig. 5's combined [W ‖ A] reuse exploits), B = 0."""
    ka, _ = jax.random.split(rng)
    a = jax.random.normal(ka, (n_in, cfg.rank), dtype) / jnp.sqrt(cfg.rank)
    b = jnp.zeros((cfg.rank, n_out), dtype)
    return {"lora_a": a, "lora_b": b}


def lora_linear(x: Array, w, adapter: Optional[dict], cfg: LoRAConfig, *,
                impl: str = "auto", out_dtype=None) -> Array:
    """y = x @ W + scaling * (x @ A) @ B; W may be a QTensor (Fig. 5 path)."""
    if adapter is None:
        return linear(x, w, impl=impl, out_dtype=out_dtype)
    if isinstance(w, QTensor):
        return ops.lora_matmul(x, w, adapter["lora_a"], adapter["lora_b"],
                               cfg.scaling, impl=impl, out_dtype=out_dtype)
    y = jnp.dot(x, w.astype(x.dtype))
    xa = jnp.dot(x, adapter["lora_a"].astype(x.dtype))
    y = y + cfg.scaling * jnp.dot(xa, adapter["lora_b"].astype(x.dtype))
    return y if out_dtype is None else y.astype(out_dtype)


def lora_delta_batched(x: Array, adapter: dict, idx: Array,
                       scaling: float) -> Array:
    """Gathered multi-adapter LoRA delta — the serve-path second pipeline.

    Computes ``scaling * (x @ A[idx]) @ B[idx]`` with a per-batch-row
    adapter selection, so one dispatch serves a mixed batch of base-only
    rows and rows running N different adapters (paper §III dual-pipeline:
    the base weight stays untouched — quantized or dense — while the
    low-rank delta rides alongside in bf16/fp32).

    x:        ``[B, ..., n_in]`` activations (any number of middle dims).
    adapter:  ``{"lora_a": [L, n_in, r], "lora_b": [L, r, n_out]}`` —
              ``L`` stacked adapters (an :class:`~repro.serve.adapters.
              AdapterRegistry` target entry for one layer).
    idx:      ``[B]`` int32 adapter row per batch element; ``-1`` means
              base-only (that row's delta is masked to exact zeros).
    scaling:  the LoRA ``alpha / rank`` factor.

    Returns a float32 ``[B, ..., n_out]`` delta (cast at the call site).
    Row ``i`` of the result is bit-identical to running the unbatched
    two-matmul LoRA path on ``x[i]`` with adapter ``idx[i]`` alone: the
    gather feeds the very same A/B operands into a per-row-independent
    contraction (property-tested in tests/test_adapters.py).
    """
    idx = jnp.asarray(idx, jnp.int32)
    safe = jnp.maximum(idx, 0)                      # -1 rows gather row 0 ...
    a = jnp.take(adapter["lora_a"], safe, axis=0).astype(jnp.float32)
    b = jnp.take(adapter["lora_b"], safe, axis=0).astype(jnp.float32)
    xf = x.astype(jnp.float32)
    xa = jnp.einsum("b...k,bkr->b...r", xf, a)      # [B, ..., r]
    delta = jnp.einsum("b...r,brn->b...n", xa, b)   # [B, ..., n_out]
    mask = (idx >= 0).astype(jnp.float32)           # ... and are masked here
    mask = mask.reshape(idx.shape[0], *([1] * (x.ndim - 1)))
    return scaling * delta * mask


def merge_lora(w: Array, adapter: dict, cfg: LoRAConfig) -> Array:
    """Fold the adapter into a dense weight (for equivalence tests)."""
    return w + cfg.scaling * (adapter["lora_a"] @ adapter["lora_b"]).astype(
        w.dtype)


def deploy_quantize(params, qcfg: QuantConfig):
    """Post-training conversion of a trained pytree to the AxLLM serving
    representation (wraps quantize_tree; named for discoverability)."""
    from repro.core.quantization import quantize_tree
    return quantize_tree(params, qcfg)
