"""Operations and bytes the served model needs, from its sizes alone.

Every count here is a function of the configuration file and of the
harness's own per-step bookkeeping (``harness.WorkLog``), so it reads the
same whatever implements the work: a faster kernel changes the time it
is divided by, never the work.
"""

from __future__ import annotations

import numpy as np

from bench import weights


def layer_params(m: dict) -> int:
    """Weight-code elements of all decoder layers (the int8 matrices)."""
    per = sum(k * n for k, n in weights.shapes(m).values())
    return per * m["num_hidden_layers"]


def layer_act_bytes(m: dict) -> int:
    """bf16 bytes in and out of every layer matrix, per token."""
    per = sum(2 * (k + n) for k, n in weights.shapes(m).values())
    return per * m["num_hidden_layers"]


def scale_bytes(m: dict) -> int:
    """float32 per-channel scales of every matrix, layers and head."""
    per = sum(n for _, n in weights.shapes(m).values())
    return 4 * (per * m["num_hidden_layers"] + weights.padded_vocab(m))


def head_params(m: dict) -> int:
    """lm_head codes: hidden x padded vocabulary."""
    return m["hidden_size"] * weights.padded_vocab(m)


def weight_flops(m: dict, tokens: int, head_rows: int) -> int:
    """2 x tokens x layer weights, plus the head for rows whose logits are
    used."""
    return 2 * tokens * layer_params(m) + \
        2 * head_rows * m["hidden_size"] * m["vocab_size"]


def weight_bytes(m: dict, weight_reads: int, tokens: int, head_rows: int
                 ) -> int:
    """Codes and scales read once per dispatch that runs the layers
    (decode step or prefill wave), plus activations in and out."""
    once = layer_params(m) + head_params(m) + scale_bytes(m)
    act = tokens * layer_act_bytes(m) + head_rows * (
        2 * m["hidden_size"] + 4 * weights.padded_vocab(m))
    return weight_reads * once + act


def kv_bytes_per_token(m: dict) -> int:
    """bf16 K and V of one position, all layers."""
    return (2 * 2 * m["num_key_value_heads"] * m["head_dim"]
            * m["num_hidden_layers"])


def attn_flops_per_key(m: dict) -> int:
    """QK^T and PV for one query against one key, all heads and layers."""
    return 4 * m["head_dim"] * m["num_attention_heads"] * \
        m["num_hidden_layers"]


def decode_attention(m: dict, ctx) -> tuple:
    """(flops, bytes) of decode attention for tokens whose contexts (keys
    attended, the new one included) are ``ctx``: every context's K and V
    read once, q in and out per head."""
    ctx = np.asarray(ctx, np.int64)
    keys = int(ctx.sum())
    qo = 2 * 2 * m["num_attention_heads"] * m["head_dim"] * \
        m["num_hidden_layers"]
    return (keys * attn_flops_per_key(m),
            keys * kv_bytes_per_token(m) + len(ctx) * qo)


def prefill_attention_flops(m: dict, spans) -> int:
    """Causal attention of prefill positions [start, end): position p
    attends p + 1 keys."""
    keys = 0
    for s, e in spans:
        keys += (e * (e + 1) - s * (s + 1)) // 2
    return keys * attn_flops_per_key(m)


def model_flops(m: dict, work) -> int:
    """Every prefill and decode token of the window: weights, head for the
    logits used, and causal attention over each token's context."""
    t_pre = sum(e - s for s, e in work.prefill_pos)
    t_dec = len(work.decode_ctx)
    return (weight_flops(m, t_pre + t_dec, t_dec + work.prefill_rows)
            + decode_attention(m, work.decode_ctx)[0]
            + prefill_attention_flops(m, work.prefill_pos))
