"""Roofline share (%) of the int8 weight-code matmul kernels: the least
time for the window's weight work (2 x tokens x weights at the int8
peak, or codes and scales read once per dispatch plus activations at HBM
bandwidth, whichever is longer) over the device time of the weight path:
the kernels and the copies of int8 codes that feed them
(``bench/kernels.json``). Moves itl_p95_ms."""

import sys

from bench import work as W


def read(ctx):
    t = ctx["trace"]["kernel_s"].get("weight_matmul")
    w, m, pk = ctx["work"], ctx["conf"], ctx["peaks"]
    tokens = sum(e - s for s, e in w.prefill_pos) + len(w.decode_ctx)
    if not t or not tokens:
        return None
    rows = len(w.decode_ctx) + w.prefill_rows
    flops = W.weight_flops(m, tokens, rows)
    nbytes = W.weight_bytes(m, w.decode_steps + w.prefill_waves, tokens, rows)
    tc, tb = flops / pk["int8_ops"], nbytes / pk["hbm_bytes_per_s"]
    print(f"weight_matmul: {flops:.4g} ops, {nbytes:.4g} bytes, kernel "
          f"{t:.4g} s, bound by {'compute' if tc > tb else 'bandwidth'}",
          file=sys.stderr)
    return 100.0 * max(tc, tb) / t
