"""Roofline share (%) of the paged decode-attention kernel: the least time
for every decoded token's attention (its whole context's K and V read
once plus q and out, or 4 x keys x head_dim x heads operations at the
bf16 peak) over the kernel's device time. Moves output_tok_s."""

import sys

from bench import work as W


def read(ctx):
    t = ctx["trace"]["kernel_s"].get("decode_attention")
    w, m, pk = ctx["work"], ctx["conf"], ctx["peaks"]
    if not t or not w.decode_ctx:
        return None
    flops, nbytes = W.decode_attention(m, w.decode_ctx)
    tc, tb = flops / pk["bf16_flops"], nbytes / pk["hbm_bytes_per_s"]
    print(f"decode_attention: {flops:.4g} flops, {nbytes:.4g} bytes, kernel "
          f"{t:.4g} s, bound by {'compute' if tc > tb else 'bandwidth'}",
          file=sys.stderr)
    return 100.0 * max(tc, tb) / t
