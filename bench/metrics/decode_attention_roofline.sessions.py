"""Roofline share (%) of the paged decode-attention kernel in the sessions
cell: the least time for the keys the window's ``engine.decode`` spans
say their rows attend (``keys``; each key's K and V read once for all
layers, or 4 x head_dim x heads x layers operations at the bf16 peak,
``bench/work.py``) over the ``decode_attention`` kernel time
(``bench/kernels.json``). The queries and outputs, under 0.2% of the
bytes at these contexts, are left out. None where the spans carry no
``keys``. Moves itl_p95_ms."""

import sys

from bench import spans as S
from bench import work as W


def read(ctx):
    red = S.reduce_dir()
    t = ctx["trace"]["kernel_s"].get("decode_attention")
    if red is None or not t:
        return None
    spans = [st for st in S.of(red, "engine.decode")
             if st.get("phase") != "draft"]
    if not spans or any("keys" not in st for st in spans):
        return None
    keys = sum(st["keys"] for st in spans)
    m, pk = ctx["conf"], ctx["peaks"]
    flops = keys * W.attn_flops_per_key(m)
    nbytes = keys * W.kv_bytes_per_token(m)
    tc, tb = flops / pk["bf16_flops"], nbytes / pk["hbm_bytes_per_s"]
    print(f"decode_attention: {keys} keys, {flops:.4g} flops, "
          f"{nbytes:.4g} bytes, kernel {t:.4g} s, bound by "
          f"{'compute' if tc > tb else 'bandwidth'}", file=sys.stderr)
    return 100.0 * max(tc, tb) / t
