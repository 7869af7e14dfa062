"""Model FLOPs of every prefill and decode token in the traced window over
device-busy seconds x the bf16 peak (%). Moves output_tok_s."""

from bench import work as W


def read(ctx):
    busy = ctx["trace"]["busy_s"]
    if busy <= 0:
        return None
    flops = W.model_flops(ctx["conf"], ctx["work"])
    if not flops:
        return None
    return 100.0 * flops / (busy * ctx["peaks"]["bf16_flops"])
