"""Share (%) of the prompt tokens admitted in the window whose KV came
from the prefix index (EngineStats.prefix_hit_tokens over hit plus
computed prompt tokens). Moves ttft_p50_ms."""


def read(ctx):
    w = ctx["work"]
    total = w.hit_tokens + w.prefill_tokens
    if total <= 0:
        return None
    return 100.0 * w.hit_tokens / total
