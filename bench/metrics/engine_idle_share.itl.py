"""Share (%) of the traced window in which the device is idle while the
harness is inside a ServeEngine call; idle time spent waiting for an
arrival is left out. Moves itl_p95_ms."""


def read(ctx):
    t = ctx["trace"]
    if t["window_s"] <= 0:
        return None
    return 100.0 * t["engine_idle_s"] / t["window_s"]
