"""Device time of one decode step (ms) in the sessions cell, where every
row attends a context of 5-15k positions: ``decode_step_ms.itl``'s
reader. Moves itl_p95_ms."""

from bench.run import load_reader


def read(ctx):
    return load_reader("decode_step_ms.itl")(ctx)
