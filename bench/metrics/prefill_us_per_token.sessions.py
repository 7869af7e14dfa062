"""Device time per computed prompt token (us) in the sessions cell, where
each chunk of a turn's new tokens attends a cached prefix of 5-15k
positions: ``prefill_us_per_token.itl``'s reader. Moves itl_p95_ms."""

from bench.run import load_reader


def read(ctx):
    return load_reader("prefill_us_per_token.itl")(ctx)
