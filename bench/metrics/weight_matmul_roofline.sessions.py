"""Roofline share (%) of the int8 weight-code matmul kernels in the
sessions cell: ``weight_matmul_roofline``'s reader, so the share has one
definition in every cell. Moves itl_p95_ms."""

from bench.run import load_reader


def read(ctx):
    return load_reader("weight_matmul_roofline")(ctx)
