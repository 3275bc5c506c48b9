"""Median device time of one decode or verify launch."""
from benchmarks.harness import readers


def read(ctx):
    return readers.program_ms(ctx, ("jit__decode", "jit__verify"))
