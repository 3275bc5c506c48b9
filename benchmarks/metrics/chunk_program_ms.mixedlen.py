"""Median device time of one chunk-prefill launch."""
from benchmarks.harness import readers


def read(ctx):
    return readers.program_ms(ctx, ("jit__chunk",))
