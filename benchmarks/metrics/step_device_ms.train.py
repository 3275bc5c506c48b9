"""Median device time of the jitted training step (module ``jit_step``)."""
from benchmarks.harness import readers


def read(ctx):
    return readers.program_ms(ctx, (readers.TRAIN_STEP,))
