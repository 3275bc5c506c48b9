"""Forward and backward operations per token, no recomputation, times the tokens per second of the whole steps in the traced window, over chips times peak."""
from benchmarks.harness.readers import train_step_mfu as read
