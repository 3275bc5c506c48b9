"""Host time per step to build the next batch and have it on the device."""
from benchmarks.harness.readers import input_wait_ms as read
