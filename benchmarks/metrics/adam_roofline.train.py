"""``_adam_kernel`` against the bytes Adam has to move for the parameter count (bandwidth-bound)."""
from benchmarks.harness.readers import adam_roofline_train as read
