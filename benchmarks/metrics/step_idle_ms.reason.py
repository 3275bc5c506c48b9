"""Device idle time inside the server's ``step`` spans of the traced sub-window over the number of those that launched work."""
from benchmarks.harness import spans

read = spans.reader("step_idle_ms.reason", spans.step_idle_ms)
