"""Median host time of one decode or verify launch: its ``inputs`` span plus its ``launch`` span."""
from benchmarks.harness import spans

read = spans.reader("launch_host_ms.chat", spans.launch_host_ms)
