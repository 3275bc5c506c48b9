"""Median host time of one ``chunk_prefill`` span, less the ``prefill_read`` in which a final chunk waits for the device."""
from benchmarks.harness import spans

read = spans.reader("chunk_dispatch_ms.backlog", spans.chunk_dispatch_ms)
