"""Median of ``Request.admitted_at - submitted_at`` over the requests of the window."""
from benchmarks.harness.readers import queue_wait_p50_ms as read
