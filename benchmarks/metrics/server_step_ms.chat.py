"""Median wall time of one ``server.step()`` that launched work."""
from benchmarks.harness.readers import server_step_ms as read
