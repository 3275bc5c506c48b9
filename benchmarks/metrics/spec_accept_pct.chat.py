"""Accepted over drafted tokens of the window (the server counters)."""
from benchmarks.harness.readers import spec_accept_pct as read
