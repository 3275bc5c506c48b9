"""1 minus the lowest free-block count of the window over the pool size."""
from benchmarks.harness.readers import kv_blocks_used_pct as read
