"""1 minus the lowest free-block count of the window over the pool size: the blocks of the layers that keep every token (the window layers' rings are their slots')."""
from benchmarks.harness.readers import kv_blocks_used_pct as read
