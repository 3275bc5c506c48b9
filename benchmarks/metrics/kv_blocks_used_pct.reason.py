"""1 minus the lowest free-block count of the window over the pool size: the blocks of the two attention layers (the conv layers' state is a ring of its slot's)."""
from benchmarks.harness.readers import kv_blocks_used_pct as read
