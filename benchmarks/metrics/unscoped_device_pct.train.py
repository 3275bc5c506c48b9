"""Share of the chip's device time in operations under no device scope, those with no metadata included: what the vocabulary does not cover."""
from benchmarks.harness import blocks

read = blocks.reader("unscoped_device_pct.train", blocks.UNSCOPED)
