"""Share of the chip's device time under the ``head`` scope: the final norm, the tied vocabulary product, its backward and the loss."""
from benchmarks.harness import blocks

read = blocks.reader("head_device_pct.train", "head")
