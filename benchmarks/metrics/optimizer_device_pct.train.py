"""Share of the chip's device time under the ``optimizer`` scope: amp's loss scale and unscale, the flat gathers and pads, ``_adam_kernel`` and the cut back to leaves."""
from benchmarks.harness import blocks

read = blocks.reader("optimizer_device_pct.train", "optimizer")
