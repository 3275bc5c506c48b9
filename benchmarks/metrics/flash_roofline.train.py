"""``_fwd_kernel``, ``_bwd_dq_kernel`` and ``_bwd_dkv_kernel`` against causal attention at the cell shapes: the larger of operations over peak FLOP/s and bytes over peak bytes/s."""
from benchmarks.harness.readers import flash_roofline_train as read
