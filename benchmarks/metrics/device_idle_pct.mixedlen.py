"""1 minus busy over the traced window, busy as the union of the operation intervals on the busiest chip."""
from benchmarks.harness.readers import device_idle_pct as read
