"""Per step, the time a collective ran on the busiest chip while no other operation did there: what of the gradient exchange the step does not hide."""
from benchmarks.harness.readers import collective_exposed_ms as read
