"""Forward operations of the tokens processed in the traced window over window times peak."""
from benchmarks.harness.readers import serve_step_mfu as read
