"""Forward operations of the tokens processed in the traced window over window times peak: the expanded equations, 4 experts a token, the tied head once a span."""
from benchmarks.harness.readers import serve_step_mfu as read
