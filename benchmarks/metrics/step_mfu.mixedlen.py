"""Forward operations of the tokens processed in the traced window over window times peak: the share held here (the held experts a token chose, the 19,200 rows of the head once a span)."""
from benchmarks.harness.readers import serve_step_mfu as read
