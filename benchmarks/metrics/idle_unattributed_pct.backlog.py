"""Share of the device's idle time under ``step`` or ``submit`` themselves or under no span of the program."""
from benchmarks.harness import spans

read = spans.reader("idle_unattributed_pct.backlog", spans.idle_unattributed_pct)
