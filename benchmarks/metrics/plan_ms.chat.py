"""Median time per step under ``plan`` spans: deadlines, pressure, shedding, ``admit``, ``cow_copy``, chunk plans, decode capacity."""
from benchmarks.harness import spans

read = spans.reader("plan_ms.chat", spans.plan_ms)
