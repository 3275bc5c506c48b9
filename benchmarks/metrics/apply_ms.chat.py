"""Median self time per step of the ``apply`` span: retired tokens applied to their requests and pushed to the streams."""
from benchmarks.harness import spans

read = spans.reader("apply_ms.chat", spans.apply_ms)
