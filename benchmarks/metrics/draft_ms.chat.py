"""Median duration per step of the ``draft`` span: the n-gram drafter over every running request."""
from benchmarks.harness import spans

read = spans.reader("draft_ms.chat", spans.draft_ms)
