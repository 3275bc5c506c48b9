"""Median time from the moment a request was due to its first token."""


def read(ctx):
    return ctx["e2e"].get("ttft_p50_ms")
