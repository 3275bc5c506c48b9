"""Median of all gaps between successive tokens at the client's stream
callback, pooled over the window's requests: one decode step of the 64
slots, and a chunk's beside it now and then.  It carries no bound here:
the cell's end-to-end metric is the tokens completed."""


def read(ctx):
    return ctx["e2e"].get("itl_p50_ms")
