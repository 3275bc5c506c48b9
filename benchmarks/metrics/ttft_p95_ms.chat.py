"""95th percentile, over all requests due in the window, of the time from
the moment a request was due to its first token at the client; a failed
request counts as the longest.  About 30 requests fall in a window at this
cell's rate, so this tail swings from run to run and carries no bound."""


def read(ctx):
    return ctx["e2e"].get("ttft_p95_ms")
