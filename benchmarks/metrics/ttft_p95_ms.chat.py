"""95th percentile, over all requests due in the window, of the time from
the moment a request was due to its first token at the client; a failed
request counts as the longest.  About 100 requests fall in a window at this
cell's rate, the tail is the fifth worst of them, and it carries no bound."""


def read(ctx):
    return ctx["e2e"].get("ttft_p95_ms")
