"""Of the window-layer rows of the tokens cached when the traced
sub-window closed, by the requests that advanced in it, the share the
pool no longer holds: a request's ring keeps its last
``cache.sliding_attention.ring_rows`` rows (the configuration's file;
``stats()["memory"]["by_kind"]["window"]["rows_a_slot"]`` in the
program) and has let the rest go.  From the requests' own ``num_cached``
at the sub-window's marks."""


def read(ctx):
    sub = ctx["run"]["sub"]
    before, after = sub["open"]["cached"], sub["close"]["cached"]
    before = before + [0] * (len(after) - len(before))
    ring = ctx["sizes"]["cache"]["sliding_attention"]["ring_rows"]
    cached = [b for a, b in zip(before, after) if b > a]
    if not cached:
        return None
    return 100.0 * sum(max(0, n - ring) for n in cached) / sum(cached)
