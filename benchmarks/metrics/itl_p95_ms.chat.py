"""95th percentile of all gaps between successive tokens at the client's
stream callback, pooled over the window's requests.  An end-to-end metric
until PR 26: it is the gap of a step with a prefill chunk beside it, a tenth
of the gaps at this cell's rate, and from run to run it slides along that
mode and onto the next (two chunks): five runs spread by 5.5 to 6.9% of
their median, more than half of the widest bound.  The median gap is the
cell's end-to-end metric; this tail stands beside it and carries no bound."""


def read(ctx):
    return ctx["e2e"].get("itl_p95_ms")
