"""How late the generator sent: sent time minus due time, 95th percentile."""


def read(ctx):
    return ctx["e2e"].get("gen_late_p95_ms")
