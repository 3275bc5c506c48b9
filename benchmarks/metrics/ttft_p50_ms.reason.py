"""Median time from the moment a request was sent to its first token: in
a closed loop over capacity it is the wait for a slot and the prompt's
own prefill.  It carries no bound here."""


def read(ctx):
    return ctx["e2e"].get("ttft_p50_ms")
