"""Mean live rows a decode or verify launch carried in the traced
sub-window: the running requests each ``launch`` span of the program
names (its ``batch``), over those spans.  A small cache is what lets a
server hold more sequences a step; this is the number it should raise.
``launched`` is what the other readers of this cell take from the same
spans: the rows of every launch, chunks included."""
from benchmarks.harness import spans


def launched(ctx):
    """The launches in the traced sub-window, from the program's spans:
    ``{"chunk": [tokens], "step": [(running requests, drafted tokens)]}``,
    one entry a launch (drafted 0 for a decode launch); None where the
    program has no spans."""
    a = spans.analysis(ctx)
    if a is None:
        return None
    return {"chunk": [s.args["tokens"] for s in a.spans
                      if s.name == "chunk_prefill" and "tokens" in s.args],
            "step": [(s.args["batch"], s.args.get("drafted", 0))
                     for s in a.spans
                     if s.name == "launch" and "batch" in s.args]}


def _mean_rows(a):
    rows = [s.args["batch"] for s in a.spans if s.name == "launch"
            and "batch" in s.args]
    return sum(rows) / len(rows) if rows else None


read = spans.reader("decode_rows_mean.reason", _mean_rows)
