"""The least time the chip could take for the routed experts' products of
the traced sub-window, over the traced time of the grouped matrix product
(``_moe_gmm_kernel``).  Operations: every token materialised through its 6
experts of every expert layer.  Bytes: what the launches made had to read
of the experts' weights, all of them for a chunk launch and the 6 one token
chooses, at least, for a decode or verify launch (``ctx["ref"]``).  The
larger of operations over peak FLOP/s and bytes over peak bytes/s."""
from benchmarks.harness.peaks import peaks_for


def _launches(sub, prefix):
    """Launches of the programs named ``prefix``... between the
    sub-window's marks (``{program: (calls, compiles)}`` at each)."""
    opened, closed = sub["open"]["families"], sub["close"]["families"]
    return sum(calls - opened.get(k, (0, 0))[0]
               for k, (calls, _) in closed.items() if k.startswith(prefix))


def read(ctx):
    took, n = ctx["trace"].kernel_seconds("_moe_gmm_kernel")
    sub = ctx["run"]["sub"]
    before, after = sub["open"]["cached"], sub["close"]["cached"]
    before = before + [0] * (len(after) - len(before))
    tokens = sum(max(0, b - a) for a, b in zip(before, after))
    if not n or tokens <= 0:
        return None
    ops, nbytes = ctx["ref"].moe_gmm_flops_bytes(
        ctx["sizes"], tokens, _launches(sub, "chunk_prefill"),
        _launches(sub, "decode") + _launches(sub, "verify"))
    peaks = peaks_for(ctx["device_kind"])
    least = max(ops / peaks["flops_bf16"], nbytes / peaks["hbm_bytes_per_s"])
    return 100.0 * least / took
