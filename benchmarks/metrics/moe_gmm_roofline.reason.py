"""The least time the chip could take for the routed experts' products
of the traced sub-window, over the traced time of the grouped matrix
product (``_moe_gmm_kernel``).  Operations: every token materialised
through the 4 experts it chose in each of the 8 expert layers.  Bytes:
the weights of the experts each launch's rows chose, in expectation over
an even router (all 64 for a chunk of 256 rows, nearly all for a decode
launch of 64 slots; the rows of each launch from the program's spans:
``ctx["ref"]``).  The larger of operations over peak FLOP/s and bytes
over peak bytes/s."""
import os

from benchmarks.harness import spec
from benchmarks.harness.peaks import peaks_for

launched = spec.load_module(os.path.join(
    os.path.dirname(os.path.abspath(__file__)),
    "decode_rows_mean.reason.py")).launched


def read(ctx):
    took, n = ctx["trace"].kernel_seconds("_moe_gmm_kernel")
    rows = launched(ctx)
    sub = ctx["run"]["sub"]
    before, after = sub["open"]["cached"], sub["close"]["cached"]
    before = before + [0] * (len(after) - len(before))
    tokens = sum(max(0, b - a) for a, b in zip(before, after))
    if not n or took <= 0 or rows is None or tokens <= 0:
        return None
    ops, nbytes = ctx["ref"].moe_gmm_flops_bytes(
        ctx["sizes"], tokens,
        rows["chunk"] + [b + d for b, d in rows["step"]])
    peaks = peaks_for(ctx["device_kind"])
    least = max(ops / peaks["flops_bf16"], nbytes / peaks["hbm_bytes_per_s"])
    return 100.0 * least / took
