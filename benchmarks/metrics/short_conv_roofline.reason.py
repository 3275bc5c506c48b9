"""The least time the chip could take for the conv layers' short
convolution in the traced sub-window, over the traced time of its kernel
(``_short_conv_kernel``).  Bytes: every token materialised read as
``B | C | x`` and gated out in bfloat16 and its ``u`` written to the
state in float32, and for every live row of every launch (a chunk's one
row, a decode or verify launch's running requests) the 2 rows of ``u``
before its first read from the state (``ctx["ref"]``).  The larger of
operations over peak FLOP/s and bytes over peak bytes/s: bytes bound it."""
import os

from benchmarks.harness import spec
from benchmarks.harness.peaks import peaks_for

launched = spec.load_module(os.path.join(
    os.path.dirname(os.path.abspath(__file__)),
    "decode_rows_mean.reason.py")).launched


def read(ctx):
    took, n = ctx["trace"].kernel_seconds("_short_conv_kernel")
    rows = launched(ctx)
    sub = ctx["run"]["sub"]
    before, after = sub["open"]["cached"], sub["close"]["cached"]
    before = before + [0] * (len(after) - len(before))
    tokens = sum(max(0, b - a) for a, b in zip(before, after))
    if not n or took <= 0 or rows is None or tokens <= 0:
        return None
    visits = len(rows["chunk"]) + sum(b for b, _ in rows["step"])
    ops, nbytes = ctx["ref"].short_conv_flops_bytes(ctx["sizes"], tokens,
                                                    visits)
    peaks = peaks_for(ctx["device_kind"])
    least = max(ops / peaks["flops_bf16"], nbytes / peaks["hbm_bytes_per_s"])
    return 100.0 * least / took
