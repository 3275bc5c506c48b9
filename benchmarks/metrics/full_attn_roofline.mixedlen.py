"""The least time the chip could take for the FULL layers' attention of
the tokens materialised in the traced sub-window, over the traced time of
their kernels (``_decode_kernel``, ``_verify_kernel``, ``_chunk_kernel``:
one row, a verify tile, a chunk).  Operations: every query head's score
over 128 values and its value row of 128 for each (token, key) pair, every
key at or before the token.  Bytes: the cached rows (8 key-value heads of
K | V, 4,096 B) each decode launch had to read.  The larger of operations
over peak FLOP/s and bytes over peak bytes/s."""
from benchmarks.harness import readers
from benchmarks.harness.peaks import peaks_for

KERNELS = ("_decode_kernel", "_verify_kernel", "_chunk_kernel")


def read(ctx):
    took = sum(ctx["trace"].kernel_seconds(k)[0] for k in KERNELS)
    sub = ctx["run"]["sub"]
    before, after = sub["open"]["cached"], sub["close"]["cached"]
    before = before + [0] * (len(after) - len(before))
    live = sum(s[4] for s in readers._sub_steps(ctx) if s[3] == "decode")
    ops, nbytes = ctx["ref"].full_attention_flops_bytes(
        ctx["sizes"], list(zip(before, after)), live)
    if took <= 0 or ops <= 0:
        return None
    peaks = peaks_for(ctx["device_kind"])
    least = max(ops / peaks["flops_bf16"], nbytes / peaks["hbm_bytes_per_s"])
    return 100.0 * least / took
