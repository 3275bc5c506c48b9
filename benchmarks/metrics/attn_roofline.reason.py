"""The least time the chip could take for the two attention layers'
attention of the tokens materialised in the traced sub-window, over the
traced time of their kernels (``_decode_kernel``, ``_verify_kernel``,
``_chunk_kernel``: one row, a verify tile, a chunk; 4 query heads of 64
over each key-value group of 128 lanes).  Operations: every query head's
score over 64 values and its value row of 64 for each (token, key) pair,
every key at or before the token.  Bytes: the cached rows (8 key-value
heads of K | V, 2,048 B) each decode or verify launch had to read.  The
harness counts a decode launch's rows; a verify launch is taken to read
those of the nearest decode launch before it (the first after it where
none came before): the same slots, a draft or two shorter.  The larger of
operations over peak FLOP/s and bytes over peak bytes/s."""
from benchmarks.harness import readers
from benchmarks.harness.peaks import peaks_for

KERNELS = ("_decode_kernel", "_verify_kernel", "_chunk_kernel")


def read(ctx):
    took = sum(ctx["trace"].kernel_seconds(k)[0] for k in KERNELS)
    sub = ctx["run"]["sub"]
    before, after = sub["open"]["cached"], sub["close"]["cached"]
    before = before + [0] * (len(after) - len(before))
    steps = readers._sub_steps(ctx)
    decoded = [s[4] for s in steps if s[3] == "decode"]
    live, last = 0, decoded[0] if decoded else 0
    for s in steps:
        if s[3] == "decode":
            last = s[4]
        if s[3] in ("decode", "verify"):
            live += last
    ops, nbytes = ctx["ref"].attention_flops_bytes(
        ctx["sizes"], list(zip(before, after)), live)
    if took <= 0 or ops <= 0:
        return None
    peaks = peaks_for(ctx["device_kind"])
    least = max(ops / peaks["flops_bf16"], nbytes / peaks["hbm_bytes_per_s"])
    return 100.0 * least / took
