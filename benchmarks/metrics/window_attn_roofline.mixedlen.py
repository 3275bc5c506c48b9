"""The least time the chip could take for the WINDOW layers' attention of
the tokens materialised in the traced sub-window, over the traced time of
their kernels (``_window_decode_kernel``, ``_window_verify_kernel``,
``_window_chunk_kernel``).  Operations: for each token the keys its window
holds, at most 128, every query head's score and value row.  Bytes: the
live rows each decode launch's queries could see, at most 128 a query (a
launch's queries are no more than the tokens its step produced or the
slots; their rows no more than the launch's cached tokens).  The larger
of operations over peak FLOP/s and bytes over peak bytes/s."""
from benchmarks.harness import readers
from benchmarks.harness.peaks import peaks_for

KERNELS = ("_window_decode_kernel", "_window_verify_kernel",
           "_window_chunk_kernel")


def read(ctx):
    took = sum(ctx["trace"].kernel_seconds(k)[0] for k in KERNELS)
    sub = ctx["run"]["sub"]
    before, after = sub["open"]["cached"], sub["close"]["cached"]
    before = before + [0] * (len(after) - len(before))
    window, slots = ctx["sizes"]["sliding_window"], ctx["run"]["slots"]
    rows = sum(min(s[4], window * min(s[2], slots))
               for s in readers._sub_steps(ctx) if s[3] == "decode")
    ops, nbytes = ctx["ref"].window_attention_flops_bytes(
        ctx["sizes"], list(zip(before, after)), rows)
    if took <= 0 or ops <= 0:
        return None
    peaks = peaks_for(ctx["device_kind"])
    least = max(ops / peaks["flops_bf16"], nbytes / peaks["hbm_bytes_per_s"])
    return 100.0 * least / took
