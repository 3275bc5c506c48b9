"""The least time the chip could take for the attention of the tokens
materialised in the traced sub-window, over the traced time of the latent
attention kernels (one row, a verify tile, a chunk).  The work is counted
from the published, expanded equations (``ctx["ref"]``): every head's
scores over 192 values and its value row of 128 for each (token, key) pair,
and for the bytes the cached rows each decode launch had to read; the
larger of operations over peak FLOP/s and bytes over peak bytes/s."""
from benchmarks.harness import readers
from benchmarks.harness.peaks import peaks_for

KERNELS = ("_latent_decode_kernel", "_latent_verify_kernel",
           "_latent_chunk_kernel")


def read(ctx):
    took = sum(ctx["trace"].kernel_seconds(k)[0] for k in KERNELS)
    sub = ctx["run"]["sub"]
    before, after = sub["open"]["cached"], sub["close"]["cached"]
    before = before + [0] * (len(after) - len(before))
    live = sum(s[4] for s in readers._sub_steps(ctx) if s[3] == "decode")
    ops, nbytes = ctx["ref"].latent_attention_flops_bytes(
        ctx["sizes"], list(zip(before, after)), live)
    if took <= 0 or ops <= 0:
        return None
    peaks = peaks_for(ctx["device_kind"])
    least = max(ops / peaks["flops_bf16"], nbytes / peaks["hbm_bytes_per_s"])
    return 100.0 * least / took
