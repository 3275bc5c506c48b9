"""The arithmetic the per-layer metrics share.  Each metric is a file of
its own under ``metrics/`` that names one of these with its arguments;
a new metric of a new kind brings its own arithmetic in its own file.

``ctx`` is what a traced run hands every reader: the reduced trace
(``ctx["trace"]``), the benchmark's spans and the server's counters of
the window, the cell's configuration and traffic, and its reference
(``ctx["ref"]``), which counts the operations and bytes of the work:
the readers know no model's sizes.  A reader that finds nothing to read
returns None and the metric stays out of the line.
"""

import numpy as np

from benchmarks.harness.peaks import peaks_for


def read_all(cell, ctx, values, device):
    """What a traced run adds: every per-layer metric of the cell whose
    reader found something, the device's busy and window seconds, and
    the breakdown for the line."""
    trace = ctx["trace"]
    for m in cell.per_layer:
        got = cell.reader(m["name"])(ctx)
        if got is not None:
            values[m["name"]] = got
    device["busy_s"], device["window_s"] = trace.busy_s, trace.window_s
    return trace.breakdown()


TRAIN_STEP = "jit_step"
FLASH_KERNELS = ("_fwd_kernel", "_bwd_dq_kernel", "_bwd_dkv_kernel")


def device_idle_pct(ctx):
    t = ctx["trace"]
    return 100.0 * (1.0 - t.busy_s / t.window_s)


def program_ms(ctx, prefixes):
    """Median device time of one launch of the programs named."""
    t = ctx["trace"]
    d = np.concatenate([t.program_durations(p) for p in prefixes])
    return 1e3 * float(np.median(d)) if d.size else None


def _whole_steps(ctx):
    n, lo, hi = ctx["trace"].whole_launches(TRAIN_STEP)
    return (n, lo, hi) if n >= 2 else (0, None, None)


def train_step_mfu(ctx):
    n, lo, hi = _whole_steps(ctx)
    if not n:
        return None
    per_token = ctx["ref"].train_flops_per_sequence(
        ctx["sizes"], ctx["seq"]) / ctx["seq"]
    rate = n * ctx["tokens_per_step"] / (hi - lo)
    peak = peaks_for(ctx["device_kind"])["flops_bf16"] * ctx["chips"]
    return 100.0 * rate * per_token / peak


def kernel_roofline(ctx, kernels, flops_bytes):
    """The least time the chip could take for the kernels' work in the
    whole steps of the window, over the time the kernels took."""
    n, lo, hi = _whole_steps(ctx)
    if not n:
        return None
    took = sum(ctx["trace"].kernel_seconds(k, lo, hi)[0] for k in kernels)
    if took <= 0:
        return None
    ops, nbytes = flops_bytes
    peaks = peaks_for(ctx["device_kind"])
    least = max(ops / peaks["flops_bf16"], nbytes / peaks["hbm_bytes_per_s"])
    return 100.0 * n * least / took


def flash_roofline_train(ctx):
    return kernel_roofline(
        ctx, FLASH_KERNELS, ctx["ref"].flash_train_flops_bytes(
            ctx["sizes"], ctx["batch_per_chip"], ctx["seq"]))


def adam_roofline_train(ctx):
    return kernel_roofline(ctx, ("_adam_kernel",),
                           (0, ctx["ref"].adam_bytes(ctx["sizes"])))


def collective_exposed_ms(ctx):
    n, lo, hi = _whole_steps(ctx)
    if not n:
        return None
    exposed = ctx["trace"].collective_exposed_s(lo, hi)
    return None if exposed is None else 1e3 * exposed / n


def input_wait_ms(ctx):
    w = ctx["spans"]["input_wait_s"]
    return 1e3 * float(np.mean(w)) if len(w) else None


# -- serving ------------------------------------------------------------------

def _window_steps(ctx):
    r = ctx["run"]
    return [s for s in r["steps"] if r["t_open"] <= s[0] < r["t_close"]]


def _sub_steps(ctx):
    sub = ctx["run"]["sub"]
    return [s for s in ctx["run"]["steps"]
            if sub["open"]["at"] <= s[0] < sub["close"]["at"]]


def server_step_ms(ctx):
    """Median wall time of one ``server.step()`` that launched work."""
    d = [s[1] - s[0] for s in _window_steps(ctx) if s[3] != "none"]
    return 1e3 * float(np.median(d)) if d else None


def queue_wait_p50_ms(ctx):
    w = ctx["queue_waits"]
    return 1e3 * float(np.median(w)) if len(w) else None


def spec_accept_pct(ctx):
    m = ctx["run"]["marks"]
    drafted = m["close"]["drafted"] - m["open"]["drafted"]
    accepted = m["close"]["accepted"] - m["open"]["accepted"]
    return 100.0 * accepted / drafted if drafted else None


def kv_blocks_used_pct(ctx):
    free = [s[5] for s in _window_steps(ctx)]
    return 100.0 * (1.0 - min(free) / ctx["num_blocks"]) if free else None


def serve_step_mfu(ctx):
    """Forward operations of every prompt and output token whose keys
    and values were materialized in the traced sub-window, over the
    window times the peak."""
    sub = ctx["run"]["sub"]
    before, after = sub["open"]["cached"], sub["close"]["cached"]
    before = before + [0] * (len(after) - len(before))
    ops = sum(ctx["ref"].forward_flops_at(ctx["sizes"], a, b)
              for a, b in zip(before, after))
    if ops <= 0:
        return None
    peak = peaks_for(ctx["device_kind"])["flops_bf16"] * ctx["chips"]
    return 100.0 * ops / (ctx["trace"].window_s * peak)


def decode_attn_roofline(ctx):
    """``_decode_kernel`` time against the bytes decode attention has to
    read: the live keys and values of the requests each decode launch
    of the sub-window served, not the copy the program gathers."""
    took, n = ctx["trace"].kernel_seconds("_decode_kernel")
    live = sum(s[4] for s in _sub_steps(ctx) if s[3] == "decode")
    if not n or not live:
        return None
    least = ctx["ref"].decode_attention_bytes(ctx["sizes"], live) \
        / peaks_for(ctx["device_kind"])["hbm_bytes_per_s"]
    return 100.0 * least / took
