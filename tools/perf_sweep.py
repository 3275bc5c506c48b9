"""Perf sweep on real hardware: find the fastest configurations for the
headline benchmark and the flash kernels.

Complements ``bench.py`` (which reports ONE headline line for the driver):
this sweeps the knobs that move single-chip throughput and prints one JSON
line per point, so block sizes / batch sizes can be chosen from data
rather than defaults.

Usage: ``python tools/perf_sweep.py [--quick | --sampler | --paged]``
"""

import argparse
import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import bench  # noqa: E402  (the step builders live there)


def sweep_resnet(batches, iters):
    for b in batches:
        try:
            ips, step_ms, flops = bench.measure("O2", b, 224, iters)
            row = {"sweep": "resnet50_O2", "batch": b,
                   "images_per_sec": round(ips, 1),
                   "step_time_ms": round(step_ms, 2)}
            if flops:
                row["step_tflops"] = round(flops / 1e12, 3)
            print(json.dumps(row), flush=True)
        except Exception as e:
            print(json.dumps({"sweep": "resnet50_O2", "batch": b,
                              "error": f"{type(e).__name__}: {e}"}),
                  flush=True)


def sweep_stem(iters, batch=128):
    """The MLPerf space-to-depth stem (exactly equivalent math,
    tests/L0/test_models.py) at the headline batch — compare against
    sweep_resnet's batch-128 row, which IS the conv-stem measurement
    (no need to compile/time it twice)."""
    try:
        ips, step_ms, _ = bench.measure("O2", batch, 224, iters,
                                        stem="s2d")
        print(json.dumps({"sweep": "stem", "stem": "s2d", "batch": batch,
                          "images_per_sec": round(ips, 1),
                          "step_time_ms": round(step_ms, 2),
                          "baseline": "resnet50_O2 batch 128 row"}),
              flush=True)
    except Exception as e:
        print(json.dumps({"sweep": "stem", "stem": "s2d",
                          "error": f"{type(e).__name__}: {e}"}),
              flush=True)


FLASH_KERNELS = ("fwd", "dq", "dkv")


def _traced(fn, args, iters):
    """``iters`` launches of ``fn(*args)`` under the profiler, reduced
    (``benchmarks/harness/trace.py``): times come from the DEVICE's
    clock (a host clock around calls of a millisecond reads the dispatch
    too: 1.33 ms where the trace says 1.13, PR 28).  The first call,
    which compiles, is apart."""
    import jax
    from benchmarks.harness.trace import SubWindow
    jax.block_until_ready(fn(*args))
    window = SubWindow()
    window.start()
    try:
        for _ in range(iters):
            out = fn(*args)
        jax.block_until_ready(out)
    finally:
        window.stop()
    return window.reduce()


def _kernel_ms(fn, args, iters, kernel):
    """Milliseconds a launch of the Pallas kernel named ``kernel``
    takes: its events summed from the trace of ``iters`` launches."""
    seconds, events = _traced(fn, args, iters).kernel_seconds(kernel)
    if events != iters:
        raise RuntimeError(f"{events} events of {kernel} in the trace, "
                           f"{iters} launched")
    return seconds / iters * 1e3


def sweep_flash(shape=(8, 1024, 16, 64), blocks=(128, 256, 512, 1024),
                iters=10, causal=True, dtype="bfloat16",
                kernels=FLASH_KERNELS):
    """Each of the three flash kernels ALONE (no ``_layout`` copies, no
    ``delta``) at ``shape`` = (batch, sequence, heads, head size), for
    every ``(block_q, block_k)`` of ``blocks`` squared that divides the
    sequence.  One JSON line a point: the milliseconds, the share of
    the MXU's bfloat16 peak that the kernel's causal (or square) count
    of products reaches, and how many blocks of the grid were skipped,
    masked and unmasked (``block_kinds``).  A point Mosaic refuses (the
    blocks do not fit VMEM) prints its error.  Returns the rows."""
    import functools
    import importlib

    import jax
    import jax.numpy as jnp
    # the package exports the function under the module's name
    fa = importlib.import_module("apex_tpu.ops.flash_attention")

    b, s, h, d = shape
    dt = jnp.dtype(dtype)
    q, k, v, do = (jax.random.normal(kk, (b * h, s, d), dt)
                   for kk in jax.random.split(jax.random.PRNGKey(2), 4))
    mask = jnp.zeros((b, s), jnp.float32)
    seed = jnp.zeros((5,), jnp.int32)
    static = dict(scale=1.0 / d ** 0.5, causal=causal, h=h,
                  interpret=not fa.on_tpu(),
                  has_mask=False)
    base = min(512, s)
    o, lse = fa._fwd_pallas(q, k, v, mask, seed, bq=base, bk=base, **static)
    delta = jnp.sum(do.astype(jnp.float32) * o.astype(jnp.float32), axis=-1)
    bwd_args = (q, k, v, do, lse, delta, mask, seed)
    calls = {   # the launcher, its operands, the (s, s, d) products it runs
        "fwd": (fa._fwd_pallas, (q, k, v, mask, seed), 2, "_fwd_kernel"),
        "dq": (fa._bwd_dq_pallas, bwd_args, 3, "_bwd_dq_kernel"),
        "dkv": (fa._bwd_dkv_pallas, bwd_args, 4, "_bwd_dkv_kernel"),
    }
    # a product is 2*s*s*d a head; half of the square under the diagonal
    product = 2.0 * b * h * s * s * d * (0.5 if causal else 1.0)
    rows = []
    for kernel in kernels:
        fn, args, products, name = calls[kernel]
        for bq in blocks:
            for bk in blocks:
                if s % bq or s % bk:
                    continue
                row = {"sweep": "flash", "kernel": kernel,
                       "shape": list(shape), "causal": causal,
                       "dtype": dt.name, "block_q": bq, "block_k": bk,
                       **fa.block_kinds(s, s, bq, bk, causal)}
                try:
                    ms = _kernel_ms(
                        functools.partial(fn, bq=bq, bk=bk, **static), args,
                        iters, name)
                    row["ms"] = round(ms, 4)
                    if fa.on_tpu():   # a share of the v5e's 197 TFLOP/s
                        row["mxu_peak_pct"] = round(
                            100 * products * product / (ms * 1e-3) / 197e12,
                            2)
                except Exception as e:
                    row["error"] = f"{type(e).__name__}: {e}"[:200]
                rows.append(row)
                print(json.dumps(row), flush=True)
    return rows


# what the serving cells launch the fused sampler over (PERF.md section 4):
# gpt2-xl's decode and verify rows, k-exaone's verify rows, kanana-2's decode
SAMPLER_SHAPES = ((8, 50257), (8, 5, 50257), (16, 5, 19200), (8, 128256))


def sweep_sampler(shapes=SAMPLER_SHAPES, iters=20, sample_tokens=None):
    """``ops.sampling.sample_tokens`` ALONE, jitted, at each of
    ``shapes``, with the cells' parameters (temperature 0.8, top-p 0.95,
    every eighth row greedy) over logits of deviation 0.8 (what
    N(0, 0.02) weights give ``gpt2-xl``: a nucleus of three quarters of
    the vocabulary).  One JSON line a shape: the milliseconds a launch
    takes on the DEVICE's clock (the program's events in the trace) and
    the share of a sampled row that the mask keeps.  ``sample_tokens``
    takes another commit's function for the comparison.  On the CPU a
    shape carries an error and no time.  Returns the rows."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from apex_tpu.ops import sampling

    fn = sample_tokens or sampling.sample_tokens

    def sampler_alone(*a):      # the program's name in the trace
        return fn(*a)

    launch = jax.jit(sampler_alone)
    rows = []
    for shape in shapes:
        lead = shape[:-1]
        rng = np.random.RandomState(len(shape) + shape[-1])
        temp = np.full(lead, 0.8, np.float32)
        temp.reshape(-1)[::8] = 0.0
        args = [jnp.asarray(a) for a in (
            (rng.randn(*shape) * 0.8).astype(np.float32), temp,
            np.zeros(lead, np.int32), np.full(lead, 0.95, np.float32),
            rng.randint(0, 2 ** 30, size=lead).astype(np.int32),
            rng.randint(0, 1000, size=lead).astype(np.int32))]
        kept = sampling.processed_logits(*args[:4]) > -jnp.inf
        row = {"sweep": "sampler", "shape": list(shape),
               "kept_pct": round(100 * float(jnp.mean(kept[temp > 0])), 2)}
        try:
            took = _traced(launch, args, iters).program_durations(
                "jit_sampler_alone")
            # the profiler can miss the first of launches this short
            if len(took) < iters // 2:
                raise RuntimeError(f"{len(took)} launches in the trace, "
                                   f"{iters} made")
            row["ms"] = round(float(np.median(took)) * 1e3, 4)
        except Exception as e:
            row["error"] = f"{type(e).__name__}: {e}"[:200]
        rows.append(row)
        print(json.dumps(row), flush=True)
    return rows


# the page loop at the serving cells' shapes (PERF.md section 4): slots,
# block-table entries, query heads, key-value heads, head size and the
# mean context of the cell's mix
PAGED_SHAPES = {
    "kexaone": dict(slots=16, table=2048, heads=64, kv_heads=8, head_dim=128,
                    mean=4000),
    "lfm2": dict(slots=64, table=384, heads=32, kv_heads=8, head_dim=64,
                 mean=1800),
    "gpt2xl": dict(slots=8, table=64, heads=25, kv_heads=25, head_dim=64,
                   mean=200),
}
# a decode launch's row, a verify launch's five, a prefill chunk's 256
PAGED_ROWS = (1, 5, 256)


def sweep_paged(shapes=PAGED_SHAPES, rows_list=PAGED_ROWS, iters=10,
                block_size=16):
    """``ops.decode_attention.paged_attention`` ALONE, jitted, at each
    cell's shape for decode, verify and chunk rows (a chunk launch is one
    slot's), every slot's context (its rows included) at one page, at
    the mix's mean and at the whole table.  Each context below the
    whole table is timed twice: through a table of the server's length
    and through one cut to the context, so that the difference is what
    the windows past a slot's last row cost.  One JSON line a point: the
    milliseconds a launch takes on the DEVICE's clock, the windows the
    contexts hold and the table has, and the context's bytes over the
    time; then one line a pair with the microseconds an empty window
    costs (``empty_window_us``: a window of a slot's table past its
    context, over all the slot's row tiles).  On the CPU a point carries an error and no time.
    Returns the rows."""
    import functools

    import jax
    import jax.numpy as jnp
    import numpy as np

    from apex_tpu.ops import decode_attention as da

    rows_out = []
    for cell, sh in shapes.items():
        hpg = sh["heads"] // sh["kv_heads"]
        d, nb, slots = sh["head_dim"], sh["table"], sh["slots"]
        width = sh["kv_heads"] * 2 * d
        # every slot's whole table in pages of its own, behind block 0
        pool = jax.random.normal(
            jax.random.PRNGKey(0), (1, (slots * nb + 1) * block_size, width),
            jnp.bfloat16)
        pages = da._GROUP_PAGES if hpg > 1 else da._PAGES
        for r in rows_list:
            b = 1 if r > da._QROWS else slots
            q = jax.random.normal(jax.random.PRNGKey(r),
                                  (b, r, sh["heads"], d), jnp.bfloat16)
            launch = jax.jit(functools.partial(
                da.paged_attention, block_size=block_size,
                heads_per_group=hpg, interpret=False))
            name = da._kernel_name(r)
            full = nb * block_size
            for ctx in (block_size, sh["mean"], full):
                ctx = min(max(ctx, r), full)
                used = -(-ctx // block_size)
                tables = np.zeros((b, nb), np.int32)
                tables[:, :used] = (1 + np.arange(b)[:, None] * nb
                                    + np.arange(used)[None])
                starts = jnp.full((b,), ctx - r, jnp.int32)
                ms = {}
                for cut in ((nb, used) if used < nb else (nb,)):
                    row = {"sweep": "paged", "cell": cell, "kernel": name,
                           "slots": b, "rows": r, "context": ctx,
                           "table": cut,
                           "windows": b * -(-used // min(pages, cut)),
                           "table_windows": b * -(-cut // min(pages, cut))}
                    try:
                        ms[cut] = _kernel_ms(
                            launch, (q, pool, jnp.int32(0),
                                     jnp.asarray(tables[:, :cut]), starts),
                            iters, name)
                        row["ms"] = round(ms[cut], 4)
                        row["context_gb_per_s"] = round(
                            b * ctx * width * 2 / (ms[cut] * 1e-3) / 1e9, 1)
                    except Exception as e:
                        row["error"] = f"{type(e).__name__}: {e}"[:200]
                    rows_out.append(row)
                    print(json.dumps(row), flush=True)
                if len(ms) == 2:
                    empty = b * (-(-nb // pages) - -(-used // pages))
                    row = {"sweep": "paged_empty", "cell": cell,
                           "kernel": name, "rows": r, "context": ctx,
                           "empty_windows": empty,
                           "empty_window_us": round(
                               (ms[nb] - ms[used]) * 1e3 / empty, 4)}
                    rows_out.append(row)
                    print(json.dumps(row), flush=True)
    return rows_out


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--quick", action="store_true",
                    help="fewer points / iterations")
    ap.add_argument("--sampler", action="store_true",
                    help="only the serving sampler at the cells' shapes")
    ap.add_argument("--paged", action="store_true",
                    help="only the paged-attention page loop at the serving "
                         "cells' shapes")
    args = ap.parse_args()

    from apex_tpu.ops.pallas_utils import require_tpu
    from apex_tpu.utils.compile_cache import enable_compile_cache

    dev = require_tpu()
    print(json.dumps({"platform": dev.platform,
                      "device_kind": dev.device_kind,
                      "compile_cache_dir": enable_compile_cache()}),
          flush=True)

    if args.sampler:
        sweep_sampler()
        return
    if args.paged:
        sweep_paged()
        return
    iters = 5 if args.quick else 20
    sweep_resnet([128] if args.quick else [64, 128, 256], iters)
    sweep_stem(iters)
    if args.quick:
        sweep_flash(blocks=(256, 512), iters=3)
    else:
        # the grid the defaults of ``_default_block`` were chosen from
        for d, h in ((64, 16), (128, 8)):
            for s_ in (1024, 2048, 4096):
                for causal in (True, False):
                    sweep_flash((8192 // s_, s_, h, d), causal=causal)
    try:
        print(json.dumps({"sweep": "fused_adam",
                          **bench.bench_fused_adam()}), flush=True)
    except Exception as e:
        print(json.dumps({"sweep": "fused_adam",
                          "error": f"{type(e).__name__}: {e}"}), flush=True)


if __name__ == "__main__":
    main()
