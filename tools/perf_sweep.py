"""Perf sweep on real hardware: find the fastest configurations for the
headline benchmark and the flash kernels.

Complements ``bench.py`` (which reports ONE headline line for the driver):
this sweeps the knobs that move single-chip throughput and prints one JSON
line per point, so block sizes / batch sizes can be chosen from data
rather than defaults.

Usage: ``python tools/perf_sweep.py [--quick]``
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


def sweep_flash(blocks, iters):
    import jax
    import jax.numpy as jnp
    from apex_tpu.ops.flash_attention import flash_attention

    b, s, h, d = 4, 2048, 8, 64
    ks = jax.random.split(jax.random.PRNGKey(2), 3)
    q, k, v = (jax.random.normal(kk, (b, s, h, d), jnp.bfloat16)
               for kk in ks)
    flops = 3.5 * 4 * b * h * s * s * d * 0.5  # fwd+bwd, causal

    for bq in blocks:
        for bk in blocks:
            try:
                @jax.jit
                def fwd_bwd(q, k, v):
                    f = lambda q, k, v: flash_attention(
                        q, k, v, causal=True, use_pallas=True,
                        interpret=False, block_q=bq,
                        block_k=bk).astype(jnp.float32).sum()
                    return jax.value_and_grad(f, argnums=(0, 1, 2))(q, k, v)
                l, g = fwd_bwd(q, k, v)
                float(l)
                t0 = time.perf_counter()
                for _ in range(iters):
                    l, g = fwd_bwd(q, k, v)
                float(l)
                dt = (time.perf_counter() - t0) / iters
                print(json.dumps({
                    "sweep": "flash_fwd_bwd", "block_q": bq, "block_k": bk,
                    "ms": round(dt * 1e3, 2),
                    "tflops": round(flops / dt / 1e12, 2)}), flush=True)
            except Exception as e:
                print(json.dumps({"sweep": "flash_fwd_bwd", "block_q": bq,
                                  "block_k": bk,
                                  "error": f"{type(e).__name__}: {e}"}),
                      flush=True)


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--quick", action="store_true",
                    help="fewer points / iterations")
    args = ap.parse_args()

    from apex_tpu.ops.pallas_utils import require_tpu
    from apex_tpu.utils.compile_cache import enable_compile_cache

    dev = require_tpu()
    print(json.dumps({"platform": dev.platform,
                      "device_kind": dev.device_kind,
                      "compile_cache_dir": enable_compile_cache()}),
          flush=True)

    iters = 5 if args.quick else 20
    sweep_resnet([128] if args.quick else [64, 128, 256], iters)
    sweep_stem(iters)
    sweep_flash([128] if args.quick else [128, 256, 512],
                3 if args.quick else 10)
    try:
        print(json.dumps({"sweep": "fused_adam",
                          **bench.bench_fused_adam()}), flush=True)
    except Exception as e:
        print(json.dumps({"sweep": "fused_adam",
                          "error": f"{type(e).__name__}: {e}"}), flush=True)


if __name__ == "__main__":
    main()
