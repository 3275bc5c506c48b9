"""Records, on the chip, the small traces the reducer's test reads.

    python3 tests/benchmark/record_fixture.py          (one chip)
    python3 tests/benchmark/record_fixture.py 4        (four: collectives)

Runs the tiny cells of ``tiny_root`` with ``--trace 1`` through the real
``main`` and keeps each run's ``.xplane.pb`` (gzipped) with the numbers
the reducer gave on the chip, under ``chiprun_out/fixture/``; copied
from there into ``tests/benchmark/data/`` they are what
``test_bench_trace.py`` checks the reducer against on the CPU.
"""

import glob
import gzip
import json
import os
import shutil
import sys
import tempfile

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, ROOT)
sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))

import tiny_root  # noqa: E402
from benchmarks import run  # noqa: E402
from benchmarks.harness import trace  # noqa: E402

OUT = os.path.join(ROOT, "chiprun_out", "fixture")
KERNELS = ("_fwd_kernel", "_bwd_dq_kernel", "_bwd_dkv_kernel",
           "_ln_fwd_kernel", "_ln_bwd_kernel", "_adam_kernel",
           "_decode_kernel")


def summary(t):
    return {
        "device": t.device, "devices": sorted(t.planes),
        "busy_s": t.busy_s, "window_s": t.window_s,
        "programs": {k: [len(v), sum(v)] for k, v in t.programs().items()},
        "kernels": {k: list(t.kernel_seconds(k)) for k in KERNELS},
        "collective_exposed_s": t.collective_exposed_s(),
        "breakdown": t.breakdown(),
    }


def main():
    chips = int(sys.argv[1]) if len(sys.argv) > 1 else 1
    os.makedirs(OUT, exist_ok=True)
    tmp, _ = tiny_root.make(tempfile.mkdtemp(), chips=chips,
                            sizes=tiny_root.SMALL_SIZES,
                            mixes=tiny_root.small_traffic(0.12))
    original = trace.Trace.from_dir
    cells = ["tiny-train"] if chips > 1 else ["tiny-train", "tiny-chat",
                                              "tiny-backlog"]
    for cell in cells:
        tag = f"{cell}-{chips}chip"

        def keep(tdir, tag=tag):
            pb, = glob.glob(os.path.join(tdir, "plugins", "profile", "*",
                                         "*.xplane.pb"))
            with open(pb, "rb") as f, gzip.open(
                    os.path.join(OUT, tag + ".xplane.pb.gz"), "wb") as g:
                shutil.copyfileobj(f, g)
            t = original(tdir)
            with open(os.path.join(OUT, tag + ".json"), "w") as f:
                json.dump(summary(t), f, indent=1)
            return t

        trace.Trace.from_dir = staticmethod(keep)
        try:
            run.main(["--workload", cell, "--seed", "11", "--seconds", "2",
                      "--trace", "1"], root=tmp)
        except SystemExit as e:
            # at these sizes a share can read past 100% (the state fits
            # on-chip memory); the trace is kept all the same
            print(f"{cell}: {e}", file=sys.stderr)
    for f in sorted(os.listdir(OUT)):
        print(f, os.path.getsize(os.path.join(OUT, f)))


if __name__ == "__main__":
    main()
