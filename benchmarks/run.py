"""The benchmark's command: one run of one cell.

    python3 benchmarks/run.py --workload <cell> --seed <n> \\
        --seconds <s> --trace <0|1>

The cell, its configuration, its traffic and its metrics are found by
the names in ``BENCHMARK.json`` (``harness/spec.py``).  The last line
of standard output is the result; the numbers compared for ``correct``
are also the last lines of standard error.
"""

import time

_T0 = time.perf_counter()       # set-up is counted from here

import argparse  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from benchmarks.harness import compare, line, spec  # noqa: E402


def runner_for(kind):
    if kind == "train":
        from benchmarks.harness import train
        return train.run
    if kind == "serve":
        from benchmarks.harness import serve
        return serve.run
    raise SystemExit(f"no runner {kind!r}: a traffic mix names train or "
                     "serve")


def main(argv=None, *, root=spec.ROOT, require_chip=True, t_start=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    traced = bool(args.trace)

    cell = spec.load_cell(args.workload, root)
    result = runner_for(cell.traffic["runner"])(
        cell, args.seed, args.seconds, traced,
        _T0 if t_start is None else t_start, require_chip)
    obj = line.last_line(
        cell, traced, correct=result["correct"],
        attempted=result["attempted"], failed=result["failed"],
        values=result["values"], device=result["device"],
        compared=result["compared"], breakdown=result["breakdown"])
    try:
        text = line.check_line(obj, cell, traced)
    except line.LineError as e:
        raise SystemExit(f"{cell.name}: the result line is not what the "
                         f"driver reads, so none is printed: {e}")
    sys.stderr.flush()
    compare.report(result["compared"], result["notes"], sys.stderr)
    print(text, flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
