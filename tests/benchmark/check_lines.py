"""A second process's look at result lines a run has printed: feeds the
last line of each file to ``check_line`` for the cell and mode its name
gives (``<cell>.<seed>.t<0|1>.out``) and says what it found.

    python3 tests/benchmark/check_lines.py chiprun_out/sets/*.out
"""

import json
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

from benchmarks.harness import line, spec  # noqa: E402


def main(paths):
    bad = 0
    for path in paths:
        with open(path) as f:
            lines = f.read().strip().splitlines()
        try:
            cell, _seed, mode = os.path.basename(path)[:-len(".out")].rsplit(
                ".", 2)
            obj = json.loads(lines[-1])
            line.check_line(obj, spec.load_cell(cell), mode == "t1")
            print(f"{path}: ok, correct={obj['correct']}")
        except (IndexError, ValueError) as e:      # LineError is one
            bad += 1
            print(f"{path}: REFUSED: {e}")
    return 1 if bad else 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
