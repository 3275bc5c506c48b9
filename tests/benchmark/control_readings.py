"""The readings a serving cell's limit and ``decided_margin`` are set
from, taken on the chip at the cell's own size in one process, the
control in ONE lower precision (``limit_readings.py serve`` takes two, at
a reference's full pass each, which a cell whose rows are 32,768
positions cannot afford):

    python3 tests/benchmark/control_readings.py <cell> <seed,seed,...> <seconds> [precision]

For every seed: the server driven for ``seconds``; for the sample of
greedy requests, every served token's gap below the float32 reference's
best WITH NOTHING LEFT OUT, beside the position's least routing margin
(one pass of the reference's ``logit_and_margin_at``); then
the same for the token the reference computed in ``precision`` (default
``fp8``) puts first at the same positions.  The widest gap is then given
for each floor in ``FLOORS``: the two readings a limit lies between, at
every floor a configuration might state.  One JSON line per reading goes
to ``chiprun_out/limits/<cell>.control.jsonl``.
"""

import gc
import json
import os
import sys
import time

import numpy as np

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

from benchmarks.harness import program, spec  # noqa: E402

FLOORS = (None, 0.0005, 0.001, 0.002, 0.005)


def gaps_and_margins(session, runner, tokens_of=None):
    """``(gap, least margin)`` of every served token of the session's
    greedy sample, nothing left out; with ``tokens_of`` of the token that
    precision puts first at each served position."""
    import jax
    ref, sizes = session.ref, dict(session.sizes, decided_margin=None)
    params = ref.stacked(session.fresh_params(), sizes)
    at = jax.jit(lambda p, ids, t: ref.logit_and_margin_at(p, ids, t, sizes))
    gaps, least = [], []
    for block, ids in runner._id_blocks(
            session.rows, session.mix["check"]["rows_per_block"],
            ref.longest_row(sizes)):
        tokens = ids[:, 1:]
        if tokens_of is not None:
            tokens = jax.jit(lambda p, i: ref.token_gaps(
                p, i, None, sizes, tokens_of))(params, ids)[2]
        gap, margin = at(params, ids, tokens)
        gaps += runner._served(block, gap)
        least += runner._served(block, margin)
    return np.concatenate(gaps), np.concatenate(least)


def readings(cell, seeds, seconds, precision, out):
    from benchmarks.harness import serve as runner
    devices = program.devices_for(cell)
    program.import_program()[3]()
    for seed in seeds:
        t0 = time.perf_counter()
        s = runner.Session(cell, seed, seconds, False, devices, t0)
        for who in ("program", precision):
            gap, least = gaps_and_margins(
                s, runner, None if who == "program" else who)
            row = dict(
                cell=cell.name, seed=seed, who=who, tokens=int(gap.size),
                requests=len(s.rows), margin_median=float(np.median(least)),
                by_floor={str(f): {
                    "served_gap_max": float(np.max(np.where(
                        least > (f or -1.0), gap, 0.0))),
                    "judged": int((least > (f or -1.0)).sum()),
                    "over_a_tenth": int(((least > (f or -1.0))
                                         & (gap > 0.1)).sum())}
                    for f in FLOORS},
                served_gap_mean=float(gap.mean()),
                serve_tokens_per_s=s.e2e["serve_tokens_per_s"],
                seconds=time.perf_counter() - t0)
            out.write(json.dumps(row) + "\n")
            out.flush()
            print(json.dumps(row), flush=True)
        del s
        gc.collect()


if __name__ == "__main__":
    name, seeds, seconds = sys.argv[1:4]
    os.makedirs(os.path.join(ROOT, "chiprun_out", "limits"), exist_ok=True)
    with open(os.path.join(ROOT, "chiprun_out", "limits",
                           name + ".control.jsonl"), "a") as out:
        readings(spec.load_cell(name), [int(s) for s in seeds.split(",")],
                 float(seconds), sys.argv[4] if len(sys.argv) > 4 else "fp8",
                 out)
