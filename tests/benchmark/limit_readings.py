"""The readings the limits of ``correct`` are set from, taken on the chip
at a cell's own size in one process (``PERF.md`` records them):

    python3 tests/benchmark/limit_readings.py train <cell> <seed,seed,...> <n_control>
    python3 tests/benchmark/limit_readings.py train_reference <cell> <seed,...> <n_control>
    python3 tests/benchmark/limit_readings.py serve <cell> <seed,seed,...> <n_control> <seconds>

For every seed: the program against the float32 reference (the lower
readings).  For the first ``n_control`` seeds: the reference computed in
lower precisions put in the program's place, and the faults a cell can
have planted in the reference (the upper readings).  One JSON line per
reading goes to ``chiprun_out/limits/<cell>.jsonl``.
"""

import gc
import json
import os
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

from benchmarks.harness import compare, program, spec  # noqa: E402


def emit(out, **row):
    out.write(json.dumps(row) + "\n")
    out.flush()
    print(json.dumps(row), flush=True)


def train(cell, seeds, n_control, out, with_program=True):
    """``with_program=False`` (``train_reference``) takes the controls'
    and the faults' readings alone: they are computations of the
    reference on one device, so a four-chip cell's can be read on one
    chip, at its own global batch."""
    from benchmarks.harness import traffic
    from benchmarks.harness import train as runner
    program.import_program()[3]()
    sizes, mix = cell.config, cell.traffic
    ref = cell.reference()
    table = ref.param_table(sizes)
    rows = mix["batch_per_chip"] * cell.chips
    trainer = (runner.Trainer(cell, program.devices_for(cell))
               if with_program else None)
    every = {"loss1_gap": 1, "loss2_gap": 1, "loss3_gap": 1,
             "grad_norm_gap": 1, "delta_norm_gap": 1,
             "grad_norm_gap_median": 1, "delta_norm_gap_median": 1}

    def numbers(mine, theirs):
        compared, notes = compare.compare_training(mine, theirs, every)
        notes["grad_leaf_gaps"] = compare.leaf_gaps(
            mine["grad_norms"], theirs["grad_norms"])
        notes["delta_leaf_gaps"] = compare.leaf_gaps(
            mine["delta_norms"], theirs["delta_norms"])
        return {k: v["value"] for k, v in compared.items()}, notes

    def reference(seed, batches, precision="float32", keep_rows=None,
                  **hyper):
        return runner.reference_readings(
            ref, table, sizes, dict(sizes["optimizer"], **hyper), seed,
            batches, mix["reference_rows"], precision, keep_rows)

    for i, seed in enumerate(seeds):
        t0 = time.perf_counter()
        feed = traffic.packed_batches(mix, seed, rows, mix["seq"],
                                      ref.vocab(sizes))
        if with_program:
            params, opt_state, loss, batches, mine = trainer.first_steps(
                seed, feed)
            skipped = int(opt_state.skipped_steps)
            del params, opt_state, loss
            gc.collect()
        else:
            batches = [next(feed) for _ in range(runner.CHECK_STEPS)]
        theirs = reference(seed, batches)
        if with_program:
            vals, notes = numbers(mine, theirs)
            emit(out, cell=cell.name, seed=seed, who="program", **vals,
                 notes=notes, skipped_steps=skipped,
                 losses=mine["losses"], ref_losses=theirs["losses"],
                 seconds=time.perf_counter() - t0)
        if i >= n_control:
            continue
        readings = {
            "fp8": dict(precision="fp8"),
            "half_batch": dict(keep_rows=rows // 2),
            # a step that returns its state unchanged: every loss is
            # taken at the first parameters, and nothing moves
            "unchanged_state": dict(lr=0.0),
        }
        if cell.chips > 1:
            readings["no_exchange"] = dict(keep_rows=rows // cell.chips)
        only = os.environ.get("READINGS")        # e.g. READINGS=fp8
        for who, how in readings.items():
            if only and who not in only.split(","):
                continue
            vals, notes = numbers(reference(seed, batches, **how), theirs)
            emit(out, cell=cell.name, seed=seed, who=who, **vals,
                 notes=notes, seconds=time.perf_counter() - t0)


def serve(cell, seeds, n_control, seconds, out):
    from benchmarks.harness import serve as runner
    devices = program.devices_for(cell)
    program.import_program()[3]()
    for i, seed in enumerate(seeds):
        t0 = time.perf_counter()
        s = runner.Session(cell, seed, seconds, False, devices, t0)
        gaps = s.reference_gaps()
        mass = s.reference_mass_above()
        emit(out, cell=cell.name, seed=seed, who="program",
             sampled_mass_above_max=float(mass.max()) if mass.size else None,
             sampled_tokens=int(mass.size),
             served_gap_max=float(gaps.max()), tokens=int(gaps.size),
             served_gap_mean=float(gaps.mean()),
             argmax_share=float((gaps == 0).mean()),
             requests=len(s.rows), e2e=s.e2e,
             seconds=time.perf_counter() - t0)
        if i < n_control:
            for precision in ("bfloat16", "fp8"):
                low = s.reference_gaps(tokens_of=precision)
                emit(out, cell=cell.name, seed=seed, who=precision,
                     served_gap_max=float(low.max()),
                     served_gap_mean=float(low.mean()),
                     argmax_share=float((low == 0).mean()))
        del s
        gc.collect()


if __name__ == "__main__":
    kind, name, seeds, n_control = sys.argv[1:5]
    cell = spec.load_cell(name)
    seeds = [int(s) for s in seeds.split(",")]
    os.makedirs(os.path.join(ROOT, "chiprun_out", "limits"), exist_ok=True)
    with open(os.path.join(ROOT, "chiprun_out", "limits",
                           name + ".jsonl"), "a") as out:
        if kind in ("train", "train_reference"):
            train(cell, seeds, int(n_control), out, kind == "train")
        else:
            serve(cell, seeds, int(n_control), float(sys.argv[5]), out)
