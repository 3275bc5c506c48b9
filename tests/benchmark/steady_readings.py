"""How steady an open-loop cell's numbers are from seed to seed, and
where its knee lies: many windows through ONE server in one process, so
that a reading costs a window and not a set-up.

    python3 tests/benchmark/steady_readings.py <cell> <out.jsonl> <phase> ...

A phase is one of

    at:<seconds>:<rate|mix>:<seed,seed,...>    one window a seed at that rate
    sweep:<seconds>:<seed>:<rate,rate,...>     one window a rate; finds the knee
    knee:<seconds>:<share>:<seed,seed,...>     windows at <share> of that knee

Every window gets the cell's own mix with only ``rate_per_s`` changed
(``mix``: not even that), its own plan from its seed, a fill and a
window, and the server is drained in between.  The weights are one
seed's for the whole process; a run of the benchmark draws them from
its own seed, so this is how the *traffic* of a seed moves the numbers.
The knee is the highest swept rate, all lower ones with it, at which no
more than two requests wait when the window closes and the 95th
percentile of the time to a first token stays under a second.  Each
window prints one JSON row; ``PERF.md`` records what they said.
"""

import json
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

import numpy as np  # noqa: E402

from benchmarks.harness import program, serve, spec, traffic, weights  # noqa: E402

WEIGHTS_SEED = 4242


def window_row(server, serving, cell, vocab, rate, seed, seconds):
    mix = cell.traffic if rate is None else dict(cell.traffic,
                                                 rate_per_s=rate)
    n = traffic.planned_count(mix, seconds, serve.DRAIN_LIMIT_S)
    plan = traffic.plan_requests(mix, seed, n, vocab)
    run = serve.drive(server, serving, plan, mix, seconds, False)
    e2e = serve.end_to_end(run, seconds)
    t_open, t_close, m = run["t_open"], run["t_close"], run["marks"]
    mine = [s for s in run["sent"] if t_open <= s.due < t_close]
    gaps = 1e3 * np.concatenate([np.diff(s.stamps) for s in mine
                                 if s.ok and len(s.stamps) > 1] or [[]])
    steps = [s for s in run["steps"] if t_open <= s[0] < t_close]
    fam0, fam1 = m["open"]["families"], m["close"]["families"]
    row = {"rate_per_s": mix["rate_per_s"], "seed": seed,
           "seconds": seconds, **e2e,
           "offered_tokens_per_s": sum(s.planned.max_new for s in mine)
           / seconds,
           "waiting_at_open": m["open"]["waiting"],
           "waiting_at_close": m["close"]["waiting"],
           "drain_s": run["drain_s"], "gaps": int(gaps.size),
           "steps": len(steps),
           "step_ms_p50": 1e3 * float(np.median(
               [s[1] - s[0] for s in steps])),
           "launches": {k.split("[")[0]: v[0] - fam0.get(k, (0, 0))[0]
                        for k, v in fam1.items()},
           "accepted": m["close"]["accepted"] - m["open"]["accepted"]}
    if gaps.size:
        row["itl_mean_ms"] = float(gaps.mean())
        row["itl_pcts_ms"] = {str(q): serve.percentile(gaps, q) for q in (
            5, 25, 50, 75, 85, 90, 92, 94, 95, 96, 97, 98, 99)}
    while server.has_work:              # leave nothing for the next window
        server.step()
    return row


def knee_of(rows):
    knee = None
    for r in sorted(rows, key=lambda r: r["rate_per_s"]):
        if r["waiting_at_close"] > 2 or r.get("ttft_p95_ms", 1e9) >= 1e3:
            break
        knee = r["rate_per_s"]
    return knee


def main():
    name, out_path, phases = sys.argv[1], sys.argv[2], sys.argv[3:]
    cell = spec.load_cell(name)
    _, models, serving, enable_compile_cache = program.import_program()
    devices = program.devices_for(cell)
    enable_compile_cache()
    import jax
    import jax.numpy as jnp
    sizes, ref = cell.config, cell.reference()
    vocab = ref.vocab(sizes)
    params = weights.make_params(
        ref.param_table(sizes), WEIGHTS_SEED, jnp.bfloat16,
        ref.weight_std(sizes),
        jax.sharding.SingleDeviceSharding(devices[0]))
    server = serve.build_server(cell, models, serving, params)
    serve.warm_up(server, serving, cell.traffic, vocab, WEIGHTS_SEED)
    os.makedirs(os.path.dirname(os.path.abspath(out_path)), exist_ok=True)
    knee = None
    with open(out_path, "a") as out:
        def window(phase, rate, seed, seconds):
            row = {"phase": phase, **window_row(
                server, serving, cell, vocab, rate, seed, seconds)}
            line = json.dumps(row)
            print(line, flush=True)
            out.write(line + "\n")
            out.flush()
            return row

        for phase in phases:
            kind, seconds, a, b = phase.split(":")
            seconds = float(seconds)
            if kind == "at":
                rate = None if a == "mix" else float(a)
                for seed in b.split(","):
                    window(phase, rate, int(seed), seconds)
            elif kind == "sweep":
                rows = []
                for r in b.split(","):      # ascending; past the knee
                    rows.append(window(phase, float(r), int(a), seconds))
                    if knee_of(rows) != rows[-1]["rate_per_s"]:
                        break               # the drains only get longer
                knee = knee_of(rows)
                print(json.dumps({"phase": phase, "knee": knee}), flush=True)
            elif kind == "knee":
                if knee is None:
                    raise SystemExit("no swept rate was sustained")
                rate = round(float(a) * knee, 1)
                for seed in b.split(","):
                    window(phase, rate, int(seed), seconds)
            else:
                raise SystemExit(f"unknown phase {phase!r}")
    server.close()


if __name__ == "__main__":
    main()
