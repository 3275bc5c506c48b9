"""The one sweep that finds an open-loop cell's knee on the chip: the
highest rate at which the backlog does not grow through the window.

    python3 tests/benchmark/knee_sweep.py <cell> <seconds> <rate,rate,...>

One server, one seed; each rate gets the cell's own mix with only
``rate_per_s`` changed, a fill and a window, and the server is drained
in between.  ``PERF.md`` records the table this prints; the cell's file
then fixes the rate at about four fifths of the knee.
"""

import json
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

import numpy as np  # noqa: E402

from benchmarks.harness import program, serve, spec, traffic, weights  # noqa: E402


def main():
    name, seconds, rates = sys.argv[1], float(sys.argv[2]), sys.argv[3]
    cell = spec.load_cell(name)
    _, models, serving, enable_compile_cache = program.import_program()
    devices = program.devices_for(cell)
    enable_compile_cache()
    import jax
    import jax.numpy as jnp
    sizes, seed, ref = cell.config, 4242, cell.reference()
    vocab = ref.vocab(sizes)
    params = weights.make_params(
        ref.param_table(sizes), seed, jnp.bfloat16, ref.weight_std(sizes),
        jax.sharding.SingleDeviceSharding(devices[0]))
    server = serve.build_server(cell, models, serving, params)
    serve.warm_up(server, serving, cell.traffic, vocab, seed)
    for rate in [float(r) for r in rates.split(",")]:
        mix = dict(cell.traffic, rate_per_s=rate)
        n = traffic.planned_count(mix, seconds, serve.DRAIN_LIMIT_S)
        plan = traffic.plan_requests(mix, seed, n, vocab)
        run = serve.drive(server, serving, plan, mix, seconds, False)
        e2e = serve.end_to_end(run, seconds)
        m = run["marks"]
        steps = [s for s in run["steps"]
                 if run["t_open"] <= s[0] < run["t_close"]]
        row = {"rate_per_s": rate, "waiting_at_open": m["open"]["waiting"],
               "waiting_at_close": m["close"]["waiting"],
               "drain_s": run["drain_s"],
               "step_ms_p50": 1e3 * float(np.median(
                   [s[1] - s[0] for s in steps])), **e2e}
        print(json.dumps(row), flush=True)
        while server.has_work:          # leave nothing for the next rate
            server.step()
    server.close()


if __name__ == "__main__":
    main()
