"""The readings ``decided_margin`` and the served limit of a
configuration with routed experts are set from, taken on the chip at a
cell's own size in one process (``PERF.md`` section 2 records them):

    python3 tests/benchmark/margin_readings.py <cell> <seed,seed,...> <n_control> <seconds> [<full_rows> <full_t>]

First, where ``full_rows`` is given, the program's full forward pass in
the cell's precision over ``full_rows`` rows of ``full_t`` random ids,
beside the float32 reference: at every position the reference's
routing margin in every expert layer, whether the program chose other
experts there, and how far the program's first token lies below the
reference's best.  Then, for every seed, the cell's own server through
one window (``harness/serve.py::Session``) and, for every served token
of its greedy sample, the gap and the position's least margin, with
``decided_margin`` left out so that nothing is masked; for the first
``n_control`` seeds also the gap of the token the fp8 control puts
first.  One JSON line per row of ids goes to
``chiprun_out/margins/<cell>.jsonl``; the arrays are per position.
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
from benchmarks.reference import deepseek_v3 as family  # noqa: E402


def emit(out, **row):
    out.write(json.dumps(row) + "\n")
    out.flush()
    print({k: v for k, v in row.items() if not isinstance(v, list)},
          flush=True)


def _gap_of(lg, *tokens):
    import jax.numpy as jnp
    best = jnp.max(lg, -1)
    return tuple(best - jnp.take_along_axis(lg, t[..., None], -1)[..., 0]
                 for t in tokens)


def judged(sizes):
    """``(params, ids, *tokens) -> (gaps of each tokens, least margin)``
    by the float32 reference, nothing masked."""
    import jax
    return jax.jit(lambda p, ids, *tokens: family._by_position(
        p, ids, sizes, "float32", _gap_of, *tokens))


def first_of(ref, sizes, precision):
    import jax
    return jax.jit(lambda p, ids: ref.token_gaps(p, ids, None, sizes,
                                                 precision)[2])


def full_pass(cell, devices, seed, rows, t, out):
    """The program's full forward pass against the reference."""
    import jax
    import jax.numpy as jnp
    from benchmarks.harness import weights
    _, models, _, _ = program.import_program()
    sizes = dict(cell.config, decided_margin=None)
    ref = cell.reference()
    params = weights.make_params(
        ref.param_table(sizes), seed, jnp.bfloat16, ref.weight_std(sizes),
        jax.sharding.SingleDeviceSharding(devices[0]))
    model = cell.model_config(models).build_model()
    layers = [i for i in range(sizes["num_hidden_layers"])
              if i >= sizes["first_k_dense_replace"]]

    @jax.jit
    def mine(p, ids):
        logits, state = model.apply({"params": p}, ids,
                                    mutable=["intermediates"])
        chosen = jnp.stack([state["intermediates"][f"block_{i}"]["moe"]
                            ["chosen"][0] for i in layers])
        return jnp.argmax(logits[:, :-1], -1), chosen

    @jax.jit
    def theirs(p, ids, first):
        """One float32 pass: the gap of ``first``, every layer's choices
        and margins."""
        choices, margins = [], []
        x = family.hidden(p, ids, sizes, "float32", choices, margins)
        lg = family._mm("bth,hv->btv", x[:, :-1],
                        p["lm_head"].astype(jnp.float32),
                        family._rounder("float32"))
        return (_gap_of(lg, first)[0], jnp.stack(choices),
                jnp.stack(margins))

    rng = np.random.default_rng([seed, 11])
    for r in range(rows):
        t0 = time.perf_counter()
        ids = jnp.asarray(rng.integers(0, ref.vocab(sizes), (1, t)),
                          jnp.int32)
        first, chosen = mine(params, ids)
        gap, want, margins = theirs(params, ids, first)
        other = (np.sort(np.asarray(chosen).reshape(np.asarray(want).shape),
                         -1) != np.sort(np.asarray(want), -1)).any(-1)
        emit(out, cell=cell.name, seed=seed, who="full_pass", row=r, t=t,
             gap=np.asarray(gap)[0].tolist(),
             margins=np.asarray(margins)[:, 0].tolist(),
             other=other[:, 0].astype(int).tolist(),
             gap_max=float(np.max(gap)),
             seconds=time.perf_counter() - t0)
    del params
    gc.collect()


def serve(cell, devices, seeds, n_control, seconds, out):
    from benchmarks.harness import serve as runner
    cell.config.pop("decided_margin", None)
    sizes, ref = cell.config, cell.reference()
    judge = judged(sizes)
    firsts = {p: first_of(ref, sizes, p) for p in ("fp8",)}
    width = ref.longest_row(sizes)
    for i, seed in enumerate(seeds):
        t0 = time.perf_counter()
        s = runner.Session(cell, seed, seconds, False, devices, t0)
        params = ref.stacked(s.fresh_params(), sizes)
        for r, (block, ids) in enumerate(runner._id_blocks(s.rows, 1,
                                                           width)):
            tokens = {"program": ids[:, 1:]}
            if i < n_control:
                tokens.update((p, f(params, ids))
                              for p, f in firsts.items())
            gaps, least = judge(params, ids, *tokens.values())
            row = {who: runner._served(block, g)[0].tolist()
                   for who, g in zip(tokens, gaps)}
            emit(out, cell=cell.name, seed=seed, who="served", row=r,
                 prompt=len(block[0][0]), tokens=len(block[0][1]),
                 least=runner._served(block, least)[0].tolist(),
                 gap_max={k: max(v) for k, v in row.items()},
                 e2e=s.e2e, seconds=time.perf_counter() - t0, **row)
        del s, params
        gc.collect()


if __name__ == "__main__":
    name, seeds, n_control, seconds = sys.argv[1:5]
    cell = spec.load_cell(name)
    seeds = [int(s) for s in seeds.split(",")]
    program.import_program()[3]()
    devices = program.devices_for(cell)
    os.makedirs(os.path.join(ROOT, "chiprun_out", "margins"), exist_ok=True)
    with open(os.path.join(ROOT, "chiprun_out", "margins",
                           name + ".jsonl"), "a") as out:
        if len(sys.argv) > 5:
            try:
                full_pass(cell, devices, seeds[0], int(sys.argv[5]),
                          int(sys.argv[6]), out)
            except Exception as e:      # the served readings matter more
                print("full_pass failed:", repr(e)[:2000], flush=True)
                gc.collect()
        serve(cell, devices, seeds, int(n_control), float(seconds), out)
