"""The training runner: drives the program's jitted step for a window.

Set-up builds one object, the compiled step with its state, takes its
first three steps through the window's own call and feed, and hands
that same object to the window.  What those three steps produced is
what ``correct`` compares, after the window has closed and the
program's state is freed.
"""

import gc
import sys
import time

import numpy as np

from benchmarks.harness import compare, program, readers, traffic, weights
from benchmarks.harness.trace import SubWindow

CHECK_STEPS = 3


def _log(*a):
    print(*a, file=sys.stderr, flush=True)


class Trainer:
    """The program's jitted step with what feeds it: built once, and
    given fresh state for a seed as often as asked (the limit readings
    take a dozen seeds in one process)."""

    def __init__(self, cell, devices):
        import jax
        import jax.numpy as jnp
        from jax.sharding import (Mesh, NamedSharding, PartitionSpec as P,
                                  SingleDeviceSharding)
        chip_smoke, models, _, _ = program.import_program()
        self.cell, self.devices = cell, devices
        self.sizes, self.mix = cell.config, cell.traffic
        self.ref = cell.reference()
        self.hyper = self.sizes["optimizer"]
        self.batch = self.mix["batch_per_chip"] * cell.chips
        self.seq = self.mix["seq"]
        if cell.chips > 1:
            mesh = Mesh(np.array(devices), ("data",))
            self.repl = NamedSharding(mesh, P())
            self.split = NamedSharding(mesh, P("data"))
        else:
            mesh = None
            self.repl = self.split = SingleDeviceSharding(devices[0])
        _, self.optimizer, self.step, _ = chip_smoke.build_trainer(
            cell.model_config(models), mesh, lr=self.hyper["lr"])
        self.table = self.ref.param_table(self.sizes)
        self._delta = jax.jit(lambda a, b: {
            "/".join(k.key for k in path): jnp.sqrt(jnp.sum(jnp.square(x)))
            for path, x in jax.tree_util.tree_leaves_with_path(
                jax.tree.map(jnp.subtract, a, b))})

    def fresh_params(self, seed, sharding):
        import jax.numpy as jnp
        return weights.make_params(self.table, seed, jnp.float32,
                                   self.ref.weight_std(self.sizes), sharding)

    def feed(self, seed):
        return traffic.packed_batches(self.mix, seed, self.batch, self.seq,
                                      self.ref.vocab(self.sizes))

    def put(self, feed):
        """The next batch, built on the host and waited for on the
        device: ``(host rows, device rows, seconds)``."""
        import jax
        t0 = time.perf_counter()
        host = next(feed)
        dev = jax.block_until_ready(jax.device_put(host, self.split))
        return host, dev, time.perf_counter() - t0

    def first_steps(self, seed, feed, fault=None):
        """Fresh state from the seed, then the first steps through the
        window's own call and feed.  Returns the state, the last loss,
        the batches, and what ``correct`` compares.  ``fault`` (tests
        and limit readings only) breaks the step underneath."""
        import jax
        params = self.fresh_params(seed, self.repl)
        opt_state = jax.device_put(self.optimizer.init(params), self.repl)
        step = self.step if fault is None else fault(self.step)
        batches, losses, grad_norms = [], [], None
        for i in range(CHECK_STEPS):
            host, ids, _ = self.put(feed)
            batches.append(host)
            params, opt_state, loss = step(params, opt_state, ids)
            losses.append(loss)
            if i == 0:
                grad_norms = program.adam_moment_norms(
                    opt_state, 1.0 / (1.0 - self.hyper["betas"][0]))
        p0 = self.fresh_params(seed, self.repl)
        delta_norms = self._delta(params, p0)
        del p0
        mine = {"losses": [float(x) for x in losses],
                "grad_norms": jax.device_get(grad_norms),
                "delta_norms": jax.device_get(delta_norms)}
        return params, opt_state, loss, batches, mine

    def reference(self, seed, batches, precision="float32", keep_rows=None):
        return reference_readings(
            self.ref, self.table, self.sizes, self.hyper, seed, batches,
            self.mix["reference_rows"], precision, keep_rows)


def run(cell, seed, seconds, traced, t_start, require_chip=True):
    import jax

    clock = time.perf_counter
    marks = [("start", t_start)]
    _, _, _, enable_compile_cache = program.import_program()
    marks.append(("imports", clock()))
    devices = program.devices_for(cell, require_chip)
    cache_dir = enable_compile_cache()
    marks.append(("devices", clock()))
    trainer = Trainer(cell, devices)
    sizes, mix = trainer.sizes, trainer.mix
    batch, seq, step = trainer.batch, trainer.seq, trainer.step
    feed = trainer.feed(seed)
    marks.append(("built", clock()))

    def put():
        return trainer.put(feed)

    # -- the first steps: compile, warm up, and what correct compares ----
    params, opt_state, loss, first_batches, mine = trainer.first_steps(
        seed, feed)
    marks.append(("first_steps", clock()))
    _log(f"train: first losses {mine['losses']} loss_scale "
         f"{float(trainer.optimizer.loss_scale(opt_state))} cache "
         f"{cache_dir}")

    # -- the window ------------------------------------------------------------
    tr = mix["trace"]
    trace_at = tr["start_fraction"] * seconds
    sub = SubWindow()
    _, ids, _ = put()
    jax.block_until_ready(loss)
    t_open = clock()
    setup_s = t_open - t_start
    steps, waits, prev = 0, [], loss
    while True:
        now = clock()
        if traced and not sub.started and now - t_open >= trace_at:
            sub.start()
        if sub.open and clock() - sub.opened_at >= tr["seconds"]:
            sub.stop()
        if clock() - t_open >= seconds:
            break
        params, opt_state, loss = step(params, opt_state, ids)
        steps += 1
        with jax.profiler.TraceAnnotation("bench_feed"):
            _, ids, waited = put()
        waits.append(waited)
        # stay one step ahead of the device, as a loop that logs its
        # loss does, and no further
        with jax.profiler.TraceAnnotation("bench_wait_step"):
            jax.block_until_ready(prev)
        prev = loss
    if sub.open:                 # a window shorter than the trace asked
        sub.stop()
    jax.block_until_ready((params, loss))
    t_close = clock()
    window_s = t_close - t_open
    last_loss = float(loss)
    tokens_per_s = steps * batch * seq / window_s
    marks.append(("open", t_open))
    _log(f"train: {steps} steps in {window_s:.3f}s, {tokens_per_s:.1f} "
         f"tokens/s, last loss {last_loss:.4f}, setup {setup_s:.2f}s = "
         + ", ".join(f"{b[0]} {b[1] - a[1]:.2f}"
                     for a, b in zip(marks, marks[1:])))

    values = {"train_tokens_per_s": tokens_per_s, "setup_s": setup_s}
    device = {"platform": devices[0].platform,
              "kind": devices[0].device_kind, "count": len(devices),
              "memory_peak_bytes": program.memory_peak_bytes(devices)}
    failed = 0 if np.isfinite(last_loss) else steps

    # -- free the program's state, then the reference ----------------------
    del params, opt_state, ids, loss, prev, step
    gc.collect()
    t0 = clock()
    theirs = trainer.reference(seed, first_batches)
    _log(f"train: reference took {clock() - t0:.2f}s")
    compared, notes = compare.compare_training(
        mine, theirs, compare.limits_for(cell, "train"))

    breakdown = None
    if traced:
        t0 = clock()
        trace = sub.reduce()
        ctx = {"trace": trace, "cell": cell, "sizes": sizes, "mix": mix,
               "ref": trainer.ref,
               "chips": cell.chips, "device_kind": devices[0].device_kind,
               "tokens_per_step": batch * seq, "batch_per_chip":
               mix["batch_per_chip"], "seq": seq,
               "spans": {"input_wait_s": waits}}
        breakdown = readers.read_all(cell, ctx, values, device)
        _log(f"train: trace reduced in {clock() - t0:.2f}s; programs "
             f"{ {k: len(v) for k, v in trace.programs().items()} }")
    return {"correct": compare.verdict(compared), "attempted": steps,
            "failed": failed, "values": values, "device": device,
            "compared": compared, "notes": notes, "breakdown": breakdown}


def reference_readings(ref, table, sizes, hyper, seed, batches, rows,
                       precision="float32", keep_rows=None):
    """The plain reference's losses, first gradient and change of the
    parameters over the same first steps, on one device.  ``keep_rows``
    plants the half-batch fault for the tests and the limit readings."""
    import jax
    import jax.numpy as jnp
    p0 = ref.stacked(weights.make_params(
        table, seed, jnp.float32, ref.weight_std(sizes)), sizes)
    if keep_rows is not None:
        batches = [b[:keep_rows] for b in batches]
        rows = min(rows, keep_rows)
    losses, grad, p3 = ref.train_steps(
        p0, [jnp.asarray(b) for b in batches], sizes, hyper, precision,
        rows_per_block=rows)
    out = {"losses": [float(x) for x in losses],
           "grad_norms": jax.device_get(
               ref.unstacked_leaf_norms(grad, sizes)),
           "delta_norms": jax.device_get(ref.unstacked_leaf_norms(
               jax.tree.map(jnp.subtract, p3, p0), sizes))}
    return out
