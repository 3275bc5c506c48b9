"""The serving runner: one process, one thread, the server as users
run it (``submit``, ``step``, ``stream``).

The generator and the server share the thread: a request that falls
due while a step runs is sent when the step returns, and its latency is
counted from the moment it was due, so the wait shows.  How late the
generator ran is reported beside the latencies.

Times: the generator starts ``fill_s`` before the window opens, so that
the batch is at its steady state when it does; the window is
``--seconds`` long; after it the runner keeps the load up until every
request of the window has ended (open loop), or lets the requests in
flight end and cancels those not begun (closed loop).

A traced run's profiler blocks this one thread when it stops (4 to 11 s
at GPT-2 XL's size), and every request due in that gap would be sent
seconds late.  So a mix whose cell reports host-clock latencies puts
the traced sub-window at the window's end (``"ends_with_window"``): the
stop then falls after the close, and a traced run's latencies are taken
over the requests due before the sub-window opened.
"""

import dataclasses
import gc
import sys
import time

import numpy as np

from benchmarks.harness import compare, program, readers, traffic, weights
from benchmarks.harness.trace import SubWindow

OK_REASONS = ("length", "eos")
DRAIN_LIMIT_S = 60.0


def _log(*a):
    print(*a, file=sys.stderr, flush=True)


@dataclasses.dataclass
class Sent:
    planned: traffic.Planned
    req: object
    due: float
    sent_at: float
    stamps: list = dataclasses.field(default_factory=list)
    reason: str = None
    cancelled: bool = False

    def on_event(self, kind, value):
        if kind == "token":
            self.stamps.append(time.perf_counter())
        else:
            self.reason = value

    @property
    def ended(self):
        return self.reason is not None

    @property
    def ok(self):
        return self.reason in OK_REASONS


def _sampling(serving, mix, planned):
    if planned.greedy:
        return None
    from apex_tpu.serving.scheduler import SamplingParams
    s = mix["sampling"]
    return SamplingParams(temperature=s["temperature"], top_p=s["top_p"],
                          seed=planned.sample_seed)


def warm_up(server, serving, mix, vocab, seed):
    """Launch every program family the mix can reach, so that nothing
    compiles later.  Greedy: a prompt of random ids longer than one
    chunk with two tokens to make (chunk, then the plain decode: a draft
    never covers a last token), then a periodic prompt whose n-gram
    drafts bring the verify program in.  Where the mix samples, the same
    with a sampled request, and the periodic greedy prompt once more
    beside a sampled one: a sampled token breaks every n-gram, so the
    sampling verify program runs only for such a mixed batch."""
    rng = np.random.default_rng([int(seed), 3])
    chunk = server.prefill_chunk or 256
    long = min(chunk + 44, server.engine.max_context - 4)
    samples = mix.get("greedy_share", 1.0) < 1.0

    def planned(prompt, new, greedy):
        return traffic.Planned(-1, 0.0, prompt, new, greedy,
                               int(rng.integers(0, 2 ** 31 - 1)))

    def random_ids(n):
        return rng.integers(0, vocab, n).tolist()

    periodic = random_ids(4) * 12
    waves = [[planned(random_ids(long), 2, True)],
             [planned(periodic, 12, True)]]
    if samples:
        waves += [[planned(random_ids(long), 2, False)],
                  [planned(periodic, 12, True),
                   planned(random_ids(40), 12, False)]]
    for wave in waves:
        reqs = [server.submit(p.prompt, p.max_new,
                              sampling=_sampling(serving, mix, p))
                for p in wave]
        while server.has_work:
            server.step()
        bad = [r.finish_reason for r in reqs
               if r.finish_reason not in OK_REASONS]
        if bad:
            raise SystemExit(f"warm-up requests ended {bad}")
    want = {"chunk_prefill_sampled", "decode_sampled", "verify_sampled"}
    if samples:
        want |= {"chunk_prefill_stoch", "decode_stoch", "verify_stoch"}
    missing = want - {k.split("[")[0] for k in _families(server)}
    if missing:
        raise SystemExit(f"warm-up did not reach the programs {missing}")


def _families(server):
    return {k: (v["calls"], v["compiles"]) for k, v in
            server.stats()["programs"]["by_program"].items()}


def drive(server, serving, plan, mix, seconds, traced):
    """The generator and the server loop.  Returns everything the
    metrics read: the requests sent, the steps taken, the window."""
    import jax
    clock = time.perf_counter
    open_loop = mix["loop"] == "open"
    clients = mix.get("clients")
    spec, alloc, sched = server.spec, server.engine.allocator, \
        server.scheduler
    tr = mix["trace"]

    sent, steps = [], []
    next_i = 0
    sub, sub_marks = SubWindow(), {}
    marks = {}
    t_gen0 = clock()
    t_open = t_gen0 + mix["fill_s"]
    t_close = t_open + seconds
    t_trace = (t_close - tr["seconds"] if tr.get("ends_with_window")
               else t_open + tr["start_fraction"] * seconds)
    closing_since = None
    stalls = {}

    def send(planned, due):
        with jax.profiler.TraceAnnotation("bench_submit"):
            req = server.submit(planned.prompt, planned.max_new,
                                sampling=_sampling(serving, mix, planned))
            s = Sent(planned, req, due, clock())
            if req.finished:                 # turned away at the door
                s.reason = req.finish_reason
            else:
                server.stream(req, callback=s.on_event)
        sent.append(s)

    def snapshot():
        return {"drafted": spec.count("drafted_tokens"),
                "accepted": spec.count("accepted_tokens"),
                "families": _families(server), "at": clock(),
                "waiting": sched.num_waiting,
                "prefix_hit_tokens": server.prefix.count(
                    "prefix_hit_tokens"),
                "prefix_miss_tokens": server.prefix.count(
                    "prefix_miss_tokens"),
                "cached": [s.req.num_cached for s in sent]}

    while True:
        now = clock()
        if "open" not in marks and now >= t_open:
            marks["open"] = snapshot()
        if "close" not in marks and now >= t_close:
            marks["close"] = snapshot()
            closing_since = now
            if not open_loop:
                for s in sent:
                    if not s.ended and not s.stamps:
                        s.cancelled = server.cancel(s.req.uid)

        # -- the generator --------------------------------------------------
        if open_loop:
            while next_i < len(plan) and t_gen0 + plan[next_i].due <= now:
                send(plan[next_i], t_gen0 + plan[next_i].due)
                next_i += 1
        elif closing_since is None:
            in_flight = sum(not s.ended for s in sent)
            while in_flight < clients and next_i < len(plan):
                send(plan[next_i], now)
                next_i += 1
                in_flight += 1

        # -- the traced sub-window ------------------------------------------
        if traced and not sub.started and now >= t_trace:
            sub.start()
            sub_marks["open"] = snapshot()
            stalls["start_trace_s"] = sub_marks["open"]["at"] - now
        if sub.open and now >= sub_marks["open"]["at"] + tr["seconds"]:
            sub_marks["close"] = snapshot()
            sub.stop()
            stalls["stop_trace_s"] = clock() - sub_marks["close"]["at"]
            stalls["stop_after_close_s"] = sub_marks["close"]["at"] - t_close

        # -- the server -----------------------------------------------------------
        if server.has_work:
            d0 = spec.count("decode_steps")
            v0 = spec.count("verify_steps")
            t0 = clock()
            with jax.profiler.TraceAnnotation("bench_step"):
                produced = server.step()
            t1 = clock()
            kind = ("decode" if spec.count("decode_steps") > d0 else
                    "verify" if spec.count("verify_steps") > v0 else "none")
            live = sum(r.num_cached for r in sched.running.values()
                       if not r.prefilling) if kind == "decode" else 0
            steps.append((t0, t1, produced, kind, live, alloc.num_free))
        else:
            wake = (t_gen0 + plan[next_i].due if open_loop
                    and next_i < len(plan) else now + 0.001)
            with jax.profiler.TraceAnnotation("bench_idle"):
                time.sleep(max(0.0, min(wake - clock(), 0.001)))

        # -- the end --------------------------------------------------------------
        if closing_since is not None and not sub.open:
            waiting = [s for s in sent if not s.ended and (
                t_open <= s.due < t_close if open_loop else True)]
            if not waiting or clock() - closing_since > DRAIN_LIMIT_S:
                break
    return {"sent": sent, "steps": steps, "t_open": t_open,
            "t_close": t_close, "marks": marks, "sub": sub_marks,
            "window": sub, "stalls": stalls,
            "slots": mix["server"]["max_batch_size"],
            "drain_s": clock() - t_close, "open_loop": open_loop}


def percentile(xs, q):
    """The q-th percentile as the smallest value with at least q% of
    the sample at or below it."""
    xs = np.sort(np.asarray(xs, np.float64))
    return float(xs[min(len(xs) - 1, int(np.ceil(q / 100.0 * len(xs))) - 1)])


def end_to_end(run, seconds, latencies_until=None):
    """The window's numbers as a client sees them, over all the
    requests and all the time of the window.  ``latencies_until``: a
    traced run takes its latencies over the requests due before that
    moment, the opening of its sub-window, since the profiler's own
    stalls lie after it.

    A closed loop's request that had no token when the window closed is
    cancelled there.  It counts as attempted, and as failed where it
    had by then waited longer than one full turn of the slots (the
    slots over the window's rate of completion): a request the
    scheduler starves shows."""
    t_open, t_close = run["t_open"], run["t_close"]
    sent = run["sent"]
    mine = [s for s in sent if t_open <= s.due < t_close or s.cancelled]
    done = sum(1 for s in sent if s.ok and s.stamps
               and t_open <= s.stamps[-1] < t_close)
    turn_s = run.get("slots", 1) * seconds / max(done, 1)
    cut_short = {id(s) for s in mine if s.cancelled
                 and t_close - s.due <= turn_s}
    failed = [s for s in mine if not s.ok and id(s) not in cut_short]
    out = {"attempted": len(mine), "failed": len(failed)}
    delivered = sum(1 for s in sent if s.ok
                    for t in s.stamps if t_open <= t < t_close)
    out["serve_tokens_per_s"] = delivered / seconds
    until = t_close if latencies_until is None else latencies_until
    timed = [s for s in mine if s.due < until and id(s) not in cut_short]
    if timed:
        # a request that failed, or never got a token, waited longest
        worst = max([s.stamps[0] - s.due for s in timed if s.stamps]
                    + [t_close - t_open])
        ttft = [s.stamps[0] - s.due if s.ok and s.stamps else worst
                for s in timed]
        out["ttft_p95_ms"] = 1e3 * percentile(ttft, 95)
        out["ttft_p50_ms"] = 1e3 * percentile(ttft, 50)
        gaps = np.concatenate([np.diff(s.stamps) for s in timed
                               if s.ok and len(s.stamps) > 1] or [[]])
        if gaps.size:
            out["itl_p95_ms"] = 1e3 * percentile(gaps, 95)
            out["itl_p50_ms"] = 1e3 * percentile(gaps, 50)
        late = [s.sent_at - s.due for s in timed]
        out["gen_late_p95_ms"] = 1e3 * percentile(late, 95)
    return out


def build_server(cell, models, serving, params):
    """The server as users run it: the slots and context the mix names,
    every other argument at its default (prefix cache, chunked prefill,
    speculation, the pipelined loop, overload control, streaming)."""
    srv = cell.traffic["server"]
    return serving.InferenceServer(
        cell.model_config(models), params,
        max_batch_size=srv["max_batch_size"],
        max_context=srv["max_context"])


class Session:
    """One server through one run: built, warmed up, driven for the
    window, sampled for the output check, closed and freed.  What is
    left is host data: the numbers, the sample, what the readers read."""

    def __init__(self, cell, seed, seconds, traced, devices, t_start):
        _, models, serving, _ = program.import_program()
        self.cell, self.seed, self.devices = cell, seed, devices
        self.sizes, self.mix = sizes, mix = cell.config, cell.traffic
        self.ref = cell.reference()
        self.table = self.ref.param_table(sizes)
        clock = time.perf_counter
        marks = [("start", t_start), ("imports", clock())]

        params = self.fresh_params()
        server = build_server(cell, models, serving, params)
        marks.append(("server", clock()))
        n = traffic.planned_count(mix, seconds, DRAIN_LIMIT_S)
        vocab = self.ref.vocab(sizes)
        plan = traffic.plan_requests(mix, seed, n, vocab)
        warm_up(server, serving, mix, vocab, seed)
        marks.append(("warm_up", clock()))
        warm = _families(server)
        _log(f"serve: warm programs {warm} planned {n} requests")

        run_ = drive(server, serving, plan, mix, seconds, traced)
        marks.append(("fill", run_["t_open"]))
        self.setup_s = run_["t_open"] - t_start
        # a traced run's latencies: of the requests due before the
        # profiler opened, whose stalls they would otherwise read
        until = run_["sub"]["open"]["at"] if traced else None
        self.e2e = e2e = end_to_end(run_, seconds, until)
        m = run_["marks"]
        # nothing may compile once the warm-up is over: not in the
        # window, and not in the fill, which has to reach a steady state
        compiled = {k: v[1] - warm.get(k, (0, 0))[1]
                    for k, v in m["close"]["families"].items()}
        compiled = {k: v for k, v in compiled.items() if v}
        st = server.stats()

        def grew(counter):
            return m["close"][counter] - m["open"][counter]

        _log(f"serve: window {seconds}s attempted {e2e['attempted']} "
             f"failed {e2e['failed']} drain {run_['drain_s']:.2f}s setup "
             f"{self.setup_s:.2f}s = " + ", ".join(
                 f"{y[0]} {y[1] - x[1]:.2f}"
                 for x, y in zip(marks, marks[1:])) + f"; {e2e}")
        _log(f"serve: compiled after the warm-up: {compiled or 'nothing'}; "
             f"speculation drafted {grew('drafted')} accepted "
             f"{grew('accepted')}; prompt tokens found in the prefix "
             f"cache {grew('prefix_hit_tokens')} and not "
             f"{grew('prefix_miss_tokens')}; "
             f"preemptions {st['preemptions']} failed "
             f"{st['requests_failed']} oom {st['oom_events']}; waiting at "
             f"open {m['open']['waiting']} at close "
             f"{m['close']['waiting']}; steps "
             f"{len(run_['steps'])}; programs {_families(server)}; "
             f"profiler stalls {run_['stalls'] or 'none'}")
        if compiled:
            raise SystemExit("programs compiled after the warm-up: "
                             f"{compiled}")
        self.device = {
            "platform": devices[0].platform, "kind": devices[0].device_kind,
            "count": len(devices),
            "memory_peak_bytes": program.memory_peak_bytes(devices)}

        # the sample that correct compares: finished greedy requests of
        # the window, drawn from the seed, the longest among them; and
        # a sample of the sampled ones, read for the nucleus they must
        # have been drawn from
        t_open, t_close = run_["t_open"], run_["t_close"]
        done = [s for s in run_["sent"] if s.ok and s.stamps
                and s.stamps[-1] >= t_open]
        check = mix["check"]
        sample = pick_sample([s for s in done if s.planned.greedy],
                             check["requests"], seed)
        sample += pick_sample([s for s in done if not s.planned.greedy],
                              check.get("sampled_requests", 0), seed)
        self.rows, self.sampled_rows = ([
            (list(s.planned.prompt), list(s.req.generated))
            for s in sample if s.planned.greedy is g] for g in (True, False))
        self.ctx = {
            "cell": cell, "sizes": sizes, "mix": mix, "ref": self.ref,
            "chips": cell.chips,
            "device_kind": devices[0].device_kind, "run": run_, "e2e": e2e,
            "num_blocks": server.engine.cache_cfg.num_blocks,
            "queue_waits": [s.req.admitted_at - s.req.submitted_at
                            for s in run_["sent"]
                            if t_open <= s.due < (until or t_close)
                            and s.req.admitted_at is not None]}
        self.run = run_
        server.close()
        del run_["sent"]
        del server, params, sample, done, plan
        gc.collect()

    def fresh_params(self):
        import jax
        import jax.numpy as jnp
        return weights.make_params(
            self.table, self.seed, jnp.bfloat16,
            self.ref.weight_std(self.sizes),
            jax.sharding.SingleDeviceSharding(self.devices[0]))

    def reference_gaps(self, precision="float32", tokens_of=None):
        return served_gaps(self.ref, self.fresh_params(), self.rows,
                           self.sizes, self.mix["check"]["rows_per_block"],
                           precision, tokens_of)

    def reference_mass_above(self, precision="float32"):
        """For every served token of the sampled sample: the mass the
        reference gives, at the mix's temperature, to the tokens it
        ranks above it."""
        if not self.sampled_rows or not hasattr(self.ref, "mass_above"):
            return np.zeros(0)
        return served_mass_above(
            self.ref, self.fresh_params(), self.sampled_rows, self.sizes,
            self.mix["check"]["rows_per_block"],
            self.mix["sampling"]["temperature"], precision)


def run(cell, seed, seconds, traced, t_start, require_chip=True):
    clock = time.perf_counter
    _, _, _, enable_compile_cache = program.import_program()
    t_imported = clock()
    devices = program.devices_for(cell, require_chip)
    enable_compile_cache()
    _log(f"serve: imports {t_imported - t_start:.2f}s, devices "
         f"{clock() - t_imported:.2f}s")
    s = Session(cell, seed, seconds, traced, devices, t_start)
    values = {"setup_s": s.setup_s}
    values.update({k: v for k, v in s.e2e.items()
                   if k not in ("attempted", "failed")})
    device = s.device

    t0 = clock()
    gaps = s.reference_gaps()
    mass = s.reference_mass_above()
    _log(f"serve: reference over {len(s.rows)} greedy requests, "
         f"{len(gaps)} served tokens, and {len(s.sampled_rows)} sampled "
         f"ones, {len(mass)} tokens, took {clock() - t0:.2f}s")
    compared, notes = compare.compare_served(
        gaps, compare.limits_for(cell, "serve"), mass,
        (s.mix.get("sampling") or {}).get("top_p"))

    breakdown = None
    if traced:
        t0 = clock()
        trace = s.ctx["trace"] = s.run["window"].reduce()
        breakdown = readers.read_all(cell, s.ctx, values, device)
        _log(f"serve: trace reduced in {clock() - t0:.2f}s; programs "
             f"{ {k: len(v) for k, v in trace.programs().items()} }")
    return {"correct": compare.verdict(compared),
            "attempted": s.e2e["attempted"], "failed": s.e2e["failed"],
            "values": values, "device": device, "compared": compared,
            "notes": notes, "breakdown": breakdown}


def pick_sample(done, k, seed):
    """``k`` of the finished requests ``done``, drawn from the seed, the
    longest always among them."""
    if not done or k <= 0:
        return []
    longest = max(range(len(done)), key=lambda i: len(
        done[i].planned.prompt) + len(done[i].req.generated))
    rng = np.random.default_rng([int(seed), 4])
    rest = [i for i in rng.permutation(len(done)) if i != longest]
    return [done[i] for i in [longest] + rest[:k - 1]]


def _id_blocks(rows, rows_per_block, width):
    """``rows`` ((prompt, generated) pairs) in blocks of padded ids."""
    import jax.numpy as jnp
    for a in range(0, len(rows), rows_per_block):
        block = rows[a:a + rows_per_block]
        ids = np.zeros((len(block), width), np.int32)
        for i, (prompt, gen) in enumerate(block):
            full = (prompt + gen)[:width]
            ids[i, :len(full)] = full
        yield block, jnp.asarray(ids)


def _served(block, per_position):
    """``per_position`` (rows, T-1) cut to the served tokens: logits at
    position p choose token p + 1."""
    per_position = np.asarray(per_position)
    return [per_position[i, len(prompt) - 1:len(prompt) - 1 + len(gen)]
            for i, (prompt, gen) in enumerate(block)]


def served_gaps(ref, params, rows, sizes, rows_per_block,
                precision="float32", tokens_of=None):
    """For every served token of ``rows`` ((prompt, generated) pairs):
    how far its logit lies below the best at its position, by the plain
    reference run once over prompt plus served tokens.  With
    ``tokens_of`` (a lower precision's name) the token judged at each
    served position is the one that precision puts first: the control."""
    import jax
    if not rows:
        return np.zeros(0)
    stacked = ref.stacked(params, sizes)
    del params
    gaps_fn = jax.jit(lambda p, ids: ref.token_gaps(p, ids, None, sizes,
                                                    precision))
    out = []
    for block, ids in _id_blocks(rows, rows_per_block,
                                 ref.longest_row(sizes)):
        _, gap, _ = gaps_fn(stacked, ids)
        if tokens_of is not None:
            low = jax.jit(lambda p, ids: ref.token_gaps(
                p, ids, None, sizes, tokens_of))(stacked, ids)[2]
            gap = jax.jit(lambda p, ids, t: ref.logit_at(
                p, ids, t, sizes, precision))(stacked, ids, low)
        out += _served(block, gap)
    return np.concatenate(out)


def served_mass_above(ref, params, rows, sizes, rows_per_block,
                      temperature, precision="float32"):
    """For every served token of ``rows``: the probability the plain
    reference gives, at ``temperature``, to all the tokens it ranks
    above the served one.  A nucleus sampler keeps a token exactly
    where this mass is under ``top_p``."""
    import jax
    stacked = ref.stacked(params, sizes)
    del params
    fn = jax.jit(lambda p, ids: ref.mass_above(p, ids, sizes, temperature,
                                               precision))
    out = []
    for block, ids in _id_blocks(rows, rows_per_block,
                                 ref.longest_row(sizes)):
        out += _served(block, fn(stacked, ids))
    return np.concatenate(out)
