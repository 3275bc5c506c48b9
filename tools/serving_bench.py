"""Serving throughput/latency: continuous batching vs naive decoding.

The number that justifies ``apex_tpu.serving`` existing: tokens/s of
the KV-cached, continuously-batched :class:`InferenceServer` against
the naive baseline every training-only codebase implies — one request
at a time, full causal recompute of the whole prefix for every
generated token (at a FIXED padded length, so the baseline pays one
compile, not one per step; it loses on algorithmic work, not on
tracing overhead).

Both paths run the same params, the same greedy sampling, and the same
request set, and are warmed up before the timed window, so the ratio
isolates (KV cache: O(1) per token instead of O(S) recompute) x
(batching: B sequences per device step instead of 1).

Emits one JSON line (and writes it to ``BENCH_serving.json`` at the
repo root unless ``--out`` says otherwise)::

    {"bench": "serving", "mode": "smoke"|"full",
     "tokens_s_continuous": ..., "tokens_s_naive": ..., "speedup": ...,
     "p50_latency_ms": ..., "p95_latency_ms": ...,
     "latency": {"ttft_ms": {"count", "p50", "p90", "p99", "max"},
                 "queue_wait_ms": ..., "decode_token_ms": ...,
                 "step_ms": ...},
     "config": {...}, "stats": {...}}

The ``latency`` block comes straight from the server's log-bucketed
histograms (``docs/observability.md``) — per-request TTFT /
queue-wait / per-token decode quantiles, not medians hand-computed
from completion lists (``p50_latency_ms``/``p95_latency_ms`` remain
the whole-request completion times for continuity).  In
``--shared-prefix`` mode the record additionally carries the
histogram's cached-arm TTFT p50 next to the directly-measured median
and their log-bucket distance — ``--smoke`` asserts they agree within
one bucket (the histogram estimator's guarantee, checked against live
traffic rather than synthetic samples).

``--smoke`` is the CPU-safe build-matrix mode: a toy GPT, a small
request set, and a hard floor assertion (speedup >= 2x — the
acceptance bar; on CPU the measured margin is far above it).

``--shared-prefix`` switches to the serving-perf workloads of
docs/serving.md's prefix-caching/chunked-prefill section (one JSON
record to ``BENCH_serving_prefix.json``):

- *shared-system-prompt TTFT*: every request = one shared prefix +
  a private tail; median time-to-first-token with the prefix cache
  on vs off (both chunked, same warmed compiles).  Token-for-token
  parity between the two servers is always asserted; ``--smoke``
  additionally asserts the >= 2x TTFT floor and that every timed
  request hit the cache.
- *long-prompt interference*: short requests are decoding when a
  near-max-context prompt arrives; the stall is the worst single
  step wall time until that prompt finishes, chunked vs monolithic
  prefill.  Parity always asserted; ``--smoke`` asserts the
  monolithic stall is >= 2x the chunked one (decode stalls bounded
  by one chunk, not one full prefill).

Both workloads run ``Scheduler.audit()`` after every step — the
refcount/free-list invariant holds under the whole measured traffic,
not just the unit tests.

``--speculative`` switches to the speculative-decoding workloads of
docs/serving.md's speculation section (one JSON record to
``BENCH_serving_spec.json``):

- *repetitive-suffix traffic*: prompts built from short repeated
  patterns, long completions — the shape prompt-lookup drafts predict
  well.  Decoded tokens per ENGINE STEP (decode-phase tokens over
  decode+verify launches, from ``stats()["speculation"]``) with
  speculation on vs off; token-for-token parity between the two
  servers is always asserted, and ``--smoke`` asserts the >= 2x
  tokens-per-engine-step floor.  The record carries the in-window
  acceptance rate.
- *random traffic*: the same measurement on incompressible random
  prompts — reported, never floored (drafting can't help traffic with
  nothing to look up; the number documents the no-win case instead of
  hiding it).

``--sampling`` switches to the stochastic-sampling A/B of
docs/serving.md's "Stochastic sampling" section (one JSON record to
``BENCH_serving_sampling.json``): seeded temperature/top-p/top-k
traffic through three arms — pipeline+speculation ON (the default
stack), pipeline-only, and the forced synchronous-logits fallback a
legacy custom ``sample_fn`` used to cost.  Byte-identical same-seed
replay and cross-arm stream parity are always asserted (the
Gumbel-max coupling makes the fast paths invisible to outputs);
``--smoke`` floors the pipeline contribution on wall throughput
(PR-8 shape) and the speculation contribution on
decoded-tokens-per-engine-step (PR-6 shape, hardware-independent).

``--kv-offload`` switches to the hierarchical-KV-offload
session-continuation A/B of docs/serving.md's "Hierarchical KV
offload" section (one JSON record to
``BENCH_serving_kvoffload.json``): N sessions' prefixes are forced
out of a fixed-size device pool, then every session resumes — median
resumed-session TTFT with the evicted blocks PROMOTED back from the
host tier vs paid as cold prefill, at the same device pool bytes.
Cross-arm parity (greedy + counter-keyed stochastic) is always
asserted; ``--smoke`` floors the resumed-TTFT speedup at >= 2x and
requires the offload arm to have actually demoted and promoted.

Usage:
    python tools/serving_bench.py --smoke
    python tools/serving_bench.py --smoke --shared-prefix
    python tools/serving_bench.py --smoke --speculative
    python tools/serving_bench.py --smoke --sampling
    python tools/serving_bench.py [--requests 32] [--max-new 64]
        [--batch-size 8] [--hidden 256] [--layers 4] [--heads 8]
        [--max-context 512] [--seed 0] [--out BENCH_serving.json]
    python tools/serving_bench.py --shared-prefix [--prefix-len 256]
        [--tail-len 16] [--chunk 64] [--long-prompt 448] [--repeats 3]
"""

import argparse
import json
import os
import sys
import time

import numpy as np

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

# --kv-quant gates (docs/serving.md, "Quantized KV cache"; pinned in
# the BENCH_NOTES kv-quant decision table): the decode-parity budget
# is the minimum mean agreeing-prefix fraction quant-on greedy decode
# must keep vs the full-width pool (measured 1.0 on the smoke config —
# the budget leaves tolerance-oracle margin), and the headroom floor
# is the usable-live-block multiple a fixed byte budget must buy net
# of the fp32 scale sidecar (2D/(D+4) per head — 1.88x at head_dim 64)
KVQ_PARITY_BUDGET = 0.75
KVQ_HEADROOM_FLOOR = 1.8


def build_model(args):
    import jax
    import jax.numpy as jnp
    from apex_tpu import models

    cfg = models.GPTConfig(
        vocab_size=args.vocab, hidden_size=args.hidden,
        num_hidden_layers=args.layers, num_attention_heads=args.heads,
        intermediate_size=4 * args.hidden,
        max_position_embeddings=args.max_context,
        hidden_dropout_prob=0.0, attention_probs_dropout_prob=0.0)
    m = models.GPTLMHeadModel(cfg)
    params = m.init(jax.random.PRNGKey(args.seed),
                    jnp.ones((1, 8), jnp.int32))["params"]
    return cfg, m, params


def make_prompts(args):
    rng = np.random.RandomState(args.seed)
    # mixed lengths across the bucket ladder — the continuous batcher
    # must win on realistic skew, not a uniform batch
    lo, hi = 4, max(8, args.max_context // 4)
    return [list(rng.randint(0, args.vocab,
                             size=int(rng.randint(lo, hi))))
            for _ in range(args.requests)]


def run_continuous(cfg, params, prompts, args):
    """Timed InferenceServer.generate over the request set; returns
    (tokens_s, per-request latencies, stats, outputs)."""
    import jax.numpy as jnp
    from apex_tpu.serving import InferenceServer, SamplingParams

    server = InferenceServer(
        cfg, params, max_batch_size=args.batch_size,
        max_context=args.max_context,
        block_size=args.block_size, cache_dtype=jnp.float32,
        kv_quant="off", enable_disagg=False,   # quant axis is its own mode
        enable_streaming=False,                # so is --streaming
        enable_kv_offload=False,               # and --kv-offload
        # speculation and pipelining are measured by their own modes
        # (--speculative / --pipeline); the continuous-vs-naive record
        # keeps comparing the same synchronous one-token decode it
        # always has
        enable_speculation=False, enable_pipeline=False)
    # arm isolation (the PR-6/PR-12 pinning precedent): legacy arms
    # pin default-greedy sampling explicitly — stochastic sampling is
    # measured by its own mode (--sampling)
    greedy = SamplingParams()
    # warmup: compile every bucket the workload will touch + decode.
    # A warm prompt of length b lands exactly in bucket b (length b-1
    # for the top bucket — a full-length prompt leaves no room to
    # generate and would be rejected)
    warm = sorted({server.engine.bucket_for(len(p)) for p in prompts})
    server.generate([[1] * (b if b < args.max_context else b - 1)
                     for b in warm], max_new_tokens=2)
    server.engine.reset_cache()
    server.reset_meters()

    # latency per request: submit all up front (offline batch), track
    # finish step. For per-request wall latency, wrap generate: run
    # step loop manually recording completion times.
    reqs = [server.submit(p, args.max_new, sampling=greedy)
            for p in prompts]
    t0 = time.perf_counter()
    done_at = {}
    while server.scheduler.has_work:
        server.step()
        now = time.perf_counter()
        for r in reqs:
            if r.finished and r.uid not in done_at:
                done_at[r.uid] = now - t0
    dt = time.perf_counter() - t0
    total = sum(len(r.generated) for r in reqs)
    lats = sorted(done_at.values())
    return (total / dt, lats, server.stats(),
            [list(r.generated) for r in reqs])


def run_naive(cfg, m, params, prompts, args):
    """One request at a time, full recompute per token at fixed padded
    length (one compile). Returns (tokens_s, outputs)."""
    import jax
    import jax.numpy as jnp

    pad_to = args.max_context

    @jax.jit
    def step(ids, mask):
        return m.apply({"params": params}, ids, attention_mask=mask)

    def generate(prompt, n):
        toks = list(prompt)
        ids = np.zeros((1, pad_to), np.int32)
        mask = np.zeros((1, pad_to), np.int32)
        for _ in range(n):
            ln = len(toks)
            ids[0, :ln] = toks
            mask[0, :ln] = 1
            logits = step(jnp.asarray(ids), jnp.asarray(mask))
            toks.append(int(np.argmax(np.asarray(logits[0, ln - 1]))))
        return toks[len(prompt):]

    generate(prompts[0][:4], 2)                    # warmup compile
    t0 = time.perf_counter()
    outs = [generate(p, args.max_new) for p in prompts]
    dt = time.perf_counter() - t0
    total = sum(len(o) for o in outs)
    return total / dt, outs


def _step_audited(server):
    """One timed server step with the refcount invariant checked
    AFTER the timer stops — audit cost never pollutes the numbers."""
    t0 = time.perf_counter()
    server.step()
    dt = time.perf_counter() - t0
    server.scheduler.audit()
    return dt


def _median(vals):
    s = sorted(vals)
    return s[len(s) // 2]


def _build_prefix_servers(cfg, params, args):
    """The three feature corners the A/Bs need: (cached+chunked,
    cacheless+chunked, cacheless+monolithic).  The middle one is both
    the TTFT baseline and the interference treatment, so three servers
    cover two experiments' four arms."""
    import jax.numpy as jnp
    from apex_tpu.serving import InferenceServer

    def mk(cache, chunk):
        return InferenceServer(
            cfg, params, max_batch_size=args.batch_size,
            max_context=args.max_context, block_size=args.block_size,
            cache_dtype=jnp.float32, kv_quant="off", enable_disagg=False,
        enable_streaming=False, enable_kv_offload=False,
            enable_prefix_cache=cache,
            enable_chunked_prefill=chunk is not None,
            prefill_chunk=chunk,
            # isolate the prefix-cache/chunking axes from speculation
            # and pipelining (their own modes): all arms the
            # synchronous one-token decode
            enable_speculation=False, enable_pipeline=False)

    return (mk(True, args.chunk), mk(False, args.chunk),
            mk(False, None))


def run_shared_prefix_ttft(servers, args):
    """Median TTFT over a shared-system-prompt workload, prefix cache
    on vs off.  Requests run one at a time (TTFT isolated from
    batching effects); the warmup request both compiles every program
    the window touches and — on the cached server — populates the
    shared prefix, which is exactly the steady state of a
    system-prompt deployment."""
    rng = np.random.RandomState(args.seed + 1)
    shared = list(rng.randint(0, args.vocab, size=args.prefix_len))
    prompts = [shared + list(rng.randint(0, args.vocab,
                                         size=args.tail_len))
               for _ in range(args.requests)]

    def measure(server):
        server.generate([shared + [1]], max_new_tokens=2)
        server.reset_meters()
        ttfts, outs = [], []
        for p in prompts:
            req = server.submit(p, args.max_new)
            ttft = 0.0
            while not req.generated and not req.finished:
                ttft += _step_audited(server)
            ttfts.append(ttft)
            while not req.finished:
                _step_audited(server)
            outs.append(list(req.generated))
        return ttfts, outs, server.stats()

    cached_server, cacheless_server, _ = servers
    ttfts_cached, outs_cached, stats = measure(cached_server)
    ttfts_off, outs_off, stats_off = measure(cacheless_server)
    t_cached, t_off = _median(ttfts_cached), _median(ttfts_off)
    # the histogram's view of the same TTFT window, plus its log-bucket
    # distance from the direct measurement — the "within one bucket"
    # acceptance check (HistogramMeter's estimator guarantee), compared
    # at the histogram's rank convention (rank ceil(q*n))
    import math

    from apex_tpu.observability import HistogramMeter

    ladder = HistogramMeter()       # the stats() histograms' default
    n = len(ttfts_cached)
    direct_p50 = sorted(ttfts_cached)[max(1, math.ceil(0.5 * n)) - 1]
    hist_p50_ms = stats["latency"]["ttft_ms"].get("p50", 0.0)
    bucket_delta = abs(ladder.bucket_index(max(hist_p50_ms, 1e-9) / 1e3)
                       - ladder.bucket_index(max(direct_p50, 1e-9)))
    return {
        "ttft_ms_cached": round(t_cached * 1e3, 2),
        "ttft_ms_cacheless": round(t_off * 1e3, 2),
        "ttft_speedup": round(t_off / max(t_cached, 1e-9), 2),
        "latency": {"cached": stats["latency"],
                    "cacheless": stats_off["latency"]},
        "ttft_hist_bucket_delta": bucket_delta,
        "prefix_parity_mismatches": sum(
            a != b for a, b in zip(outs_cached, outs_off)),
        "prefix_hit_requests": stats.get("prefix_hit_requests", 0),
        "prefix_hit_rate": stats.get("prefix_hit_rate", 0.0),
        "prefix_stats": stats,
    }


def run_interference(servers, args):
    """Worst decode stall while a near-max-context prompt prefills,
    chunked vs monolithic.  The stall is the max single-step wall
    time between the long prompt's submission and its completion —
    with chunked prefill each such step carries one chunk; monolithic
    carries the whole bucketed prefill.  min over repeats: the floor
    of what each mode can do, immune to one-off scheduler noise (the
    monolithic floor still contains a full prefill)."""
    rng = np.random.RandomState(args.seed + 2)
    decoders = [list(rng.randint(0, args.vocab, size=8))
                for _ in range(2)]
    long_prompt = list(rng.randint(0, args.vocab,
                                   size=args.long_prompt))
    decode_budget = 4 + 4 * max(
        1, -(-args.long_prompt // (args.chunk or args.long_prompt)))

    def measure(server):
        server.generate([long_prompt, decoders[0]], max_new_tokens=2)
        server.reset_meters()
        stalls, outs = [], None
        for _ in range(args.repeats):
            short = [server.submit(p, decode_budget)
                     for p in decoders]
            for _ in range(4):          # decoders into steady decode
                _step_audited(server)
            longer = server.submit(long_prompt, 1)
            window = []
            while not longer.finished:
                window.append(_step_audited(server))
            stalls.append(max(window))
            while server.scheduler.has_work:
                _step_audited(server)
            outs = [list(r.generated) for r in short] \
                + [list(longer.generated)]
        return min(stalls), outs

    _, chunked_server, mono_server = servers
    s_chunk, outs_chunk = measure(chunked_server)
    s_mono, outs_mono = measure(mono_server)
    return {
        "stall_ms_chunked": round(s_chunk * 1e3, 2),
        "stall_ms_monolithic": round(s_mono * 1e3, 2),
        "stall_ratio": round(s_mono / max(s_chunk, 1e-9), 2),
        "interference_parity_mismatches": sum(
            a != b for a, b in zip(outs_chunk, outs_mono)),
    }


def _spec_server(cfg, params, args, spec):
    import jax.numpy as jnp
    from apex_tpu.serving import InferenceServer

    return InferenceServer(
        cfg, params, max_batch_size=args.batch_size,
        max_context=args.max_context, block_size=args.block_size,
        cache_dtype=jnp.float32, kv_quant="off", enable_disagg=False,
        enable_streaming=False, enable_kv_offload=False,
        enable_speculation=spec,
        spec_tokens=args.spec_tokens,
        # the speculation A/B isolates drafting from loop overlap
        # (--pipeline measures that axis)
        enable_pipeline=False)


def _run_spec_workload(server, prompts, args):
    """Drive one server over ``prompts`` (audited every step) and
    return (per-window speculation numbers, outputs).  Engine-step
    accounting comes from ``stats()["speculation"]`` deltas — the
    counters are monotonic, so the warmup is subtracted out."""
    server.generate([[1, 2, 3, 1, 2, 3, 1, 2]], max_new_tokens=4)
    # repetitive traffic repeats whole prompts -> whole-context COW
    # hits; compile the block-copy program outside the window too
    # ((0, 0) pairs are the garbage-block no-op)
    server.engine.copy_blocks([(0, 0)])
    # compile both decode-phase programs outside the timed window with
    # all-idle-slots calls (zero lengths/tables garbage-sink every
    # write): the warmup generate may have taken only one of the two
    # paths depending on whether its drafts fired
    b = server.engine.max_batch_size
    mb = server.engine.blocks_per_seq
    server.engine.decode(np.zeros((b,), np.int32),
                         np.zeros((b,), np.int32),
                         np.zeros((b, mb), np.int32))
    if server.speculating:
        kw = server.spec_tokens + 1
        server.engine.verify(
            np.zeros((b, kw), np.int32), np.zeros((b,), np.int32),
            np.zeros((b,), np.int32), np.zeros((b, mb), np.int32))
    server.engine.reset_cache()
    server.reset_meters()
    st0 = server.stats()["speculation"]
    reqs = [server.submit(p, args.max_new) for p in prompts]
    t0 = time.perf_counter()
    while server.scheduler.has_work:
        _step_audited(server)
    dt = time.perf_counter() - t0
    st = server.stats()["speculation"]
    steps = (st["verify_steps"] + st["decode_steps"]
             - st0["verify_steps"] - st0["decode_steps"])
    toks = st["decode_tokens"] - st0["decode_tokens"]
    drafted = st["drafted_tokens"] - st0["drafted_tokens"]
    accepted = st["accepted_tokens"] - st0["accepted_tokens"]
    outs = [list(r.generated) for r in reqs]
    return {
        "tokens_per_engine_step": round(toks / max(1, steps), 3),
        "engine_steps": steps,
        "decode_tokens": toks,
        "acceptance_rate": round(accepted / drafted, 3) if drafted
        else 0.0,
        "drafted_tokens": drafted,
        "tokens_s": round(sum(len(o) for o in outs) / max(dt, 1e-9), 1),
    }, outs


def run_speculative_mode(args):
    """Speculation on vs off over repetitive-suffix and random
    traffic: parity always, >= 2x tokens-per-engine-step floor on the
    repetitive workload under --smoke, random reported unfloored."""
    cfg, m, params = build_model(args)
    rng = np.random.RandomState(args.seed + 3)

    # repetitive-suffix: short patterns repeated through the prompt, so
    # the completion's own suffix (and often the prompt itself) is
    # exactly what prompt-lookup predicts
    rep_prompts = []
    for _ in range(args.requests):
        period = int(rng.randint(1, 4))
        pat = list(rng.randint(0, args.vocab, size=period))
        reps = -(-args.prompt_tokens // period)
        rep_prompts.append((pat * reps)[:args.prompt_tokens])
    rand_prompts = [list(rng.randint(0, args.vocab,
                                     size=args.prompt_tokens))
                    for _ in range(args.requests)]

    record = {
        "bench": "serving_speculative",
        "mode": "smoke" if args.smoke else "full",
        "config": {"requests": args.requests, "max_new": args.max_new,
                   "batch_size": args.batch_size,
                   "block_size": args.block_size,
                   "hidden": args.hidden, "layers": args.layers,
                   "heads": args.heads,
                   "max_context": args.max_context,
                   "vocab": args.vocab,
                   "prompt_tokens": args.prompt_tokens,
                   "spec_tokens": args.spec_tokens},
    }
    mismatches = 0
    for tag, prompts in (("repetitive", rep_prompts),
                         ("random", rand_prompts)):
        on, outs_on = _run_spec_workload(
            _spec_server(cfg, params, args, True), prompts, args)
        off, outs_off = _run_spec_workload(
            _spec_server(cfg, params, args, False), prompts, args)
        bad = sum(a != b for a, b in zip(outs_on, outs_off))
        mismatches += bad
        record[tag] = {
            "speculative": on, "baseline": off,
            "tokens_per_step_ratio": round(
                on["tokens_per_engine_step"]
                / max(off["tokens_per_engine_step"], 1e-9), 2),
            "parity_mismatches": bad,
        }
    # the acceptance-criteria headline numbers, hoisted for scrapers
    record["acceptance_rate"] = \
        record["repetitive"]["speculative"]["acceptance_rate"]
    record["tokens_per_step_ratio"] = \
        record["repetitive"]["tokens_per_step_ratio"]
    print(json.dumps(record))

    out = args.out
    if out != "-":
        if out is None:
            out = os.path.join(
                os.path.dirname(os.path.dirname(
                    os.path.abspath(__file__))),
                "BENCH_serving_spec.json")
        with open(out, "w") as f:
            json.dump(record, f, indent=1)
            f.write("\n")

    rc = 0
    if mismatches:
        print(f"FAIL: {mismatches} requests diverged between "
              "speculative and one-token greedy decode",
              file=sys.stderr)
        rc = 1
    if args.smoke and record["tokens_per_step_ratio"] < 2.0:
        print(f"FAIL: repetitive-suffix tokens-per-engine-step ratio "
              f"{record['tokens_per_step_ratio']} < 2.0x floor",
              file=sys.stderr)
        rc = 1
    return rc


def _pipeline_server(cfg, params, args, on):
    import jax.numpy as jnp
    from apex_tpu.serving import InferenceServer

    return InferenceServer(
        cfg, params, max_batch_size=args.batch_size,
        max_context=args.max_context, block_size=args.block_size,
        cache_dtype=jnp.float32, kv_quant="off", enable_disagg=False,
        enable_streaming=False, enable_kv_offload=False,
        enable_pipeline=on,
        # one-token decode in both arms: the pipeline axis measures
        # loop overlap, not speculation
        enable_speculation=False)


def _run_pipeline_workload(server, prompts, args):
    """Drive one server over a decode-heavy request set (audited
    every step); returns (window numbers, outputs).  Warmup compiles
    every program the arm's loop uses before the timed window."""
    from apex_tpu.serving import SamplingParams

    warm = sorted({server.engine.bucket_for(len(p)) for p in prompts})
    server.generate([[1] * (b if b < args.max_context else b - 1)
                     for b in warm], max_new_tokens=4)
    server.engine.reset_cache()
    server.reset_meters()
    # legacy-arm isolation: default greedy sampling pinned explicitly
    reqs = [server.submit(p, args.max_new,
                          sampling=SamplingParams())
            for p in prompts]
    t0 = time.perf_counter()
    steps = 0
    while server.scheduler.has_work:
        _step_audited(server)
        steps += 1
    dt = time.perf_counter() - t0
    outs = [list(r.generated) for r in reqs]
    st = server.stats()
    toks = sum(len(o) for o in outs)
    return {
        "tokens_s": round(toks / max(dt, 1e-9), 1),
        "steps_per_s": round(steps / max(dt, 1e-9), 1),
        "steps": steps,
        "tokens": toks,
        "wall_s": round(dt, 3),
        "step_ms": st["latency"]["step_ms"],
        "pipeline": st["pipeline"],
    }, outs


def run_pipeline_mode(args):
    """Pipelined vs synchronous step loop over identical decode-heavy
    traffic: short prompts, long completions, full batch — the
    steady-state shape where per-step host scheduling and device
    compute either overlap (dispatch-ahead) or serialize.  Parity is
    always asserted (greedy outputs must be bit-identical);
    ``--smoke`` floors the tokens/s ratio at >= 1.25x (the
    step-throughput acceptance bar — both arms produce the same token
    count, so the tokens/s ratio IS the step-throughput ratio up to
    the one extra drain step the window costs)."""
    cfg, m, params = build_model(args)
    rng = np.random.RandomState(args.seed + 4)
    prompts = [list(rng.randint(0, args.vocab,
                                size=args.prompt_tokens))
               for _ in range(args.requests)]

    on, outs_on = _run_pipeline_workload(
        _pipeline_server(cfg, params, args, True), prompts, args)
    off, outs_off = _run_pipeline_workload(
        _pipeline_server(cfg, params, args, False), prompts, args)
    mismatches = sum(a != b for a, b in zip(outs_on, outs_off))
    # dispatch-ahead hides host work UNDER device compute — that needs
    # a second core for the backend's execution thread.  On a
    # single-core host the two serialize whatever the loop does, so
    # the throughput floor is only meaningful (and only asserted)
    # where the hardware can express overlap; parity is asserted
    # everywhere.
    overlap_capable = (os.cpu_count() or 1) >= 2
    record = {
        "bench": "serving_pipeline",
        "mode": "smoke" if args.smoke else "full",
        "overlap_capable": overlap_capable,
        "cpu_count": os.cpu_count() or 1,
        "config": {"requests": args.requests, "max_new": args.max_new,
                   "batch_size": args.batch_size,
                   "block_size": args.block_size,
                   "hidden": args.hidden, "layers": args.layers,
                   "heads": args.heads,
                   "max_context": args.max_context,
                   "vocab": args.vocab,
                   "prompt_tokens": args.prompt_tokens},
        "pipelined": on,
        "synchronous": off,
        "speedup": round(on["tokens_s"] / max(off["tokens_s"], 1e-9),
                         2),
        "parity_mismatches": mismatches,
    }
    print(json.dumps(record))

    out = args.out
    if out != "-":
        if out is None:
            out = os.path.join(
                os.path.dirname(os.path.dirname(
                    os.path.abspath(__file__))),
                "BENCH_serving_pipeline.json")
        with open(out, "w") as f:
            json.dump(record, f, indent=1)
            f.write("\n")

    rc = 0
    if mismatches:
        print(f"FAIL: {mismatches} requests diverged between "
              "pipelined and synchronous greedy decode",
              file=sys.stderr)
        rc = 1
    if args.smoke:
        if overlap_capable and record["speedup"] < 1.25:
            print(f"FAIL: pipelined/synchronous step-throughput ratio "
                  f"{record['speedup']} < 1.25x floor",
                  file=sys.stderr)
            rc = 1
        elif not overlap_capable and record["speedup"] < 0.9:
            # no second core to overlap on: require the pipelined
            # loop to at least not regress the serial step
            print(f"FAIL: pipelined loop regressed the synchronous "
                  f"one ({record['speedup']}x < 0.9x) on a "
                  "single-core host", file=sys.stderr)
            rc = 1
        if not overlap_capable:
            print("note: single-core host — dispatch-ahead overlap "
                  "cannot run; 1.25x floor asserted only on "
                  ">= 2 cores", file=sys.stderr)
    return rc


def _disagg_server(cfg, params, args, disagg):
    """The disaggregation A/B arms at EQUAL total HBM: the disagg arm
    splits ``--disagg-blocks`` + ``--disagg-prefill-blocks`` between
    its two pools; the monolithic arm gets their sum as one pool.  The
    decode pool keeps the full default fast-path stack (speculation +
    pipeline) — phase separation must protect the decode tail without
    turning anything off."""
    import jax.numpy as jnp
    from apex_tpu.serving import InferenceServer

    total = args.disagg_blocks + args.disagg_prefill_blocks
    return InferenceServer(
        cfg, params, max_batch_size=args.batch_size,
        max_context=args.max_context, block_size=args.block_size,
        num_blocks=args.disagg_blocks if disagg else total,
        cache_dtype=jnp.float32, kv_quant="off",
        enable_streaming=False, enable_kv_offload=False,
        prefill_chunk=args.chunk,
        enable_disagg=disagg,
        disagg_prefill_blocks=(args.disagg_prefill_blocks
                               if disagg else None),
        prefill_max_concurrent=args.disagg_prefill_concurrent)


def _run_disagg_arm(server, decode_prompts, long_prompts, args,
                    interference):
    """Drive one arm: ``decode_prompts`` settle into steady decode,
    meters reset, then (under ``interference``) one long prompt
    submits per step until ``long_prompts`` is exhausted — 10x the
    decode arrival rate on the stock shapes — while the decoders run
    to completion.  Long prompts carry ``max_new_tokens=1`` (pure
    prefill traffic), so the ITL histogram measured over the window
    contains EXACTLY the decoders' inter-token gaps.  Every step is
    audited (both pools under disaggregation).  Returns (window
    record, decoder outputs, long outputs)."""
    from apex_tpu.serving import SamplingParams

    greedy = SamplingParams()
    # warmup compiles every program the arm touches: the decode
    # bucket, the long prompt's chunk ladder, decode, verify (the
    # repetitive prompt makes drafts fire), and — under
    # disaggregation — the cross-pool hand-off copy.  A compile
    # landing inside one arm's measured window but not another's
    # would fake (or hide) the very tail the A/B measures.
    server.generate([decode_prompts[0], long_prompts[0],
                     [1, 2] * (args.prompt_tokens // 2 + 1)],
                    max_new_tokens=8, sampling=greedy)
    server.engine.reset_cache()
    if server.disagg:
        server.prefill_engine.reset_cache()
    server.reset_meters()

    decoders = [server.submit(p, args.max_new, sampling=greedy)
                for p in decode_prompts]
    # settle PAST the first decode steps (not just the prefill-sampled
    # token): the prefill->decode transition costs differently across
    # arms, and the window must compare steady decode against steady
    # decode
    while any(len(r.generated) < 3 for r in decoders):
        server.step()
        server.audit()
    server.reset_meters()       # the measured window: steady decode
    t0 = time.perf_counter()
    longs = []
    next_long = 0
    while any(not r.finished for r in decoders):
        if interference:
            for _ in range(args.disagg_arrival):
                if next_long >= len(long_prompts):
                    break
                longs.append(server.submit(long_prompts[next_long], 1,
                                           sampling=greedy))
                next_long += 1
        server.step()
        server.audit()
    window_s = time.perf_counter() - t0
    st_window = server.stats()
    # drain the long-prompt tail OUTSIDE the measured window (the
    # decoders are done; no further ITL samples can record)
    while interference and next_long < len(long_prompts):
        longs.append(server.submit(long_prompts[next_long], 1,
                                   sampling=greedy))
        next_long += 1
    while server.has_work:
        server.step()
        server.audit()
    itl = st_window["latency"]["itl_ms"]
    rec = {
        "itl_ms": itl,
        "itl_p99_ms": itl.get("p99", 0.0),
        "itl_p50_ms": itl.get("p50", 0.0),
        "window_s": round(window_s, 3),
        "step_ms": st_window["latency"]["step_ms"],
        "longs_submitted_in_window": len(longs),
        "disagg": st_window["disagg"],
    }
    return (rec, [list(r.generated) for r in decoders],
            [list(r.generated) for r in longs])


def run_disagg_mode(args):
    """Disaggregated prefill/decode interference A/B
    (``docs/serving.md``, "Disaggregated prefill/decode"; one JSON
    record to ``BENCH_serving_disagg.json``), extending the PR-3
    stall-ratio methodology from one long prompt to sustained 10x
    long-prompt pressure:

    - *solo decode*: the disagg server serving only the decoders —
      the ITL p99 floor everything is measured against;
    - *interference, disagg ON*: one long (pure-prefill) request
      submitted per step while the decoders run — the prefill pool
      absorbs them and the decode pool never yields a step;
    - *interference, disagg OFF*: the same schedule into a monolithic
      server of EQUAL total HBM — chunk prefills crowd every step.

    Parity is ALWAYS asserted (decoder streams identical across all
    three arms, long outputs identical across the two interference
    arms).  ``--smoke`` floors: the monolithic arm must SHOW the
    interference (ITL p99 >= 1.5x solo), disaggregation must beat it
    (disagg p99 strictly below mono p99), and — on hosts with a
    second core, where prefill compute can actually run under the
    in-flight decode — the headline floor: disagg ITL p99 <= 1.1x
    solo.  Single-core hosts record ``phase_overlap_capable: false``
    and assert the interference-reduction floor only (the PR-8
    ``overlap_capable`` precedent)."""
    cfg, m, params = build_model(args)
    rng = np.random.RandomState(args.seed + 7)
    decode_prompts = [list(rng.randint(0, args.vocab,
                                       size=args.prompt_tokens))
                      for _ in range(args.disagg_decoders)]
    long_prompts = [list(rng.randint(0, args.vocab,
                                     size=args.long_prompt))
                    for _ in range(10 * args.disagg_decoders)]

    solo, outs_solo, _ = _run_disagg_arm(
        _disagg_server(cfg, params, args, True),
        decode_prompts, long_prompts, args, interference=False)
    on, outs_on, longs_on = _run_disagg_arm(
        _disagg_server(cfg, params, args, True),
        decode_prompts, long_prompts, args, interference=True)
    off, outs_off, longs_off = _run_disagg_arm(
        _disagg_server(cfg, params, args, False),
        decode_prompts, long_prompts, args, interference=True)

    mismatches = (
        sum(a != b for a, b in zip(outs_solo, outs_on))
        + sum(a != b for a, b in zip(outs_solo, outs_off))
        + sum(a != b for a, b in zip(longs_on, longs_off)))
    overlap_capable = (os.cpu_count() or 1) >= 2
    p99_solo = max(solo["itl_p99_ms"], 1e-6)
    record = {
        "bench": "serving_disagg",
        "mode": "smoke" if args.smoke else "full",
        "phase_overlap_capable": overlap_capable,
        "cpu_count": os.cpu_count() or 1,
        "config": {"decoders": args.disagg_decoders,
                   "max_new": args.max_new,
                   "batch_size": args.batch_size,
                   "block_size": args.block_size,
                   "chunk": args.chunk,
                   "long_prompt": args.long_prompt,
                   "long_requests": len(long_prompts),
                   "decode_blocks": args.disagg_blocks,
                   "prefill_blocks": args.disagg_prefill_blocks,
                   "prefill_max_concurrent":
                       args.disagg_prefill_concurrent,
                   "hidden": args.hidden, "layers": args.layers,
                   "heads": args.heads,
                   "max_context": args.max_context,
                   "vocab": args.vocab,
                   "prompt_tokens": args.prompt_tokens},
        "solo": solo,
        "disagg_on": on,
        "disagg_off": off,
        # the headline ratios: decode ITL p99 under 10x long-prompt
        # pressure, relative to the solo-decode floor
        "itl_p99_ratio_disagg": round(on["itl_p99_ms"] / p99_solo, 3),
        "itl_p99_ratio_monolithic": round(
            off["itl_p99_ms"] / p99_solo, 3),
        "interference_reduction": round(
            off["itl_p99_ms"] / max(on["itl_p99_ms"], 1e-6), 3),
        "parity_mismatches": mismatches,
    }
    print(json.dumps(record))

    out = args.out
    if out != "-":
        if out is None:
            out = os.path.join(
                os.path.dirname(os.path.dirname(
                    os.path.abspath(__file__))),
                "BENCH_serving_disagg.json")
        with open(out, "w") as f:
            json.dump(record, f, indent=1)
            f.write("\n")

    rc = 0
    if mismatches:
        print(f"FAIL: {mismatches} streams diverged across the "
              "disagg/monolithic/solo arms (greedy outputs must be "
              "bit-exact)", file=sys.stderr)
        rc = 1
    if args.smoke:
        if record["itl_p99_ratio_monolithic"] < 1.5:
            print(f"FAIL: the monolithic arm shows no interference "
                  f"(ITL p99 ratio "
                  f"{record['itl_p99_ratio_monolithic']} < 1.5x solo "
                  f"under 10x long-prompt traffic) — the A/B is not "
                  f"measuring the problem", file=sys.stderr)
            rc = 1
        if record["interference_reduction"] < 1.25:
            print(f"FAIL: disaggregation reduced the interference "
                  f"tail only {record['interference_reduction']}x "
                  f"(< 1.25x floor; disagg "
                  f"{record['itl_p99_ratio_disagg']}x vs monolithic "
                  f"{record['itl_p99_ratio_monolithic']}x solo)",
                  file=sys.stderr)
            rc = 1
        if overlap_capable and record["itl_p99_ratio_disagg"] > 1.1:
            print(f"FAIL: disagg decode ITL p99 "
                  f"{record['itl_p99_ratio_disagg']}x solo exceeds "
                  f"the 1.1x flatness floor under 10x long-prompt "
                  f"traffic", file=sys.stderr)
            rc = 1
        if not overlap_capable:
            print("note: single-core host — prefill compute cannot "
                  "run under the in-flight decode, so the 1.1x "
                  "flatness floor is asserted only on >= 2 cores; "
                  "the interference-reduction floors still hold",
                  file=sys.stderr)
    return rc


def _streaming_server(cfg, params, args, streaming, num_blocks=None):
    """The streaming A/B arms: one shape, only the delivery tier
    differs.  The pool is roomy (every slot can hold a full-context
    request) so the gap tail measures decode cadence, not preemption;
    the cancellation arm passes its own deliberately small pool."""
    import jax.numpy as jnp
    from apex_tpu.serving import InferenceServer

    bps = -(-args.max_context // args.block_size)
    return InferenceServer(
        cfg, params, max_batch_size=args.batch_size,
        max_context=args.max_context, block_size=args.block_size,
        num_blocks=(num_blocks if num_blocks is not None
                    else args.batch_size * bps + 1),
        cache_dtype=jnp.float32, kv_quant="off", enable_disagg=False,
        enable_kv_offload=False,
        enable_streaming=streaming)


def _run_streaming_arm(server, prompts, args, streaming):
    """Drive one arm and measure when tokens become VISIBLE to a
    client: the streaming arm drains each request's ``TokenStream``
    after every step and timestamps each delivered token; the baseline
    arm polls ``req.generated`` growth on the identical loop.  Both
    arms therefore measure the same thing — the wall-clock gap between
    consecutive token arrivals per request — so their p99 ratio
    isolates the delivery tier's cost.  Every step is audited.
    Returns (gaps_ms, outputs, engine-ITL block)."""
    from apex_tpu.serving import SamplingParams

    greedy = SamplingParams()
    # warmup compiles the prefill bucket + decode before the window
    server.generate([prompts[0]], max_new_tokens=8, sampling=greedy)
    server.engine.reset_cache()
    server.reset_meters()

    reqs = [server.submit(p, args.max_new, sampling=greedy)
            for p in prompts]
    streams = ({r.uid: server.stream(r) for r in reqs}
               if streaming else None)
    delivered = {r.uid: [] for r in reqs}
    last_at = {}
    gaps = []
    while any(not r.finished for r in reqs):
        server.step()
        server.audit()
        now = time.perf_counter()
        for r in reqs:
            if streaming:
                new = streams[r.uid].drain()
            else:
                new = list(r.generated)[len(delivered[r.uid]):]
            for tok in new:
                if r.uid in last_at:
                    gaps.append((now - last_at[r.uid]) * 1e3)
                last_at[r.uid] = now
                delivered[r.uid].append(tok)
    if streaming:
        # terminal events: every stream must close with the request's
        # finish reason and the delivered bytes must equal the output
        for r in reqs:
            s = streams[r.uid]
            delivered[r.uid].extend(s.drain())
            assert s.done and s.finish_reason == r.finish_reason, (
                r.uid, s.finish_reason, r.finish_reason)
        assert server.stream_broker.active == 0
    for r in reqs:
        assert delivered[r.uid] == list(r.generated), (
            "delivered stream diverged from Request.output "
            f"(uid {r.uid})")
    gaps.sort()
    st = server.stats()
    rec = {
        "gap_p50_ms": round(gaps[int(0.50 * (len(gaps) - 1))], 3),
        "gap_p99_ms": round(gaps[int(0.99 * (len(gaps) - 1))], 3),
        "gap_samples": len(gaps),
        "engine_itl_ms": st["latency"]["itl_ms"],
    }
    if streaming:
        rec["streams"] = st["streams"]
    return rec, [list(r.generated) for r in reqs]


def _run_streaming_cancel_arm(cfg, params, args):
    """The cancellation-reclaims-capacity arm: a pool sized for
    exactly ``batch_size`` full-context requests is filled with
    long-running streamed decoders, every stream is torn down
    mid-decode (client disconnect -> ``cancel``), and a SECOND full
    batch must then run to a healthy finish on the reclaimed blocks —
    with the allocator audited every step.  A leaked block or
    lookahead hold would starve the second batch or trip the audit."""
    from apex_tpu.serving import SamplingParams

    greedy = SamplingParams()
    bps = -(-args.max_context // args.block_size)
    server = _streaming_server(cfg, params, args, True,
                               num_blocks=args.batch_size * bps + 1)
    rng = np.random.RandomState(args.seed + 11)
    prompts = [list(rng.randint(0, args.vocab, size=args.prompt_tokens))
               for _ in range(args.batch_size)]
    server.generate([prompts[0]], max_new_tokens=8, sampling=greedy)
    server.engine.reset_cache()
    server.reset_meters()

    max_new = min(args.max_context - args.prompt_tokens - 1, 48)
    first = [server.submit(p, max_new, sampling=greedy)
             for p in prompts]
    streams = {r.uid: server.stream(r) for r in first}
    for _ in range(4):                    # into steady mid-decode
        server.step()
        server.audit()
    live_before = server.stats()["memory"]["blocks_live"]
    cancelled = 0
    for r in first:
        streams[r.uid].close()            # the client hangs up...
        if server.cancel(r.uid):          # ...and the SSE tier cancels
            cancelled += 1
    server.audit()
    while server.has_work:
        server.step()
        server.audit()
    live_after = server.stats()["memory"]["blocks_live"]

    second = [server.submit(p, max_new, sampling=greedy)
              for p in prompts]
    while server.has_work:
        server.step()
        server.audit()
    tally = {}
    for r in second:
        tally[r.finish_reason] = tally.get(r.finish_reason, 0) + 1
    healthy_after = sum(tally.get(k, 0) for k in ("eos", "length"))
    return {
        "pool_blocks": args.batch_size * bps + 1,
        "first_batch": len(first),
        "cancelled": cancelled,
        "blocks_live_mid_decode": live_before,
        "blocks_live_after_cancel": live_after,
        "second_batch_finished": tally,
        "second_batch_healthy": healthy_after,
    }


def run_streaming_mode(args):
    """Streaming delivery A/B + cancellation capacity arm
    (``docs/serving.md``, "Streaming & cancellation"; one JSON record
    to ``BENCH_serving_streaming.json``):

    - *baseline*: ``enable_streaming=False`` server, token visibility
      measured by polling ``req.generated`` each step — the
      non-streaming gap tail everything is measured against;
    - *streaming*: the same traffic with a ``TokenStream`` per
      request drained each step; delivered sequences are asserted
      byte-identical to ``Request.output`` and every stream must
      close with the request's finish reason;
    - *cancellation*: a full pool of streamed decoders is disconnected
      mid-decode; the freed blocks must carry a second full batch to
      a healthy finish (audit-clean throughout).

    ``--smoke`` floors: delivered-ITL p99 <= 1.1x the baseline gap
    tail (retire-time fan-out must not add a scheduling stall), zero
    parity mismatches, every cancel reclaimed (``blocks_live`` back
    to zero), and the post-cancel batch 100% healthy."""
    cfg, m, params = build_model(args)
    rng = np.random.RandomState(args.seed + 5)
    prompts = [list(rng.randint(0, args.vocab, size=args.prompt_tokens))
               for _ in range(args.requests)]

    # wall-clock gap tails are jittery on a shared CPU host, so the
    # A/B interleaves ``--repeats`` baseline/streaming pairs and
    # takes the MIN of the per-pair p99 ratios (the existing repeats
    # precedent): delivery fan-out can only ADD latency, so the
    # least-jittered pair is the honest estimate of its true cost
    mismatches = 0
    ratios = []
    base = stream = None
    for _ in range(max(1, args.repeats)):
        b, outs_base = _run_streaming_arm(
            _streaming_server(cfg, params, args, False), prompts,
            args, streaming=False)
        s, outs_stream = _run_streaming_arm(
            _streaming_server(cfg, params, args, True), prompts,
            args, streaming=True)
        mismatches += sum(x != y
                          for x, y in zip(outs_base, outs_stream))
        ratios.append(
            round(s["gap_p99_ms"] / max(b["gap_p99_ms"], 1e-6), 3))
        if base is None or ratios[-1] == min(ratios):
            base, stream = b, s
    cancel = _run_streaming_cancel_arm(cfg, params, args)
    record = {
        "bench": "serving_streaming",
        "mode": "smoke" if args.smoke else "full",
        "config": {"requests": args.requests, "max_new": args.max_new,
                   "batch_size": args.batch_size,
                   "block_size": args.block_size,
                   "hidden": args.hidden, "layers": args.layers,
                   "heads": args.heads,
                   "max_context": args.max_context,
                   "vocab": args.vocab,
                   "prompt_tokens": args.prompt_tokens},
        "baseline": base,
        "streaming": stream,
        # the headline: wall-clock inter-token delivery tail with the
        # streaming tier on, relative to polling the same server shape
        "delivered_itl_p99_ratio": min(ratios),
        "delivered_itl_p99_ratio_repeats": ratios,
        "cancellation": cancel,
        "parity_mismatches": mismatches,
    }
    print(json.dumps(record))

    out = args.out
    if out != "-":
        if out is None:
            out = os.path.join(
                os.path.dirname(os.path.dirname(
                    os.path.abspath(__file__))),
                "BENCH_serving_streaming.json")
        with open(out, "w") as f:
            json.dump(record, f, indent=1)
            f.write("\n")

    rc = 0
    if mismatches:
        print(f"FAIL: {mismatches} streams diverged between the "
              "baseline and streaming arms (delivery is observation-"
              "only; greedy outputs must be bit-exact)",
              file=sys.stderr)
        rc = 1
    if args.smoke:
        if record["delivered_itl_p99_ratio"] > 1.1:
            print(f"FAIL: delivered-ITL p99 "
                  f"{record['delivered_itl_p99_ratio']}x the "
                  f"non-streaming gap tail exceeds the 1.1x floor "
                  f"(retire-time fan-out must not stall the step "
                  f"loop)", file=sys.stderr)
            rc = 1
        if cancel["cancelled"] != cancel["first_batch"]:
            print(f"FAIL: only {cancel['cancelled']} of "
                  f"{cancel['first_batch']} mid-decode disconnects "
                  f"cancelled", file=sys.stderr)
            rc = 1
        if cancel["blocks_live_after_cancel"] != 0:
            print(f"FAIL: {cancel['blocks_live_after_cancel']} KV "
                  f"blocks still live after every stream was "
                  f"disconnected and cancelled (leak)",
                  file=sys.stderr)
            rc = 1
        if cancel["second_batch_healthy"] != cancel["first_batch"]:
            print(f"FAIL: post-cancel batch finished "
                  f"{cancel['second_batch_finished']} — the reclaimed "
                  f"pool must carry a full healthy batch",
                  file=sys.stderr)
            rc = 1
    return rc


def _sampling_server(cfg, params, args, pipeline, speculation):
    import jax.numpy as jnp
    from apex_tpu.serving import InferenceServer

    # (True, True): the server DEFAULT stack — stochastic requests
    # keep speculation and the pipelined loop ON (the on-device
    # sampling suite, docs/serving.md "Stochastic sampling").
    # (False, False): the forced logits fallback — exactly what the
    # legacy custom sample_fn escape hatch cost (both fast paths off,
    # per-step (B, V) host logits + host sampling).  (True, False):
    # the pipeline-contribution arm, isolating dispatch-ahead overlap
    # from speculation width (the two floors below are per-axis).
    return InferenceServer(
        cfg, params, max_batch_size=args.batch_size,
        max_context=args.max_context, block_size=args.block_size,
        cache_dtype=jnp.float32, kv_quant="off", enable_disagg=False,
        enable_streaming=False, enable_kv_offload=False,
        enable_pipeline=pipeline, enable_speculation=speculation,
        spec_tokens=args.spec_tokens)


def _sampling_traffic(args):
    """The stochastic chat mix: repetitive prompts (so prompt-lookup
    drafts fire) with per-request seeded temperature/top-p/top-k
    params — low-ish temperatures, the peaked-distribution regime
    where rejection sampling actually accepts."""
    from apex_tpu.serving import SamplingParams

    rng = np.random.RandomState(args.seed + 11)
    prompts, params = [], []
    for i in range(args.requests):
        period = int(rng.randint(1, 4))
        pat = [int(x) for x in rng.randint(0, args.vocab, size=period)]
        prompts.append((pat * (args.prompt_tokens // period + 1))
                       [:args.prompt_tokens])
        # low temperatures: the toy bench model is random-init, so
        # only near-argmax distributions give drafts a real accept
        # probability (p(draft) is what rejection sampling accepts
        # with) — the same peaked-regime argument behind the PR-6
        # repetitive-traffic floor.  A trained model is peaked at
        # chat temperatures; a random one needs help.
        params.append(SamplingParams(
            temperature=float(rng.uniform(0.02, 0.15)),
            top_k=int(rng.choice([0, 16, 64])) or None,
            top_p=float(rng.choice([1.0, 0.95, 0.9])),
            seed=int(rng.randint(1 << 30))))
    return prompts, params


def _run_sampling_workload(server, prompts, params, args):
    """Drive one arm over the stochastic request set (audited every
    step) TWICE — the second pass is the same-seed replay, asserted
    byte-identical (the counter-key determinism contract).  Returns
    (window numbers of the best pass, outputs)."""
    warm = sorted({server.engine.bucket_for(len(p)) for p in prompts})
    server.generate([[1] * (b if b < args.max_context else b - 1)
                     for b in warm], max_new_tokens=4)
    # one stochastic warmup so the stochastic twins compile outside
    # the timed window, mirroring the greedy warmup above
    server.engine.reset_cache()
    server.generate(prompts[:1], max_new_tokens=4,
                    sampling=params[:1])
    outs, best = None, None
    for _ in range(2):
        server.engine.reset_cache()
        server.reset_meters()
        reqs = [server.submit(p, args.max_new, sampling=s)
                for p, s in zip(prompts, params)]
        t0 = time.perf_counter()
        steps = 0
        while server.scheduler.has_work:
            _step_audited(server)
            steps += 1
        dt = time.perf_counter() - t0
        run_outs = [list(r.generated) for r in reqs]
        if outs is not None and run_outs != outs:
            raise AssertionError(
                "same-seed stochastic replay diverged — counter-key "
                "determinism is broken")
        outs = run_outs
        toks = sum(len(o) for o in run_outs)
        if best is None or toks / max(dt, 1e-9) > best["tokens_s"]:
            st = server.stats()
            best = {
                "tokens_s": round(toks / max(dt, 1e-9), 1),
                "steps_per_s": round(steps / max(dt, 1e-9), 1),
                "steps": steps,
                "tokens": toks,
                "wall_s": round(dt, 3),
                "tokens_per_engine_step":
                    st["speculation"]["tokens_per_engine_step"],
                "stoch_acceptance_rate":
                    st["sampling"]["rejection"]["acceptance_rate"],
                "stoch_resamples":
                    st["sampling"]["rejection"]["resamples"],
                "requests_by_class": st["sampling"]["requests"],
                "pipeline": st["pipeline"]["enabled"],
                "speculation": st["speculation"]["enabled"],
            }
    return best, outs


def run_sampling_mode(args):
    """Stochastic traffic A/B (docs/serving.md, "Stochastic
    sampling"): the on-device sampling suite with pipeline +
    speculation ON vs the forced synchronous-logits fallback (what a
    legacy custom ``sample_fn`` used to silently cost) over identical
    seeded temperature/top-p/top-k traffic, plus a pipeline-only
    middle arm that isolates the two fast paths' contributions.

    Oracles: each arm replays byte-identically under the same seeds
    (asserted always), and ALL arms emit IDENTICAL streams — the
    Gumbel-max coupling makes the sampled stream independent of
    speculation and pipelining (asserted always).  ``--smoke`` floors
    each fast path on the axis it actually accelerates, mirroring its
    own bench's precedent:

    - pipeline (PR-8 floor shape, wall time): pipeline-on /
      fallback tokens/s >= 1.25x on overlap-capable (>= 2 core)
      hosts; single-core hosts record ``overlap_capable: false`` and
      floor >= 0.9x no-regression (dispatch-ahead can't overlap on
      one core, and speculation is held out of both arms because its
      verify width is a deliberate compute-for-latency trade that
      serial hardware can't amortize);
    - speculation (PR-6 floor shape, tokens per engine step): full
      fast path / fallback decoded-tokens-per-engine-step >= 1.25x
      on EVERY host — the hardware-independent statement that
      rejection sampling multiplies tokens per launch on this
      traffic.  The full fast/fallback wall ratio is recorded
      unfloored alongside (on wide accelerators the verify columns
      ride the same matmul the single token would, so the
      tokens-per-step multiple converges to wall — the PR-6
      argument)."""
    cfg, m, params = build_model(args)
    prompts, sparams = _sampling_traffic(args)

    fast, outs_fast = _run_sampling_workload(
        _sampling_server(cfg, params, args, True, True), prompts,
        sparams, args)
    pipe, outs_pipe = _run_sampling_workload(
        _sampling_server(cfg, params, args, True, False), prompts,
        sparams, args)
    fallback, outs_fb = _run_sampling_workload(
        _sampling_server(cfg, params, args, False, False), prompts,
        sparams, args)
    mismatches = sum(a != b for a, b in zip(outs_fast, outs_fb))
    mismatches += sum(a != b for a, b in zip(outs_pipe, outs_fb))
    overlap_capable = (os.cpu_count() or 1) >= 2
    record = {
        "bench": "serving_sampling",
        "mode": "smoke" if args.smoke else "full",
        "overlap_capable": overlap_capable,
        "cpu_count": os.cpu_count() or 1,
        "config": {"requests": args.requests, "max_new": args.max_new,
                   "batch_size": args.batch_size,
                   "block_size": args.block_size,
                   "hidden": args.hidden, "layers": args.layers,
                   "heads": args.heads,
                   "max_context": args.max_context,
                   "vocab": args.vocab,
                   "prompt_tokens": args.prompt_tokens,
                   "spec_tokens": args.spec_tokens},
        "fast": fast,               # pipeline + speculation ON
        "pipeline_only": pipe,      # dispatch-ahead, no speculation
        "fallback": fallback,       # forced synchronous logits path
        "speedup_wall": round(fast["tokens_s"]
                              / max(fallback["tokens_s"], 1e-9), 2),
        "speedup_pipeline": round(
            pipe["tokens_s"] / max(fallback["tokens_s"], 1e-9), 2),
        "speedup_tokens_per_step": round(
            fast["tokens_per_engine_step"]
            / max(fallback["tokens_per_engine_step"], 1e-9), 2),
        "parity_mismatches": mismatches,
    }
    print(json.dumps(record))

    out = args.out
    if out != "-":
        if out is None:
            out = os.path.join(
                os.path.dirname(os.path.dirname(
                    os.path.abspath(__file__))),
                "BENCH_serving_sampling.json")
        with open(out, "w") as f:
            json.dump(record, f, indent=1)
            f.write("\n")

    rc = 0
    if mismatches:
        print(f"FAIL: {mismatches} request streams diverged across "
              "the stochastic arms (the Gumbel-max coupling should "
              "make pipeline/speculation invisible to outputs)",
              file=sys.stderr)
        rc = 1
    if args.smoke:
        if record["speedup_tokens_per_step"] < 1.25:
            print(f"FAIL: stochastic speculation tokens-per-engine-"
                  f"step ratio {record['speedup_tokens_per_step']} "
                  f"< 1.25x floor", file=sys.stderr)
            rc = 1
        if overlap_capable and record["speedup_pipeline"] < 1.25:
            print(f"FAIL: stochastic pipeline/fallback "
                  f"step-throughput ratio "
                  f"{record['speedup_pipeline']} < 1.25x floor",
                  file=sys.stderr)
            rc = 1
        elif not overlap_capable \
                and record["speedup_pipeline"] < 0.9:
            print(f"FAIL: the stochastic pipelined loop regressed "
                  f"the logits fallback "
                  f"({record['speedup_pipeline']}x < 0.9x) on a "
                  "single-core host", file=sys.stderr)
            rc = 1
        if not overlap_capable:
            print("note: single-core host — dispatch-ahead overlap "
                  "cannot run; the 1.25x wall floor is asserted only "
                  "on >= 2 cores (speculation's tokens-per-step "
                  "floor is asserted everywhere)", file=sys.stderr)
    return rc


def _tp_server(cfg, params, args, mesh):
    import jax.numpy as jnp
    from apex_tpu.serving import InferenceServer

    # BOTH arms run the server's DEFAULT stack (speculation +
    # pipelined loop + prefix cache + chunked prefill): the tp axis
    # must prove sharding COMPOSES with everything that ships on, and
    # on an emulated mesh the multi-token engine steps amortize the
    # partitioned-dispatch overhead the same way they would amortize
    # collective latency on real interconnect
    return InferenceServer(
        cfg, params, max_batch_size=args.batch_size,
        max_context=args.max_context, block_size=args.block_size,
        cache_dtype=jnp.float32, kv_quant="off", enable_disagg=False,
        enable_streaming=False, enable_kv_offload=False, mesh=mesh)


def _run_tp_workload(server, prompts, args):
    """Drive one arm over the repetitive decode-heavy request set
    (audited every step), ``--repeats`` times; returns (best-window
    numbers, outputs).  Best-of-repeats is the PR-3 interference
    precedent: the floor of what the arm can do, immune to one-off
    scheduler noise on a shared host."""
    from apex_tpu.serving import SamplingParams

    server.generate([prompts[0]], max_new_tokens=4)     # warm compiles
    best_tps, outs = 0.0, None
    for _ in range(args.repeats):
        server.engine.reset_cache()
        server.reset_meters()
        # legacy-arm isolation: default greedy pinned explicitly
        reqs = [server.submit(p, args.max_new,
                              sampling=SamplingParams())
                for p in prompts]
        t0 = time.perf_counter()
        steps = 0
        while server.scheduler.has_work:
            _step_audited(server)
            steps += 1
        dt = time.perf_counter() - t0
        run_outs = [list(r.generated) for r in reqs]
        if outs is not None and run_outs != outs:
            raise AssertionError(
                "tp bench arm produced different tokens across "
                "repeats — greedy decode must be deterministic")
        outs = run_outs
        best_tps = max(best_tps,
                       sum(len(o) for o in outs) / max(dt, 1e-9))
    st = server.stats()
    return {
        "tokens_s": round(best_tps, 1),
        "tokens_per_engine_step":
            st["speculation"]["tokens_per_engine_step"],
        "step_ms": st["latency"]["step_ms"],
    }, outs


def run_tp_mode(args):
    """Tensor-parallel vs single-chip serving over identical
    repetitive decode-heavy traffic (docs/serving.md,
    "Tensor-parallel serving").  Token-for-token greedy parity
    between the tp=N and tp=1 arms is ALWAYS asserted — the sharded
    lowering must be a placement of the same computation.  The
    throughput floor is backend-aware: an emulated CPU mesh
    time-slices N "devices" over the same cores, so scaling
    physically cannot show — ``--smoke`` there floors no-regression
    (>= 0.9x tp=1) and records ``tp_capable: false``; on a real
    multi-chip backend the >= 1.0x-scaling floor arms instead
    (BENCH_NOTES precedent from the PR-8 single-core pipeline
    bench)."""
    # the emulated mesh must exist BEFORE jax initializes its backend
    # (same trick as tests/conftest.py); a no-op when the operator
    # already set the flag or runs on real multi-chip hardware
    flags = os.environ.get("XLA_FLAGS", "")
    if "xla_force_host_platform_device_count" not in flags:
        os.environ["XLA_FLAGS"] = (
            flags + f" --xla_force_host_platform_device_count="
            f"{max(8, args.tp)}").strip()

    import jax
    from jax.sharding import Mesh

    if len(jax.devices()) < args.tp:
        print(f"FAIL: --tp {args.tp} needs {args.tp} devices, have "
              f"{len(jax.devices())}", file=sys.stderr)
        return 1
    cfg, m, params = build_model(args)
    rng = np.random.RandomState(args.seed + 5)
    # repetitive prompts (the speculative-bench traffic class): the
    # default server's drafts fire, several tokens retire per engine
    # step, and the per-step sharding overhead amortizes accordingly
    prompts = []
    for _ in range(args.requests):
        period = int(rng.randint(1, 4))
        pat = list(rng.randint(0, args.vocab, size=period))
        reps = -(-args.prompt_tokens // period)
        prompts.append((pat * reps)[:args.prompt_tokens])

    mesh = Mesh(np.asarray(jax.devices()[:args.tp]), ("model",))
    sharded_server = _tp_server(cfg, params, args, mesh)
    on, outs_on = _run_tp_workload(sharded_server, prompts, args)
    off, outs_off = _run_tp_workload(
        _tp_server(cfg, params, args, None), prompts, args)
    mismatches = sum(a != b for a, b in zip(outs_on, outs_off))
    # real chips scale; an emulated host-platform mesh time-slices
    tp_capable = jax.default_backend() != "cpu"
    srv_stats = sharded_server.stats()
    record = {
        "bench": "serving_tp",
        "mode": "smoke" if args.smoke else "full",
        "tp": args.tp,
        "tp_capable": tp_capable,
        "backend": jax.default_backend(),
        "device_count": len(jax.devices()),
        "sharding": srv_stats["sharding"],
        "kv_pool_bytes_per_device":
            srv_stats["memory"]["pool_bytes_per_device"],
        "kv_pool_bytes_logical": srv_stats["memory"]["pool_bytes"],
        "config": {"requests": args.requests, "max_new": args.max_new,
                   "batch_size": args.batch_size,
                   "block_size": args.block_size,
                   "hidden": args.hidden, "layers": args.layers,
                   "heads": args.heads,
                   "max_context": args.max_context,
                   "vocab": args.vocab,
                   "prompt_tokens": args.prompt_tokens},
        "sharded": on,
        "unsharded": off,
        "speedup": round(on["tokens_s"] / max(off["tokens_s"], 1e-9),
                         2),
        "parity_mismatches": mismatches,
    }
    print(json.dumps(record))

    out = args.out
    if out != "-":
        if out is None:
            out = os.path.join(
                os.path.dirname(os.path.dirname(
                    os.path.abspath(__file__))),
                "BENCH_serving_tp.json")
        with open(out, "w") as f:
            json.dump(record, f, indent=1)
            f.write("\n")

    rc = 0
    if mismatches:
        print(f"FAIL: {mismatches} requests diverged between tp="
              f"{args.tp} and unsharded greedy decode",
              file=sys.stderr)
        rc = 1
    if args.smoke:
        if tp_capable and record["speedup"] < 1.0:
            # the scaling floor, armed only where chips are real:
            # sharded serving must not be slower than one chip doing
            # all the work (aggregate tokens/s scales with tp on
            # memory-bound decode; 1.0x is the conservative gate)
            print(f"FAIL: tp={args.tp} speedup {record['speedup']} "
                  "< 1.0x scaling floor on a multi-chip backend",
                  file=sys.stderr)
            rc = 1
        elif not tp_capable and record["speedup"] < 0.9:
            print(f"FAIL: tp={args.tp} regressed the single-chip "
                  f"engine ({record['speedup']}x < 0.9x) on an "
                  "emulated CPU mesh", file=sys.stderr)
            rc = 1
        if not tp_capable:
            print("note: emulated CPU mesh — tp devices time-slice "
                  "the same cores; scaling floor armed only on real "
                  "multi-chip backends", file=sys.stderr)
    return rc


def _kvq_server(cfg, params, args, quant, num_blocks=None,
                cache_dtype=None):
    import jax.numpy as jnp
    from apex_tpu.serving import InferenceServer

    # both arms run the full default stack (prefix cache + chunked
    # prefill + speculation + pipeline): quantization must compose
    # with everything that ships on, not with a stripped-down loop
    return InferenceServer(
        cfg, params, max_batch_size=args.batch_size,
        max_context=args.max_context, block_size=args.block_size,
        cache_dtype=(cache_dtype if cache_dtype is not None
                     else jnp.float32),
        kv_quant="int8" if quant else "off",
        enable_disagg=False, enable_streaming=False,
        enable_kv_offload=False,
        num_blocks=num_blocks)


def _run_kvq_workload(server, prompts, args):
    """Drive one arm over the request set, auditing every step;
    returns (outputs, stats)."""
    reqs = [server.submit(p, args.max_new) for p in prompts]
    while server.scheduler.has_work:
        _step_audited(server)
    return [list(r.generated) for r in reqs], server.stats()


def _lcp(a, b):
    n = 0
    for x, y in zip(a, b):
        if x != y:
            break
        n += 1
    return n


def run_kv_quant_mode(args):
    """The int8-KV-cache A/B (docs/serving.md, "Quantized KV cache").
    Two gates in one record (``BENCH_serving_kvquant.json``):

    - *decode-parity budget* (ALWAYS asserted, smoke or full):
      quant-on vs quant-off greedy generations over identical traffic
      on roomy fp32-compute pools; the agreement metric is the mean
      agreeing-prefix fraction, floored at the pinned budget
      (BENCH_NOTES, kv-quant decision table).  Quantization is lossy
      by design, so this is a tolerance oracle, never bit parity.
    - *capacity at fixed pool bytes* (the headline): the bf16
      production pool's byte budget re-spent on int8+scale blocks
      must yield >= 1.8x usable live-block headroom NET of the fp32
      scale sidecar — asserted from the config price math AND
      reconciled against the live arrays' actual bytes — and an
      over-committed shared-prefix workload on the two equal-byte
      pools records what the headroom buys: preemptions and
      prefix-cache evictions on the quantized arm must not exceed
      the baseline's (the ~2x-concurrency-per-HBM-byte claim,
      observed rather than asserted from geometry alone).
    """
    import jax.numpy as jnp

    from apex_tpu.serving import KVCacheConfig

    cfg, m, params = build_model(args)
    rng = np.random.RandomState(args.seed + 6)
    shared = list(rng.randint(0, args.vocab, size=16))
    prompts = []
    for i in range(args.requests):
        if i % 2 == 0:
            # shared-prefix sessions: the prefix-cache capacity half
            prompts.append(shared + list(rng.randint(
                0, args.vocab, size=8)))
        else:
            # repetitive tails: the speculation traffic class rides
            # along, so drafts/rollback run quantized too
            period = int(rng.randint(1, 4))
            pat = list(rng.randint(0, args.vocab, size=period))
            prompts.append((pat * 24)[:24])

    # -- gate 1: the decode-parity tolerance budget (roomy pools) ----
    on_srv = _kvq_server(cfg, params, args, quant=True)
    outs_on, stats_on = _run_kvq_workload(on_srv, prompts, args)
    off_srv = _kvq_server(cfg, params, args, quant=False)
    outs_off, _ = _run_kvq_workload(off_srv, prompts, args)
    total = sum(len(o) for o in outs_off)
    agree = sum(_lcp(a, b) for a, b in zip(outs_on, outs_off))
    agreement = agree / max(total, 1)

    # -- gate 2: capacity at fixed pool bytes ------------------------
    bps = -(-args.max_context // args.block_size)
    # a deliberately TIGHT baseline pool (half of full provisioning):
    # the regime where HBM bounds concurrency — the premise of the
    # whole mode
    base_blocks = args.batch_size * bps // 2 + 1
    ck = dict(num_layers=args.layers, num_heads=args.heads,
              head_dim=args.hidden // args.heads,
              block_size=args.block_size)
    base_cfg = KVCacheConfig(num_blocks=base_blocks,
                             dtype=jnp.bfloat16, **ck)
    budget = base_cfg.bytes()
    quant_bpb = KVCacheConfig(num_blocks=2, dtype=jnp.bfloat16,
                              quantize="int8", **ck).bytes_per_block
    quant_blocks = budget // quant_bpb
    headroom = (quant_blocks - 1) / (base_blocks - 1)

    base_arm = _kvq_server(cfg, params, args, quant=False,
                           num_blocks=base_blocks,
                           cache_dtype=jnp.bfloat16)
    outs_base, stats_base = _run_kvq_workload(base_arm, prompts, args)
    quant_arm = _kvq_server(cfg, params, args, quant=True,
                            num_blocks=quant_blocks,
                            cache_dtype=jnp.bfloat16)
    outs_q, stats_q = _run_kvq_workload(quant_arm, prompts, args)
    # the live arrays must actually fit the budget (price math and
    # allocation reconcile — no headroom claimed on paper only)
    live_bytes = stats_q["memory"]["pool_bytes"]
    assert live_bytes <= budget + quant_bpb, \
        f"quant pool {live_bytes}B exceeds the {budget}B budget"
    cap_agree = sum(_lcp(a, b) for a, b in zip(outs_q, outs_base)) \
        / max(sum(len(o) for o in outs_base), 1)

    def _cap(st):
        return {
            "blocks_usable": st["memory"]["blocks_usable"],
            "pool_bytes": st["memory"]["pool_bytes"],
            "bytes_per_block": st["memory"]["bytes_per_block"],
            "preemptions": st["preemptions"],
            "capacity_failures": st["requests_failed"].get(
                "requests_failed_capacity", 0),
            "blocks_live_peak": st["memory"]["blocks_live_peak"],
            "evicted_blocks": st.get("prefix_evicted_blocks", 0),
            "evictable_peak":
                st["memory"]["blocks_evictable_peak"],
            "prefix_hit_rate": st.get("prefix_hit_rate", 0.0),
        }

    record = {
        "bench": "serving_kvquant",
        "mode": "smoke" if args.smoke else "full",
        "kv_quant": "int8",
        "config": {"requests": args.requests, "max_new": args.max_new,
                   "batch_size": args.batch_size,
                   "block_size": args.block_size,
                   "hidden": args.hidden, "layers": args.layers,
                   "heads": args.heads,
                   "head_dim": args.hidden // args.heads,
                   "max_context": args.max_context,
                   "vocab": args.vocab},
        # gate 1
        "token_agreement": round(agreement, 4),
        "parity_budget": KVQ_PARITY_BUDGET,
        "quant_speculation":
            stats_on["speculation"]["accepted_tokens"],
        # gate 2
        "pool_budget_bytes": int(budget),
        "baseline_blocks_usable": base_blocks - 1,
        "quant_blocks_usable": int(quant_blocks - 1),
        "live_block_headroom": round(headroom, 3),
        "headroom_floor": KVQ_HEADROOM_FLOOR,
        "capacity_token_agreement": round(cap_agree, 4),
        "baseline_arm": _cap(stats_base),
        "quant_arm": _cap(stats_q),
    }
    print(json.dumps(record))

    out = args.out
    if out != "-":
        if out is None:
            out = os.path.join(
                os.path.dirname(os.path.dirname(
                    os.path.abspath(__file__))),
                "BENCH_serving_kvquant.json")
        with open(out, "w") as f:
            json.dump(record, f, indent=1)
            f.write("\n")

    rc = 0
    # the parity budget is ALWAYS checked — a quantization scheme
    # that moves too many tokens is rejected no matter how much
    # memory it saves (the BENCH_NOTES decision table)
    if agreement < KVQ_PARITY_BUDGET:
        print(f"FAIL: quant-on token agreement {agreement:.3f} < "
              f"{KVQ_PARITY_BUDGET} parity budget", file=sys.stderr)
        rc = 1
    if headroom < KVQ_HEADROOM_FLOOR:
        print(f"FAIL: live-block headroom {headroom:.2f}x < "
              f"{KVQ_HEADROOM_FLOOR}x at fixed pool bytes "
              f"(head_dim {args.hidden // args.heads} — the sidecar "
              "overhead shrinks as head_dim grows)", file=sys.stderr)
        rc = 1
    if args.smoke:
        # what the headroom must BUY on the over-committed workload:
        # never more memory churn than the baseline at equal bytes
        if record["quant_arm"]["preemptions"] > \
                record["baseline_arm"]["preemptions"]:
            print("FAIL: quantized arm preempted more than the "
                  "baseline at the same pool bytes", file=sys.stderr)
            rc = 1
        if record["quant_arm"]["evicted_blocks"] > \
                record["baseline_arm"]["evicted_blocks"]:
            print("FAIL: quantized arm evicted more cached blocks "
                  "than the baseline at the same pool bytes",
                  file=sys.stderr)
            rc = 1
    return rc


def _kvoff_server(cfg, params, args, offload, num_blocks):
    import jax.numpy as jnp

    from apex_tpu.serving import InferenceServer

    # both arms: identical DEVICE pool (the fixed byte budget the
    # whole mode is about), prefix cache + chunked prefill on, every
    # other axis pinned to its own mode — they differ ONLY in whether
    # evicted cache blocks demote to the host tier or die
    return InferenceServer(
        cfg, params, max_batch_size=args.batch_size,
        max_context=args.max_context, block_size=args.block_size,
        num_blocks=num_blocks,
        cache_dtype=jnp.float32, kv_quant="off", enable_disagg=False,
        enable_streaming=False,
        enable_prefix_cache=True,
        enable_chunked_prefill=True, prefill_chunk=args.chunk,
        enable_speculation=False, enable_pipeline=False,
        enable_kv_offload=offload)


def _kvoff_pass(server, prompts, args, sampling=None):
    """One pass over the session set, one request at a time (TTFT
    isolated from batching — the PR-3 methodology): returns
    (per-request TTFT seconds, outputs)."""
    ttfts, outs = [], []
    for i, p in enumerate(prompts):
        req = server.submit(p, args.max_new,
                            sampling=sampling[i] if sampling else None)
        ttft = 0.0
        while not req.generated and not req.finished:
            ttft += _step_audited(server)
        while not req.finished:
            _step_audited(server)
        ttfts.append(ttft)
        outs.append(list(req.generated))
    return ttfts, outs


def run_kv_offload_mode(args):
    """The hierarchical-KV-offload session-continuation A/B
    (docs/serving.md, "Hierarchical KV offload"; one JSON record to
    ``BENCH_serving_kvoffload.json``).

    The workload is the returning-session shape the offload tiers
    exist for: N sessions, each a distinct long prefix + short tail,
    over a device pool deliberately sized to hold only ~2.5 sessions'
    blocks — so by the time the last cold session finishes, the first
    sessions' cached prefixes have been EVICTED under pool pressure.
    Then every session RESUMES (same prompt resubmitted) and the
    median resumed-session TTFT is compared across two arms at the
    SAME device pool bytes:

    - *offload on*: eviction demoted the blocks to the host tier, so
      the resume promotes them back through the checksummed
      ``import_blocks`` path and prefills only what is missing;
    - *offload off*: eviction destroyed the blocks, so the resume
      pays the full cold chunked prefill.

    Token-for-token parity (greedy AND counter-keyed stochastic) is
    ALWAYS asserted across arms and across passes — promotion must
    move bytes, never tokens.  ``--smoke`` additionally asserts the
    >= 2x resumed-TTFT floor, that the offload arm actually promoted,
    and that the off arm's resumes were genuinely cold."""
    from apex_tpu.ops.sampling import SamplingParams

    cfg, m, params = build_model(args)
    rng = np.random.RandomState(args.seed + 7)
    sessions = [list(rng.randint(0, args.vocab,
                                 size=args.prefix_len + args.tail_len))
                for _ in range(args.requests)]

    # the fixed byte budget: ~2.5 sessions' prefix blocks (plus the
    # active request's own headroom), far below what the whole
    # session set needs — eviction MUST fire between cold passes
    session_blocks = -(-(args.prefix_len + args.tail_len)
                       // args.block_size)
    req_blocks = -(-(args.prefix_len + args.tail_len + args.max_new)
                   // args.block_size) + 2
    num_blocks = max(session_blocks * 5 // 2, req_blocks
                     + session_blocks) + 1
    assert args.requests * session_blocks > num_blocks, \
        "pool roomy enough to hold every session — nothing can evict"

    def run_arm(offload):
        server = _kvoff_server(cfg, params, args, offload, num_blocks)
        server.generate([sessions[0][:8]], max_new_tokens=2)
        server.reset_meters()
        ttft_cold, outs_cold = _kvoff_pass(server, sessions, args)
        ttft_resume, outs_resume = _kvoff_pass(server, sessions, args)
        # the stochastic rider: counter-keyed streams are pure
        # functions of (prompt, params, seed), so cross-arm parity
        # must hold through promote exactly as it does for greedy
        sampling = [SamplingParams(temperature=0.8, top_k=13,
                                   top_p=0.9, seed=args.seed + i)
                    for i in range(len(sessions))]
        _, outs_stoch = _kvoff_pass(server, sessions, args,
                                    sampling=sampling)
        return (ttft_cold, ttft_resume, outs_cold, outs_resume,
                outs_stoch, server.stats())

    (cold_on, res_on, outs_cold_on, outs_res_on,
     outs_st_on, stats_on) = run_arm(True)
    (cold_off, res_off, outs_cold_off, outs_res_off,
     outs_st_off, stats_off) = run_arm(False)

    parity = (
        sum(a != b for a, b in zip(outs_cold_on, outs_cold_off))
        + sum(a != b for a, b in zip(outs_res_on, outs_res_off))
        # greedy resume must also equal its own cold pass — the
        # promoted blocks ARE the cold prefill's bytes
        + sum(a != b for a, b in zip(outs_res_on, outs_cold_on)))
    stoch_parity = sum(a != b
                       for a, b in zip(outs_st_on, outs_st_off))

    t_on, t_off = _median(res_on), _median(res_off)
    off = stats_on["offload"]
    record = {
        "bench": "serving_kvoffload",
        "mode": "smoke" if args.smoke else "full",
        "config": {"sessions": args.requests,
                   "prefix_len": args.prefix_len,
                   "tail_len": args.tail_len,
                   "max_new": args.max_new,
                   "block_size": args.block_size,
                   "device_pool_blocks": num_blocks,
                   "session_blocks": session_blocks,
                   "chunk": args.chunk,
                   "hidden": args.hidden, "layers": args.layers,
                   "heads": args.heads,
                   "max_context": args.max_context,
                   "vocab": args.vocab, "seed": args.seed},
        "ttft_ms_resumed_offload": round(t_on * 1e3, 2),
        "ttft_ms_resumed_cold": round(t_off * 1e3, 2),
        "resume_speedup": round(t_off / max(t_on, 1e-9), 2),
        # cold-pass medians: the two arms must START equal — offload
        # costs nothing until eviction has something to demote
        "ttft_ms_first_pass_offload": round(_median(cold_on) * 1e3, 2),
        "ttft_ms_first_pass_cold": round(_median(cold_off) * 1e3, 2),
        "parity_mismatches": parity,
        "stochastic_parity_mismatches": stoch_parity,
        "offload": off,
        "evictable_bytes_peak_priced": (
            stats_on["memory"]["evictable_bytes"]),
        "cold_arm_resume_prefix_hits":
            stats_off.get("prefix_hit_requests", 0),
    }
    print(json.dumps(record))

    out = args.out
    if out != "-":
        if out is None:
            out = os.path.join(
                os.path.dirname(os.path.dirname(
                    os.path.abspath(__file__))),
                "BENCH_serving_kvoffload.json")
        with open(out, "w") as f:
            json.dump(record, f, indent=1)
            f.write("\n")

    rc = 0
    # parity is ALWAYS the gate — a fast promote that changes tokens
    # is a corruption, not a win (the BENCH_NOTES decision table)
    if parity or stoch_parity:
        print(f"FAIL: {parity} greedy + {stoch_parity} stochastic "
              "parity mismatches across the offload A/B",
              file=sys.stderr)
        rc = 1
    if args.smoke:
        if record["resume_speedup"] < 2.0:
            print(f"FAIL: resumed-session TTFT speedup "
                  f"{record['resume_speedup']} < 2.0x floor at fixed "
                  f"device pool bytes", file=sys.stderr)
            rc = 1
        if not (off["promotes_host"] + off["promotes_disk"]):
            print("FAIL: offload arm never promoted — the workload "
                  "did not exercise the tier it measures",
                  file=sys.stderr)
            rc = 1
        if off["demotes"] == 0:
            print("FAIL: offload arm never demoted — pool pressure "
                  "never reached the cache", file=sys.stderr)
            rc = 1
    return rc


def _transport_sink(eng):
    """The bench's receiver handler: alloc -> checksummed import ->
    re-export -> free, acking the re-exported leaf checksums so the
    sender can prove byte parity WITHOUT shipping the bytes back
    (socket acks carry JSON only).  This is exactly the consumer
    shape of the real hand-off/warm/promote handlers."""

    def handler(meta, payload):
        n = int(meta["n"])
        ids = eng.allocator.alloc(n)
        if ids is None:
            raise MemoryError("transport bench pool exhausted")
        try:
            eng.import_blocks(ids, payload)
            back = eng.export_blocks(ids)
        finally:
            eng.allocator.free(ids)
        return {"crc": {k: int(v) for k, v in back["crc"].items()}}

    return handler


def _run_transport_arm(send, payload, n, repeats):
    """Time ``repeats`` transfers of the same ``n``-block payload
    through ``send`` (one warmup transfer outside the window).
    Returns (blocks/s, per-transfer latency p50/p99 ms, final ack)."""
    meta = {"op": "bench", "n": n}
    ack = send(meta, payload)                  # warmup / compile
    lats = []
    t0 = time.perf_counter()
    for _ in range(repeats):
        s0 = time.perf_counter()
        ack = send(meta, payload)
        lats.append((time.perf_counter() - s0) * 1e3)
    dt = time.perf_counter() - t0
    lats.sort()
    return ({
        "blocks_s": round(n * repeats / max(dt, 1e-9), 1),
        "transfers": repeats,
        "handoff_ms": {
            "p50": round(lats[int(0.50 * (len(lats) - 1))], 3),
            "p99": round(lats[int(0.99 * (len(lats) - 1))], 3),
        },
        "wall_s": round(dt, 3),
    }, ack)


def run_transport_mode(args):
    """The KV-transport backend A/B (docs/serving.md, "KV transport";
    one JSON record to ``BENCH_serving_transport.json``): the same
    ``n``-block checksummed payload is moved repeatedly through three
    paths —

    - *direct*: the receiver handler called as a plain function (the
      pre-refactor copy: no envelope, no policy) — the baseline the
      abstraction must not tax;
    - *inprocess*: ``InProcessTransport.send`` (the default backend
      everywhere) — envelope, retry policy, breaker, and dedup ledger
      all engaged;
    - *socket*: ``SocketTransport.send`` over loopback TCP — frame
      encode, length-prefix + crc verify, decode, and the server
      thread round trip.

    Every arm's receiver re-exports what it ingested and acks the
    leaf checksums; all three acks must equal the source payload's
    (byte parity is ALWAYS asserted — a fast transport that rots
    bytes is a corruption, not a win).  ``--smoke`` floors
    inprocess/direct blocks/s >= 0.9x (the abstraction-overhead
    no-regression bar); the socket ratio is reported, never floored —
    framing and syscalls are its documented price."""
    from apex_tpu.serving import InferenceServer
    from apex_tpu.serving.transport import (InProcessTransport,
                                            SocketTransport,
                                            TransportPolicy)

    import jax.numpy as jnp

    cfg, m, params = build_model(args)
    n = args.transport_blocks
    repeats = args.transport_repeats

    def mk_server():
        # a roomy pool on both sides: the bench times block movement,
        # never allocator pressure
        return InferenceServer(
            cfg, params, max_batch_size=args.batch_size,
            max_context=args.max_context, block_size=args.block_size,
            num_blocks=3 * n + 2,
            cache_dtype=jnp.float32, kv_quant="off",
            enable_disagg=False, enable_streaming=False,
            enable_kv_offload=False, enable_speculation=False,
            enable_pipeline=False)

    rng = np.random.RandomState(args.seed + 13)
    src_server, dst_server = mk_server(), mk_server()
    # one real generate writes KV bytes into the source pool so the
    # exported payload carries live-looking data, not zeros
    src_server.generate(
        [list(rng.randint(0, args.vocab, size=args.block_size * 2))],
        max_new_tokens=8)
    src = src_server.engine
    ids = src.allocator.alloc(n)
    payload = src.export_blocks(ids)
    src.allocator.free(ids)
    handler = _transport_sink(dst_server.engine)

    direct, ack_direct = _run_transport_arm(
        lambda meta, p: handler(meta, p), payload, n, repeats)

    inproc_tr = InProcessTransport(policy=TransportPolicy())
    inproc_tr.register_peer("sink", handler)
    inproc, ack_inproc = _run_transport_arm(
        lambda meta, p: inproc_tr.send("sink", meta, p),
        payload, n, repeats)
    inproc_stats = inproc_tr.stats()
    inproc_tr.close()

    sock_tr = SocketTransport(policy=TransportPolicy())
    sock_tr.register_peer("sink", handler)     # loops back via TCP
    sock, ack_sock = _run_transport_arm(
        lambda meta, p: sock_tr.send("sink", meta, p),
        payload, n, repeats)
    sock_stats = sock_tr.stats()
    sock_tr.close()

    want = {k: int(v) for k, v in payload["crc"].items()}
    parity = sum(ack["crc"] != want
                 for ack in (ack_direct, ack_inproc, ack_sock))

    record = {
        "bench": "serving_transport",
        "mode": "smoke" if args.smoke else "full",
        "config": {"blocks_per_transfer": n, "transfers": repeats,
                   "block_size": args.block_size,
                   "hidden": args.hidden, "layers": args.layers,
                   "heads": args.heads,
                   "max_context": args.max_context,
                   "vocab": args.vocab, "seed": args.seed,
                   "payload_bytes": int(sum(
                       a.nbytes for a in payload["leaves"].values()))},
        "direct": direct,
        "inprocess": dict(inproc, stats=inproc_stats),
        "socket": dict(sock, stats=sock_stats),
        # the headline ratios: the abstraction's own tax (floored
        # under --smoke) and the socket backend's documented price
        "inprocess_vs_direct": round(
            inproc["blocks_s"] / max(direct["blocks_s"], 1e-9), 3),
        "socket_vs_inprocess": round(
            sock["blocks_s"] / max(inproc["blocks_s"], 1e-9), 3),
        "parity_mismatches": parity,
    }
    print(json.dumps(record))

    out = args.out
    if out != "-":
        if out is None:
            out = os.path.join(
                os.path.dirname(os.path.dirname(
                    os.path.abspath(__file__))),
                "BENCH_serving_transport.json")
        with open(out, "w") as f:
            json.dump(record, f, indent=1)
            f.write("\n")

    rc = 0
    if parity:
        print(f"FAIL: {parity} backend(s) acked checksums diverging "
              "from the source payload (block movement must be "
              "byte-exact on every backend)", file=sys.stderr)
        rc = 1
    if (inproc_stats["failures"] or sock_stats["failures"]
            or inproc_stats["rejects"] or sock_stats["rejects"]):
        print("FAIL: transfers failed or were rejected on a healthy "
              f"loopback (inprocess={inproc_stats}, "
              f"socket={sock_stats})", file=sys.stderr)
        rc = 1
    if args.smoke and record["inprocess_vs_direct"] < 0.9:
        print(f"FAIL: in-process transport moved blocks at "
              f"{record['inprocess_vs_direct']}x the direct copy "
              f"(< 0.9x no-regression floor)", file=sys.stderr)
        rc = 1
    return rc


def _router_fleet(cfg, params, args, kind):
    from apex_tpu.serving import RouterFleet, RouterPolicy

    import jax.numpy as jnp

    # both arms run the identical fleet — same replica geometry, same
    # full default stack per replica (prefix cache on: it is the thing
    # affinity concentrates) — differing ONLY in placement kind
    return RouterFleet(
        cfg, params, replicas=args.router,
        policy=RouterPolicy(kind=kind, seed=args.seed,
                            affinity_block=args.block_size),
        max_batch_size=args.batch_size,
        max_context=args.max_context, block_size=args.block_size,
        num_blocks=args.router_blocks, cache_dtype=jnp.float32,
        kv_quant="off", enable_disagg=False,
        enable_streaming=False, enable_kv_offload=False,
        # the elastic axis has its own arm (--elastic); pinned OFF
        # here so the placement A/B keeps a fixed-geometry fleet
        enable_elastic=False)


def _run_router_arm(cfg, params, args, kind, groups):
    """Drive one placement arm over the grouped shared-prefix
    traffic: each round submits one request per group (shared
    ``prefix_len``-token group prefix + a private tail), then runs
    the fleet idle so finished requests' blocks become evictable
    cache holds before the next round — the steady multi-session
    shape affinity exists for.  Per-replica audits every step.
    Returns (outputs in submit order, fleet stats, wall seconds)."""
    fleet = _router_fleet(cfg, params, args, kind)
    reqs = []
    t0 = time.perf_counter()
    for r in range(args.router_rounds):
        for prefix, tails in groups:
            reqs.append(fleet.submit(prefix + tails[r], args.max_new))
        while fleet.has_work:
            fleet.step()
            for rep in fleet.replicas:
                rep.server.scheduler.audit()
    wall = time.perf_counter() - t0
    outs = [list(r.generated) for r in reqs]
    st = fleet.stats()
    fleet.close()
    return outs, st, wall


def run_router_mode(args):
    """The multi-replica placement A/B (docs/serving.md,
    "Multi-replica routing"): identical grouped shared-prefix traffic
    through an N-replica RouterFleet under AFFINITY placement vs
    seeded RANDOM placement.  Affinity keeps each group's sessions on
    one replica, so the group prefix prefills once per group; random
    placement sprays a group across the fleet and re-prefills its
    prefix once per replica it touches.  The measured axis is the
    aggregate prefix-cache hit ratio; ``--smoke`` floors
    affinity >= 1.5x random.  Token-for-token parity between the two
    arms is ALWAYS asserted — placement may move work, never change
    tokens."""
    cfg, m, params = build_model(args)
    rng = np.random.RandomState(args.seed + 7)
    groups = []
    for _ in range(args.router_groups):
        prefix = list(rng.randint(0, args.vocab,
                                  size=args.prefix_len))
        tails = [list(rng.randint(0, args.vocab, size=args.tail_len))
                 for _ in range(args.router_rounds)]
        groups.append((prefix, tails))

    outs_aff, st_aff, wall_aff = _run_router_arm(
        cfg, params, args, "affinity", groups)
    outs_rnd, st_rnd, wall_rnd = _run_router_arm(
        cfg, params, args, "random", groups)
    mismatches = sum(a != b for a, b in zip(outs_aff, outs_rnd))
    tokens = sum(len(o) for o in outs_aff)

    ratio = (st_aff["prefix_hit_rate"]
             / max(st_rnd["prefix_hit_rate"], 1e-9))
    record = {
        "bench": "serving_router",
        "mode": "smoke" if args.smoke else "full",
        "replicas": args.router,
        "config": {"router_groups": args.router_groups,
                   "router_rounds": args.router_rounds,
                   "prefix_len": args.prefix_len,
                   "tail_len": args.tail_len,
                   "max_new": args.max_new,
                   "batch_size": args.batch_size,
                   "block_size": args.block_size,
                   "num_blocks": args.router_blocks,
                   "hidden": args.hidden, "layers": args.layers,
                   "heads": args.heads,
                   "max_context": args.max_context,
                   "vocab": args.vocab},
        "affinity": {
            "prefix_hit_rate": st_aff["prefix_hit_rate"],
            "prefix_hit_tokens": st_aff["prefix_hit_tokens"],
            "prefix_miss_tokens": st_aff["prefix_miss_tokens"],
            "tokens_s": round(tokens / max(wall_aff, 1e-9), 1),
            "placements": st_aff["router"]["placements"],
            "affinity_counters": st_aff["router"]["affinity"],
        },
        "random": {
            "prefix_hit_rate": st_rnd["prefix_hit_rate"],
            "prefix_hit_tokens": st_rnd["prefix_hit_tokens"],
            "prefix_miss_tokens": st_rnd["prefix_miss_tokens"],
            "tokens_s": round(tokens / max(wall_rnd, 1e-9), 1),
            "placements": st_rnd["router"]["placements"],
        },
        "hit_ratio_affinity_over_random": round(ratio, 2),
        "parity_mismatches": mismatches,
        "router": st_aff["router"],
    }
    print(json.dumps(record))

    out = args.out
    if out != "-":
        if out is None:
            out = os.path.join(
                os.path.dirname(os.path.dirname(
                    os.path.abspath(__file__))),
                "BENCH_serving_router.json")
        with open(out, "w") as f:
            json.dump(record, f, indent=1)
            f.write("\n")

    rc = 0
    if mismatches:
        print(f"FAIL: {mismatches} requests diverged between "
              "affinity and random placement — placement must never "
              "change tokens", file=sys.stderr)
        rc = 1
    if args.smoke:
        if record["affinity"]["prefix_hit_rate"] <= 0.0:
            print("FAIL: affinity arm recorded no prefix-cache hits",
                  file=sys.stderr)
            rc = 1
        if ratio < 1.5:
            print(f"FAIL: affinity/random prefix-hit ratio "
                  f"{record['hit_ratio_affinity_over_random']} < "
                  "1.5x floor", file=sys.stderr)
            rc = 1
    return rc


def _run_elastic_arm(cfg, params, args, schedule, elastic_on):
    """Drive one arm (autoscaling or fixed one-replica fleet) through
    the identical seeded flash-crowd schedule on an injected
    iteration clock (1 s per iteration — wall-clock independent, so
    the A/B is deterministic per seed).  Every arrival carries a
    ``deadline_s``; GOODPUT is the tokens of requests that finished
    HEALTHY — a deadline miss finishes ``timeout`` and earns nothing,
    a shed earns nothing, so goodput is exactly "useful tokens
    delivered within deadline"."""
    import jax.numpy as jnp

    from apex_tpu.serving import RouterFleet
    from apex_tpu.serving.elastic import AutoscalerConfig
    from apex_tpu.serving.reasons import HEALTHY_REASONS

    clock_state = {"t": 0.0}
    fleet = RouterFleet(
        cfg, params, replicas=1,
        max_batch_size=args.batch_size, max_context=args.max_context,
        block_size=args.block_size, num_blocks=args.router_blocks,
        cache_dtype=jnp.float32, max_waiting=8,
        clock=lambda: clock_state["t"],
        enable_kv_offload=False,
        enable_elastic=elastic_on,
        elastic=AutoscalerConfig(
            min_replicas=1, max_replicas=3,
            up_pressure=0.85, down_pressure=0.2, window=8,
            up_cooldown_s=25.0, down_cooldown_s=60.0,
            warm_blocks=8) if elastic_on else None)
    tracked = []
    size_peak = len(fleet.replicas)
    t0 = time.perf_counter()
    for i in range(schedule.cfg.iters):
        clock_state["t"] = float(i)
        for a in schedule.arrivals.get(i, ()):
            rr = fleet.submit(list(a.prompt), a.max_new_tokens,
                              priority=a.priority,
                              deadline_iters=a.deadline_iters,
                              deadline_s=a.deadline_s)
            tracked.append((rr, a))
        fleet.step()
        for rep in fleet.replicas:
            rep.server.scheduler.audit()
        size_peak = max(size_peak, len(fleet.replicas))
    clock_state["t"] = float(schedule.cfg.iters)
    fleet.drain()
    wall = time.perf_counter() - t0

    goodput = 0
    healthy = {}
    tally = {}
    for idx, (rr, _a) in enumerate(tracked):
        tally[rr.finish_reason] = tally.get(rr.finish_reason, 0) + 1
    for idx, (rr, _a) in enumerate(tracked):
        if rr.finish_reason in HEALTHY_REASONS:
            goodput += len(rr.generated)
            healthy[idx] = list(rr.generated)
    st = fleet.stats()
    arm = {
        "goodput_tokens": goodput,
        "submitted": len(tracked),
        "finished": dict(sorted(tally.items())),
        "size_peak": size_peak,
        "final_replicas": len(fleet.replicas),
        "scale_ups": st["elastic"].get("scale_ups", 0),
        "scale_downs": st["elastic"].get("scale_downs", 0),
        "shed_debt_tokens": fleet.shed_debt_tokens(),
        "wall_s": round(wall, 2),
    }
    fleet.close()
    return arm, healthy


def run_elastic_mode(args):
    """The elastic-fleet goodput A/B (docs/serving.md, "Elastic
    fleet"): the IDENTICAL seeded flash-crowd schedule — every
    arrival deadline-carrying — through (a) a one-replica fleet whose
    autoscaler may grow it to three, and (b) the same fleet pinned
    FIXED at one replica.  Measured axis: goodput (tokens of requests
    that finished healthy, i.e. within deadline).  ``--smoke`` floors
    elastic/fixed >= 1.25x; token-for-token parity on requests
    healthy in BOTH arms is ALWAYS asserted — capacity may change who
    gets served, never what a served request reads."""
    from apex_tpu.resilience.chaos import ChaosConfig, ChaosSchedule

    cfg, m, params = build_model(args)
    iters = args.elastic_iters
    crowd_start = iters // 4
    crowd_len = max(1, iters // 4)
    chaos_cfg = ChaosConfig(
        iters=iters, vocab=args.vocab,
        # calm baseline + a sustained crowd; every arrival carries a
        # wall deadline on the injected clock (1 s per iteration), so
        # the fixed arm's queue waits convert directly to timeouts
        arrival_rate=0.2, burst_rate=0.0,
        prompt_len=(2, 12), max_new=(4, args.max_new),
        deadline_iters_rate=0.0,
        deadline_s_rate=1.0, deadline_s=(12.0, 30.0),
        nonfinite_rate=0.0, oom_rate=0.0, crash_every=0,
        flash_crowd_iter=crowd_start, flash_crowd_len=crowd_len,
        flash_crowd_arrivals=(2, 4))
    schedule = ChaosSchedule.generate(chaos_cfg, args.seed)

    elastic, healthy_e = _run_elastic_arm(cfg, params, args,
                                          schedule, True)
    fixed, healthy_f = _run_elastic_arm(cfg, params, args,
                                        schedule, False)

    both = sorted(set(healthy_e) & set(healthy_f))
    mismatches = sum(healthy_e[i] != healthy_f[i] for i in both)
    ratio = (elastic["goodput_tokens"]
             / max(fixed["goodput_tokens"], 1e-9))

    record = {
        "bench": "serving_elastic",
        "mode": "smoke" if args.smoke else "full",
        "config": {"iters": iters,
                   "flash_crowd": [crowd_start,
                                   crowd_start + crowd_len],
                   "max_new": args.max_new,
                   "batch_size": args.batch_size,
                   "block_size": args.block_size,
                   "num_blocks": args.router_blocks,
                   "hidden": args.hidden, "layers": args.layers,
                   "heads": args.heads,
                   "max_context": args.max_context,
                   "vocab": args.vocab, "seed": args.seed},
        "elastic": elastic,
        "fixed": fixed,
        "goodput_ratio_elastic_over_fixed": round(ratio, 2),
        "parity_checked": len(both),
        "parity_mismatches": mismatches,
    }
    print(json.dumps(record))

    out = args.out
    if out != "-":
        if out is None:
            out = os.path.join(
                os.path.dirname(os.path.dirname(
                    os.path.abspath(__file__))),
                "BENCH_serving_elastic.json")
        with open(out, "w") as f:
            json.dump(record, f, indent=1)
            f.write("\n")

    rc = 0
    if mismatches:
        print(f"FAIL: {mismatches} requests healthy in both arms "
              "diverged — capacity must never change tokens",
              file=sys.stderr)
        rc = 1
    if elastic["scale_ups"] < 1:
        print("FAIL: the flash crowd never triggered a scale-up in "
              "the elastic arm", file=sys.stderr)
        rc = 1
    if args.smoke and ratio < 1.25:
        print(f"FAIL: elastic/fixed goodput ratio "
              f"{record['goodput_ratio_elastic_over_fixed']} < "
              "1.25x floor", file=sys.stderr)
        rc = 1
    return rc


def run_shared_prefix_mode(args):
    cfg, m, params = build_model(args)
    servers = _build_prefix_servers(cfg, params, args)
    record = {
        "bench": "serving_prefix",
        "mode": "smoke" if args.smoke else "full",
        "config": {"requests": args.requests, "max_new": args.max_new,
                   "batch_size": args.batch_size,
                   "block_size": args.block_size,
                   "hidden": args.hidden, "layers": args.layers,
                   "heads": args.heads,
                   "max_context": args.max_context,
                   "vocab": args.vocab,
                   "prefix_len": args.prefix_len,
                   "tail_len": args.tail_len, "chunk": args.chunk,
                   "long_prompt": args.long_prompt,
                   "repeats": args.repeats},
    }
    record.update(run_shared_prefix_ttft(servers, args))
    record.update(run_interference(servers, args))
    print(json.dumps(record))

    out = args.out
    if out != "-":
        if out is None:
            out = os.path.join(
                os.path.dirname(os.path.dirname(
                    os.path.abspath(__file__))),
                "BENCH_serving_prefix.json")
        with open(out, "w") as f:
            json.dump(record, f, indent=1)
            f.write("\n")

    rc = 0
    if record["prefix_parity_mismatches"]:
        print(f"FAIL: {record['prefix_parity_mismatches']} requests "
              "diverged between cached and cacheless greedy decode",
              file=sys.stderr)
        rc = 1
    if record["interference_parity_mismatches"]:
        print(f"FAIL: {record['interference_parity_mismatches']} "
              "requests diverged between chunked and monolithic "
              "prefill", file=sys.stderr)
        rc = 1
    if args.smoke:
        if record["ttft_speedup"] < 2.0:
            print(f"FAIL: shared-prefix TTFT speedup "
                  f"{record['ttft_speedup']} < 2.0x floor",
                  file=sys.stderr)
            rc = 1
        if record["prefix_hit_requests"] < args.requests:
            print(f"FAIL: only {record['prefix_hit_requests']}/"
                  f"{args.requests} timed requests hit the prefix "
                  "cache", file=sys.stderr)
            rc = 1
        if record["stall_ratio"] < 2.0:
            print(f"FAIL: monolithic/chunked stall ratio "
                  f"{record['stall_ratio']} < 2.0x — chunked prefill "
                  "is not bounding the decode stall", file=sys.stderr)
            rc = 1
        if record["ttft_hist_bucket_delta"] > 1:
            print(f"FAIL: TTFT histogram p50 is "
                  f"{record['ttft_hist_bucket_delta']} log-buckets "
                  "from the directly-measured median (must be <= 1)",
                  file=sys.stderr)
            rc = 1
    return rc


def main():
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--smoke", action="store_true",
                    help="CPU-safe build-matrix mode: toy config, "
                    "asserts the >=2x acceptance floor")
    ap.add_argument("--requests", type=int, default=32)
    ap.add_argument("--max-new", type=int, default=64)
    ap.add_argument("--batch-size", type=int, default=8)
    ap.add_argument("--block-size", type=int, default=16)
    ap.add_argument("--vocab", type=int, default=1024)
    ap.add_argument("--hidden", type=int, default=256)
    ap.add_argument("--layers", type=int, default=4)
    ap.add_argument("--heads", type=int, default=8)
    ap.add_argument("--max-context", type=int, default=512)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--out", default=None,
                    help="JSON record path (default: repo-root "
                    "BENCH_serving.json, or BENCH_serving_prefix.json "
                    "with --shared-prefix; '-' = stdout only)")
    ap.add_argument("--shared-prefix", action="store_true",
                    help="run the prefix-cache TTFT and long-prompt "
                    "interference workloads instead of the "
                    "continuous-vs-naive throughput compare")
    ap.add_argument("--speculative", action="store_true",
                    help="run the speculative-decoding workloads "
                    "(repetitive-suffix floor + random report) "
                    "instead of the continuous-vs-naive compare")
    ap.add_argument("--sampling", action="store_true",
                    help="stochastic-sampling A/B (docs/serving.md, "
                    "'Stochastic sampling'): seeded temperature/"
                    "top-p/top-k traffic with pipeline+speculation ON "
                    "vs the forced synchronous-logits fallback; "
                    "byte-identical same-seed replay and cross-arm "
                    "parity always asserted, --smoke floors the "
                    "step-throughput ratio (BENCH_serving_sampling."
                    "json)")
    ap.add_argument("--disagg", action="store_true",
                    help="disaggregated prefill/decode interference "
                    "A/B: decode ITL p99 under 10x long-prompt "
                    "pressure, disagg on/off vs a solo-decode floor "
                    "(BENCH_serving_disagg.json, docs/serving.md)")
    ap.add_argument("--disagg-decoders", type=int, default=4,
                    help="steady-decode requests in the disagg A/B")
    ap.add_argument("--disagg-blocks", type=int, default=None,
                    help="decode-pool blocks in the disagg arm (the "
                    "monolithic arm gets decode+prefill blocks as "
                    "one pool — equal total HBM)")
    ap.add_argument("--disagg-prefill-blocks", type=int, default=None,
                    help="prefill-pool blocks in the disagg arm")
    ap.add_argument("--disagg-prefill-concurrent", type=int, default=2,
                    help="prefill-pool concurrency (chunk launches "
                    "per step bound)")
    ap.add_argument("--disagg-arrival", type=int, default=2,
                    help="long-prompt submissions per step during the "
                    "interference window (keeps the monolithic arm's "
                    "prefill slots saturated)")
    ap.add_argument("--streaming", action="store_true",
                    help="streaming delivery A/B (docs/serving.md, "
                    "'Streaming & cancellation'): wall-clock token-"
                    "arrival gap tail with per-request TokenStreams "
                    "drained each step vs polling the identical "
                    "non-streaming server, plus the cancellation-"
                    "reclaims-capacity arm; delivered bytes always "
                    "asserted identical to Request.output, --smoke "
                    "floors delivered-ITL p99 <= 1.1x baseline "
                    "(BENCH_serving_streaming.json)")
    ap.add_argument("--pipeline", action="store_true",
                    help="run the pipelined-vs-synchronous step-loop "
                    "A/B (decode-heavy traffic, >= 1.25x "
                    "step-throughput floor under --smoke, parity "
                    "always) instead of the continuous-vs-naive "
                    "compare")
    ap.add_argument("--tp", type=int, default=None, metavar="N",
                    help="run the tensor-parallel A/B (tp=N mesh vs "
                    "unsharded over identical decode-heavy traffic; "
                    "parity always, backend-aware throughput floor "
                    "under --smoke) instead of the "
                    "continuous-vs-naive compare — emulated CPU "
                    "meshes auto-provision via "
                    "--xla_force_host_platform_device_count")
    ap.add_argument("--kv-quant", dest="kv_quant",
                    action="store_true",
                    help="run the int8-KV-cache A/B (quant-on vs "
                    "quant-off parity budget + fixed-pool-bytes "
                    "capacity headroom, >= 1.8x usable-block floor "
                    "net of the scale sidecar; docs/serving.md, "
                    "'Quantized KV cache') instead of the "
                    "continuous-vs-naive compare")
    ap.add_argument("--kv-offload", dest="kv_offload",
                    action="store_true",
                    help="run the hierarchical-KV-offload "
                    "session-continuation A/B (docs/serving.md, "
                    "'Hierarchical KV offload'): resumed-session "
                    "TTFT with evicted prefixes promoted from the "
                    "host tier vs paid as cold prefill, at the SAME "
                    "device pool bytes; parity (greedy + "
                    "counter-keyed stochastic) always, >= 2x "
                    "resumed-TTFT floor under --smoke "
                    "(BENCH_serving_kvoffload.json)")
    ap.add_argument("--transport", action="store_true",
                    help="run the KV-transport backend A/B "
                    "(docs/serving.md, 'KV transport'): the same "
                    "checksummed block payload moved through the "
                    "direct copy, the in-process transport envelope, "
                    "and the loopback-TCP socket backend — blocks/s "
                    "and per-transfer hand-off latency per arm, byte "
                    "parity via re-exported checksums always, "
                    "inprocess/direct >= 0.9x floored under --smoke "
                    "(BENCH_serving_transport.json)")
    ap.add_argument("--transport-blocks", type=int, default=None,
                    help="transport mode: KV blocks per transfer "
                    "(default: min(24, max_context // block_size) — "
                    "one import launch, the real consumers' bound)")
    ap.add_argument("--transport-repeats", type=int, default=None,
                    help="transport mode: timed transfers per arm "
                    "(default: 40 under --smoke, else 200)")
    ap.add_argument("--router", type=int, default=None, metavar="N",
                    help="run the multi-replica placement A/B "
                    "(affinity vs seeded-random routing of grouped "
                    "shared-prefix traffic through an N-replica "
                    "RouterFleet; aggregate prefix-hit ratio floored "
                    ">= 1.5x under --smoke, parity always) instead "
                    "of the continuous-vs-naive compare")
    ap.add_argument("--elastic", action="store_true",
                    help="run the elastic-fleet goodput A/B "
                    "(docs/serving.md, 'Elastic fleet'): an "
                    "identical seeded flash-crowd schedule with "
                    "deadline-carrying arrivals through an "
                    "autoscaling fleet vs the same fleet pinned at "
                    "one replica; goodput = tokens delivered within "
                    "deadline, elastic/fixed floored >= 1.25x under "
                    "--smoke, parity on both-healthy requests "
                    "always (BENCH_serving_elastic.json)")
    ap.add_argument("--elastic-iters", type=int, default=None,
                    help="elastic mode: schedule length in "
                    "iterations (default: 240 under --smoke, else "
                    "900)")
    ap.add_argument("--router-groups", type=int, default=6,
                    help="router mode: shared-prefix session groups")
    ap.add_argument("--router-rounds", type=int, default=3,
                    help="router mode: requests per group (arrive "
                    "one per group per round)")
    ap.add_argument("--router-blocks", type=int, default=None,
                    help="router mode: KV blocks per replica "
                    "(default: roomy enough to hold every group's "
                    "prefix as cache holds)")
    ap.add_argument("--spec-tokens", type=int, default=4,
                    help="max drafted tokens per verify step")
    ap.add_argument("--prompt-tokens", type=int, default=None,
                    help="speculative-mode prompt length (default: "
                    "max_context // 8)")
    ap.add_argument("--prefix-len", type=int, default=None,
                    help="shared system-prompt length in tokens "
                    "(default: max_context // 2)")
    ap.add_argument("--tail-len", type=int, default=16,
                    help="private tail length per request")
    ap.add_argument("--chunk", type=int, default=64,
                    help="prefill chunk width for the chunked arms")
    ap.add_argument("--long-prompt", type=int, default=None,
                    help="interference prompt length (default: "
                    "7/8 max_context)")
    ap.add_argument("--repeats", type=int, default=3,
                    help="interference repeats (min of maxes)")
    args = ap.parse_args()
    from apex_tpu.utils.compile_cache import enable_compile_cache
    enable_compile_cache()

    if args.smoke:
        args.requests = 8
        args.max_new = 16
        args.batch_size = 4
        args.block_size = 8
        args.vocab = 61
        args.hidden = 32
        args.layers = 2
        args.heads = 2
        args.max_context = 64
        if args.speculative:
            # long completions so the self-generated suffix settles
            # into the repetitive steady state drafts predict
            args.requests = 6
            args.max_new = 48
            args.max_context = 128
            args.prompt_tokens = 16
        if args.pipeline:
            # decode-heavy steady state with the device step sized
            # comparable to the host's per-step scheduling work — the
            # balance point where dispatch-ahead overlap pays most
            # (overlap can hide at most min(host, device) per step)
            args.requests = 16
            args.max_new = 32
            args.batch_size = 8
            args.block_size = 8
            args.vocab = 2048
            args.hidden = 128
            args.layers = 2
            args.heads = 4
            args.max_context = 64
            args.prompt_tokens = 8
        if args.streaming:
            # decode-heavy steady state: enough concurrent streams
            # that per-step fan-out work would show in the gap tail
            # if it stalled the loop, completions long enough for a
            # stable per-request gap series
            args.requests = 16
            args.max_new = 32
            args.batch_size = 8
            args.block_size = 8
            args.vocab = 61
            args.hidden = 32
            args.layers = 2
            args.heads = 2
            args.max_context = 64
            args.prompt_tokens = 8
        if args.sampling:
            # the pipeline smoke shape (the overlap balance point)
            # with longer completions so the repetitive self-suffix
            # settles and stochastic drafts get accepts at low
            # temperature
            args.requests = 12
            args.max_new = 40
            args.batch_size = 6
            args.block_size = 8
            args.vocab = 2048
            args.hidden = 128
            args.layers = 2
            args.heads = 4
            args.max_context = 128
            args.prompt_tokens = 12
        if args.tp:
            # the tp A/B wants compute large enough that partitioned
            # dispatch doesn't dominate a sub-millisecond step, with
            # heads and vocab divisible by the tp degree so the KV
            # pool head-shards and the tied wte vocab-shards
            args.requests = 6
            args.max_new = 32
            args.batch_size = 4
            args.block_size = 8
            args.vocab = 2048
            args.hidden = 128
            args.layers = 2
            args.heads = 4
            args.max_context = 128
            args.prompt_tokens = 16
        if args.kv_quant:
            # head_dim 64 (the TPU-native lane width): the fp32 scale
            # sidecar costs 4/(64+4) of an int8 block, so the
            # bf16->int8 headroom (2D/(D+4) = 1.88x) clears the 1.8x
            # floor; the over-committed capacity workload needs
            # context room for long completions
            args.requests = 8
            args.max_new = 48
            args.batch_size = 4
            args.block_size = 8
            args.vocab = 61
            args.hidden = 128
            args.layers = 2
            args.heads = 2
            args.max_context = 128
        if args.disagg:
            # a steady decode batch with free slots left for long
            # prompts to prefill through (the monolithic arm must be
            # ABLE to interleave prefills — slots-full would hide the
            # interference, not prevent it), and long prompts several
            # chunks deep so the chunk machinery is what interferes
            args.disagg_decoders = 4
            args.max_new = 48
            args.batch_size = 8
            args.block_size = 8
            args.vocab = 61
            args.hidden = 64
            args.layers = 2
            args.heads = 2
            args.max_context = 128
            args.prompt_tokens = 8
            args.chunk = 32
            args.long_prompt = 96
        if args.kv_offload:
            # the session-continuation shape: prefixes long enough
            # that a promote (host->device scatter) is decisively
            # cheaper than re-prefilling them, a pool ~2.5 sessions
            # deep so cold passes genuinely evict, still CPU-safe
            args.requests = 6
            args.max_new = 8
            args.batch_size = 4
            args.block_size = 8
            args.vocab = 61
            args.hidden = 64
            args.layers = 2
            args.heads = 2
            args.max_context = 512
            args.prefix_len = 448
            args.tail_len = 7
            args.chunk = 32
        if args.shared_prefix:
            # the prefix workloads need room for a long shared prefix
            # and a near-max-context prompt; still toy-model CPU-safe
            args.requests = 6
            args.max_new = 8
            args.hidden = 64
            args.max_context = 512
            args.prefix_len = 192
            args.tail_len = 7
            args.chunk = 32
            args.long_prompt = 448
        if args.elastic:
            # the soak's small-pool replica shape: a one-replica
            # fleet a sustained crowd genuinely overwhelms, so the
            # fixed arm's deadline misses are real and the
            # autoscaler's extra capacity is what goodput measures
            args.max_new = 12
            args.batch_size = 4
            args.block_size = 8
            args.vocab = 61
            args.hidden = 32
            args.layers = 2
            args.heads = 2
            args.max_context = 64
        if args.transport:
            # block movement, not model compute, is the measured
            # axis: a toy model keeps the one warmup generate cheap
            # while block_size x heads x hidden sizes a realistic
            # per-block byte payload
            args.max_new = 8
            args.batch_size = 4
            args.block_size = 8
            args.vocab = 61
            args.hidden = 64
            args.layers = 2
            args.heads = 2
            args.max_context = 64
        if args.router:
            # grouped multi-session traffic: few rounds keep the
            # random arm's accidental same-replica revisits rare (the
            # honest control), block-aligned prefixes keep the hit
            # accounting exact
            args.requests = 18
            args.max_new = 8
            args.batch_size = 2
            args.block_size = 8
            args.vocab = 61
            args.hidden = 32
            args.layers = 2
            args.heads = 2
            args.max_context = 128
            args.prefix_len = 48
            args.tail_len = 7

    if args.elastic:
        if args.elastic_iters is None:
            args.elastic_iters = 240 if args.smoke else 900
        if args.router_blocks is None:
            # the soak's small-pool shape: enough for the live batch
            # plus a little cache, NOT enough to absorb a crowd
            args.router_blocks = 40
        return run_elastic_mode(args)

    if args.transport:
        if args.transport_blocks is None:
            # import_blocks scatters through the blocks_per_seq-wide
            # program, so one transfer is bounded by it — exactly the
            # bound the real consumers (hand-off, warm, promote) obey
            args.transport_blocks = min(
                24, args.max_context // args.block_size)
        if args.transport_repeats is None:
            args.transport_repeats = 40 if args.smoke else 200
        return run_transport_mode(args)

    if args.router:
        if args.prefix_len is None:
            args.prefix_len = args.max_context // 4
        if args.router_blocks is None:
            # every group's prefix must survive as evictable holds
            # across rounds on whichever replicas hold it, plus live
            # decode headroom — a starved pool would measure eviction,
            # not placement
            per_prefix = -(-args.prefix_len // args.block_size)
            args.router_blocks = (
                args.router_groups * (per_prefix + 4)
                + args.batch_size * (
                    -(-args.max_context // args.block_size)) + 1)
        return run_router_mode(args)

    if args.disagg:
        if args.prompt_tokens is None:
            args.prompt_tokens = max(4, args.max_context // 8)
        if args.long_prompt is None:
            args.long_prompt = args.max_context * 3 // 4
        bps = -(-args.max_context // args.block_size)
        if args.disagg_prefill_blocks is None:
            args.disagg_prefill_blocks = (
                args.disagg_prefill_concurrent * bps + 1)
        if args.disagg_blocks is None:
            # every decode slot can hold a full-context request (the
            # solo floor must measure decode, not preemption)
            args.disagg_blocks = args.batch_size * bps + 1
        return run_disagg_mode(args)

    if args.streaming:
        if args.prompt_tokens is None:
            args.prompt_tokens = max(4, args.max_context // 8)
        return run_streaming_mode(args)

    if args.kv_quant:
        return run_kv_quant_mode(args)

    if args.kv_offload:
        if args.prefix_len is None:
            args.prefix_len = args.max_context // 2
        return run_kv_offload_mode(args)

    if args.shared_prefix:
        if args.prefix_len is None:
            args.prefix_len = args.max_context // 2
        if args.long_prompt is None:
            args.long_prompt = args.max_context * 7 // 8
        return run_shared_prefix_mode(args)

    if args.sampling:
        if args.prompt_tokens is None:
            args.prompt_tokens = max(4, args.max_context // 8)
        return run_sampling_mode(args)

    if args.speculative:
        if args.prompt_tokens is None:
            args.prompt_tokens = max(4, args.max_context // 8)
        return run_speculative_mode(args)

    if args.pipeline:
        if args.prompt_tokens is None:
            args.prompt_tokens = max(4, args.max_context // 8)
        return run_pipeline_mode(args)

    if args.tp:
        if args.prompt_tokens is None:
            args.prompt_tokens = max(4, args.max_context // 8)
        if args.heads % args.tp or args.vocab % args.tp:
            print(f"FAIL: --tp {args.tp} needs heads ({args.heads}) "
                  f"and vocab ({args.vocab}) divisible by the tp "
                  "degree", file=sys.stderr)
            return 1
        return run_tp_mode(args)

    cfg, m, params = build_model(args)
    prompts = make_prompts(args)

    cont_tps, lats, stats, cont_outs = run_continuous(
        cfg, params, prompts, args)
    naive_tps, naive_outs = run_naive(cfg, m, params, prompts, args)

    # both decoders are greedy over the same params: outputs must agree
    # token-for-token or the speedup is measuring a different model
    mismatches = sum(a != b for a, b in zip(cont_outs, naive_outs))

    def pct(v, q):
        return round(v[min(len(v) - 1, int(q * len(v)))] * 1e3, 1)

    record = {
        "bench": "serving",
        "mode": "smoke" if args.smoke else "full",
        "tokens_s_continuous": round(cont_tps, 1),
        "tokens_s_naive": round(naive_tps, 1),
        "speedup": round(cont_tps / max(naive_tps, 1e-9), 2),
        "p50_latency_ms": pct(lats, 0.50),
        "p95_latency_ms": pct(lats, 0.95),
        "latency": stats["latency"],
        # memory observability headline (docs/observability.md,
        # "Memory accounting"): pool high-watermark + fragmentation,
        # and the goodput/throughput ratio against the (default
        # no-latency-bound) SLO policy — the full blocks ride in
        # "stats" below
        "memory": {
            "blocks_usable": stats["memory"]["blocks_usable"],
            "blocks_live_peak": stats["memory"]["blocks_live_peak"],
            "occupancy_peak": stats["memory"]["occupancy_peak"],
            "frag_slots": stats["memory"]["frag_slots"],
        },
        "goodput_ratio": stats["slo"]["goodput_ratio"],
        "parity_mismatches": mismatches,
        "config": {"requests": args.requests, "max_new": args.max_new,
                   "batch_size": args.batch_size,
                   "block_size": args.block_size,
                   "hidden": args.hidden, "layers": args.layers,
                   "heads": args.heads,
                   "max_context": args.max_context,
                   "vocab": args.vocab},
        "stats": stats,
    }
    print(json.dumps(record))

    out = args.out
    if out != "-":
        if out is None:
            out = os.path.join(
                os.path.dirname(os.path.dirname(
                    os.path.abspath(__file__))), "BENCH_serving.json")
        with open(out, "w") as f:
            json.dump(record, f, indent=1)
            f.write("\n")

    if mismatches:
        print(f"FAIL: {mismatches} requests diverged between "
              "continuous and naive greedy decode", file=sys.stderr)
        return 1
    if args.smoke and record["speedup"] < 2.0:
        print(f"FAIL: smoke speedup {record['speedup']} < 2.0x "
              "acceptance floor", file=sys.stderr)
        return 1
    return 0


if __name__ == "__main__":
    sys.exit(main())
