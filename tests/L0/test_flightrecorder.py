"""Flight recorder, postmortem bundles, SLO/goodput, memory accounting.

The deep-observability acceptance oracles (``docs/observability.md``):

- **headline**: a chaos-soak invariant violation (forced via
  ``ChaosConfig.force_violation_iter``) auto-writes a postmortem
  bundle whose flight-recorder steps, metrics snapshot, and Chrome
  trace all parse and cross-reconcile — recorder step count equals
  the engine's step counters, and per-request slices reconstruct each
  request's admit → finish path — gated through
  ``tools/postmortem.py --assert-complete`` (the ``postmortem``
  build-matrix axis runs the CLI twin);
- the disabled recorder path adds ZERO allocations per step
  (tracemalloc-bounded, the ``NULL_TRACER`` contract);
- ``stats()`` carries pinned ``slo`` (attainment per priority class,
  goodput/throughput ratio, shed debt) and ``memory`` (occupancy,
  high-watermarks, fragmentation, lookahead accounting) blocks;
- ``SLOTracker`` classification against injectable-clock timelines:
  TTFT/decode bounds, deadline misses, refused-vs-served routing,
  shed debt;
- breaker-open transitions and ``InferenceServer.audit()`` failures
  auto-dump bundles;
- recording never changes behavior: the same seeded soak produces
  identical outputs recorder-on vs recorder-off.
"""

import json
import os
import sys
import tracemalloc

import jax
import jax.numpy as jnp
import pytest

from apex_tpu import models
from apex_tpu.observability import (
    NULL_FLIGHT_RECORDER,
    FlightRecorder,
    MetricsRegistry,
    SLOPolicy,
    SLOTargets,
    SLOTracker,
    write_postmortem,
)
from apex_tpu.resilience import CircuitBreaker
from apex_tpu.resilience.chaos import ChaosConfig, run_soak
from apex_tpu.serving import InferenceServer
from apex_tpu.serving.scheduler import Request

pytestmark = pytest.mark.serving

VOCAB = 61

REPO = os.path.dirname(os.path.dirname(
    os.path.dirname(os.path.abspath(__file__))))
sys.path.insert(0, os.path.join(REPO, "tools"))


@pytest.fixture(scope="module")
def tiny():
    cfg = models.GPTConfig(
        vocab_size=VOCAB, hidden_size=32, num_hidden_layers=2,
        num_attention_heads=2, intermediate_size=64,
        max_position_embeddings=128, hidden_dropout_prob=0.0,
        attention_probs_dropout_prob=0.0)
    m = models.GPTLMHeadModel(cfg)
    params = m.init(jax.random.PRNGKey(1),
                    jnp.ones((1, 8), jnp.int32))["params"]
    return cfg, params


def _server(cfg, params, **kw):
    kw.setdefault("cache_dtype", jnp.float32)
    kw.setdefault("max_batch_size", 2)
    kw.setdefault("max_context", 64)
    kw.setdefault("block_size", 8)
    return InferenceServer(cfg, params, **kw)


class FakeClock:
    def __init__(self):
        self.now = 0.0

    def __call__(self):
        return self.now

    def advance(self, dt):
        self.now += dt


# -- headline: forced violation -> bundle that cross-reconciles -----------


@pytest.mark.chaos
def test_forced_violation_autowrites_reconciling_bundle(tiny, tmp_path):
    """The postmortem pipeline end-to-end: a forced chaos invariant
    violation must fail the soak AND leave a bundle whose three
    artifacts parse and cross-reconcile — flight step count == the
    metrics snapshot's serving_step_s count, strictly increasing
    iterations, and per-request slices that reconstruct each
    admit→finish path — verified both directly and through the
    ``tools/postmortem.py --assert-complete`` gate."""
    cfg, params = tiny
    pm_dir = str(tmp_path / "pm")

    def make_server(clock):
        return InferenceServer(
            cfg, params, max_batch_size=4, max_context=64,
            block_size=4, num_blocks=40, cache_dtype=jnp.float32,
            max_waiting=8, clock=clock,
            flight_recorder=FlightRecorder(capacity=4096),
            breaker=CircuitBreaker(failure_threshold=3,
                                   recovery_time=25.0,
                                   probe_successes=2, clock=clock))

    chaos_cfg = ChaosConfig(iters=120, vocab=VOCAB,
                            force_violation_iter=80)
    with pytest.raises(AssertionError, match="finished twice"):
        run_soak(make_server, chaos_cfg, seed=0,
                 postmortem_dir=pm_dir)
    bundle = os.path.join(pm_dir, "invariant_violation")
    assert os.path.isdir(bundle)

    manifest = json.load(open(os.path.join(bundle, "manifest.json")))
    metrics = json.load(open(os.path.join(bundle, "metrics.json")))
    trace = json.load(open(os.path.join(bundle, "trace.json")))
    steps = [json.loads(ln) for ln in
             open(os.path.join(bundle, "flight.jsonl"))]

    # step accounting reconciles three ways: manifest vs flight log vs
    # the engine-step histogram in the metrics snapshot
    assert manifest["reason"] == "invariant_violation"
    assert manifest["steps_in_bundle"] == len(steps)
    assert manifest["steps_recorded"] == \
        len(steps) + manifest["steps_dropped"]
    assert metrics["serving_step_s"]["count"] == \
        manifest["steps_recorded"]
    assert "error" in manifest["extra"]
    assert isinstance(trace["traceEvents"], list)

    iters = [r["iter"] for r in steps]
    assert iters == sorted(set(iters)), "iters must strictly increase"

    # per-request reconstruction: every finished-with-admission uid has
    # admit <= finish, and finishes exactly once in the window
    admit_at, finish_at = {}, {}
    for rec in steps:
        for uid in rec["admitted"]:
            admit_at.setdefault(uid, rec["iter"])
        for f in rec["finished"]:
            assert f["uid"] not in finish_at, \
                f"request {f['uid']} finished twice in the flight log"
            finish_at[f["uid"]] = rec["iter"]
    assert finish_at, "no finishes recorded before the violation"
    overlap = set(admit_at) & set(finish_at)
    assert overlap, "no admit->finish path reconstructable"
    for uid in overlap:
        assert admit_at[uid] <= finish_at[uid]

    # memory occupancy in every record is internally consistent
    usable = 39
    for rec in steps:
        m = rec["memory"]
        assert 0 <= m["live"] <= usable
        assert m["free"] + m["live"] + m["evictable"] == usable

    # and the CLI gate agrees
    import postmortem as pm_cli
    assert pm_cli.main([bundle, "--assert-complete"]) == 0
    assert pm_cli.main([bundle, "--last-n-steps", "5"]) == 0
    # per-request slice mode renders the overlap uid's path
    uid = sorted(overlap)[0]
    assert pm_cli.main([bundle, "--request", str(uid)]) == 0


@pytest.mark.chaos
def test_recorder_never_changes_behavior(tiny):
    """Recording is observation only: the same seeded soak produces
    the identical report (requests, outcomes, bit-exact counts)
    recorder-on vs recorder-off."""
    cfg, params = tiny

    def make(recorder):
        def make_server(clock):
            return InferenceServer(
                cfg, params, max_batch_size=4, max_context=64,
                block_size=4, num_blocks=40, cache_dtype=jnp.float32,
                max_waiting=8, clock=clock,
                flight_recorder=recorder,
                breaker=CircuitBreaker(failure_threshold=3,
                                       recovery_time=25.0,
                                       probe_successes=2, clock=clock))
        return make_server

    def make_replay(clock):
        # roomy pool, unbounded queue: the bit-exactness oracle
        return InferenceServer(
            cfg, params, max_batch_size=4, max_context=64,
            block_size=4, cache_dtype=jnp.float32, clock=clock)

    chaos_cfg = ChaosConfig(iters=120, vocab=VOCAB)
    on = run_soak(make(FlightRecorder()), chaos_cfg, seed=3,
                  make_replay=make_replay)
    off = run_soak(make(None), chaos_cfg, seed=3,
                   make_replay=make_replay)
    assert on["flight_steps"] > 0 and off["flight_steps"] == 0
    for key in ("submitted", "finished", "bit_exact_checked",
                "prefix_checked", "injected", "preemptions"):
        assert on[key] == off[key], key


# -- disabled path: zero allocations per step ------------------------------


def test_disabled_recorder_allocates_nothing_per_step():
    """The NULL pattern contract: the serve loop guards record
    assembly on ``recorder.enabled``, so with the null recorder 10k
    step-records-worth of the hot path allocate nothing."""
    assert NULL_FLIGHT_RECORDER.enabled is False
    assert NULL_FLIGHT_RECORDER.records() == ()
    assert NULL_FLIGHT_RECORDER.steps_recorded == 0
    NULL_FLIGHT_RECORDER.record({"warm": 1})      # no-op, drops it
    assert NULL_FLIGHT_RECORDER.records() == ()
    tracemalloc.start()
    base = tracemalloc.get_traced_memory()[0]
    for _ in range(10_000):
        if NULL_FLIGHT_RECORDER.enabled:          # the step() guard
            NULL_FLIGHT_RECORDER.record({"iter": 0})
    cur, peak = tracemalloc.get_traced_memory()
    tracemalloc.stop()
    assert cur - base < 2048, "disabled recorder retained memory"
    assert peak - base < 8192, "disabled recorder allocated per step"


def test_ring_bound_and_dump(tmp_path):
    rec = FlightRecorder(capacity=4)
    for i in range(10):
        rec.record({"iter": i})
    assert rec.steps_recorded == 10
    assert rec.dropped == 6
    assert [r["iter"] for r in rec.records()] == [6, 7, 8, 9]
    path = rec.dump_jsonl(str(tmp_path / "f.jsonl"))
    lines = [json.loads(ln) for ln in open(path)]
    assert [r["iter"] for r in lines] == [6, 7, 8, 9]
    rec.clear()
    assert rec.steps_recorded == 0 and rec.records() == ()
    with pytest.raises(ValueError):
        FlightRecorder(capacity=0)


def test_write_postmortem_without_registry_or_tracer(tmp_path):
    """A bundle is always structurally complete: no registry -> empty
    metrics dict, disabled tracer -> empty-but-valid Chrome trace."""
    rec = FlightRecorder()
    rec.record({"iter": 1})
    man = write_postmortem(str(tmp_path / "b"), recorder=rec,
                           reason="unit")
    assert man["steps_in_bundle"] == 1
    assert json.load(open(tmp_path / "b" / "metrics.json")) == {}
    tr = json.load(open(tmp_path / "b" / "trace.json"))
    assert tr["traceEvents"] == []


# -- stats(): pinned slo + memory blocks ----------------------------------


def test_stats_slo_and_memory_blocks_pinned(tiny):
    """The new stats() surface the bench/dashboards key on: pinned
    ``slo`` and ``memory`` keys ride alongside every pre-existing
    block."""
    cfg, params = tiny
    server = _server(cfg, params)
    server.generate([[1, 2, 3], [4, 5, 6, 7]], max_new_tokens=4)
    st = server.stats()
    slo = st["slo"]
    assert not {"goodput_tokens", "total_tokens", "goodput_ratio",
                "by_priority", "debt"} - slo.keys()
    assert slo["total_tokens"] == 8
    # stock policy: healthy finishes are goodput
    assert slo["goodput_tokens"] == 8 and slo["goodput_ratio"] == 1.0
    cls = slo["by_priority"][0]
    assert cls["requests"] == 2 and cls["attained"] == 2
    assert cls["attainment"] == 1.0
    assert slo["debt"] == {"shed_requests": 0, "shed_tokens": 0}
    mem = st["memory"]
    assert not {"blocks_usable", "blocks_free", "blocks_live",
                "blocks_live_peak", "blocks_evictable",
                "blocks_evictable_peak", "occupancy", "occupancy_peak",
                "frag_slots", "frag_frac", "lookahead_granted_blocks",
                "lookahead_rolled_back_blocks", "pool_bytes",
                "pool_bytes_per_device", "bytes_per_block",
                "cache_dtype", "quantize",
                "compute_dtype"} - mem.keys()
    assert mem["blocks_live_peak"] >= 1
    # quantization off on this server: storage == compute dtype, the
    # per-block price is sidecar-free, and byte totals reconcile
    assert mem["quantize"] is None
    assert mem["cache_dtype"] == mem["compute_dtype"]
    assert mem["pool_bytes"] == \
        server.engine.cache_cfg.num_blocks * mem["bytes_per_block"]
    assert mem["occupancy_peak"] == pytest.approx(
        mem["blocks_live_peak"] / mem["blocks_usable"], abs=1e-3)
    assert mem["pool_bytes"] > 0
    # recorder off by default: flight block says so, zero steps
    assert st["flight"] == {"enabled": False, "steps_recorded": 0,
                            "dropped": 0}
    assert st["trace_dropped_events"] == 0


def test_memory_accounting_partition_holds_during_run(tiny):
    """free + live + evictable must partition the usable pool at
    every step (the allocator's three-state invariant, now surfaced
    as numbers)."""
    cfg, params = tiny
    server = _server(cfg, params, flight_recorder=FlightRecorder())
    server.generate([[i, i + 1, i + 2] for i in range(6)],
                    max_new_tokens=6)
    usable = server.engine.allocator.cfg.num_blocks - 1
    for rec in server.recorder.records():
        m = rec["memory"]
        assert m["free"] + m["live"] + m["evictable"] == usable
    st = server.stats()["memory"]
    assert st["blocks_free"] + st["blocks_live"] \
        + st["blocks_evictable"] == usable
    assert st["blocks_live_peak"] <= usable
    # speculation ran: lookahead accounting moved
    assert st["lookahead_granted_blocks"] >= \
        st["lookahead_rolled_back_blocks"]


# -- SLO tracker units -----------------------------------------------------


def _req(priority=0, max_new=8, reason="length", submitted=0.0,
         admitted=1.0, first=2.0, finished=10.0, tokens=8):
    r = Request(prompt=[1, 2, 3], max_new_tokens=max_new,
                priority=priority)
    r.generated = list(range(tokens))
    r.finished = True
    r.finish_reason = reason
    r.submitted_at = submitted
    r.admitted_at = admitted
    r.first_token_at = first
    r.finished_at = finished
    return r


def test_slo_tracker_latency_bounds_and_goodput():
    reg = MetricsRegistry()
    pol = SLOPolicy(targets={0: SLOTargets(ttft_s=3.0,
                                           decode_token_s=2.0)},
                    default=SLOTargets())
    t = SLOTracker(pol, registry=reg)
    # ttft 2.0 <= 3.0, decode (10-2)/7 ~ 1.14 <= 2.0 -> attained
    assert t.observe(_req()) is True
    # ttft 5.0 > 3.0 -> missed, its tokens are throughput not goodput
    assert t.observe(_req(first=5.0, finished=12.0)) is False
    st = t.as_stats()
    assert st["total_tokens"] == 16
    assert st["goodput_tokens"] == 8
    assert st["goodput_ratio"] == 0.5
    c0 = st["by_priority"][0]
    assert (c0["ttft_met"], c0["ttft_missed"]) == (1, 1)
    assert c0["attainment"] == 0.5
    # attainment gauge lives in the registry per class
    snap = reg.snapshot()
    assert snap['serving_slo_attainment{priority="0"}']["value"] == 0.5
    assert snap["serving_goodput_tokens"]["value"] == 8
    assert snap["serving_served_tokens"]["value"] == 16


def test_slo_tracker_deadline_and_refused_routing():
    t = SLOTracker()
    # timeout: served (counts requests), deadline missed, not attained
    assert t.observe(_req(reason="timeout")) is False
    # shed: refused -> debt side, not a served request
    shed = _req(reason="shed", tokens=2, max_new=10)
    assert t.observe(shed) is False
    # rejected: refused, no debt (never held resources)
    assert t.observe(_req(reason="rejected", tokens=0)) is False
    st = t.as_stats()
    c0 = st["by_priority"][0]
    assert c0["requests"] == 1           # only the timeout was served
    assert c0["deadline_missed"] == 1
    assert c0["shed_requests"] == 1
    assert c0["shed_tokens"] == 8        # 10 budget - 2 generated
    assert st["debt"] == {"shed_requests": 1, "shed_tokens": 8}


def test_slo_tracker_per_class_isolation():
    pol = SLOPolicy(targets={0: SLOTargets(ttft_s=1.0)},
                    default=SLOTargets())
    t = SLOTracker(pol)
    t.observe(_req(priority=0, first=5.0))    # misses class-0 ttft
    t.observe(_req(priority=2, first=5.0))    # class 2: no bound, ok
    st = t.as_stats()
    assert st["by_priority"][0]["attainment"] == 0.0
    assert st["by_priority"][2]["attainment"] == 1.0
    assert st["by_priority"][0]["ttft_target_s"] == 1.0
    assert st["by_priority"][2]["ttft_target_s"] is None


def test_server_slo_with_wall_clock_targets(tiny):
    """End-to-end on the injectable server clock: a tight TTFT budget
    fails attainment, a loose one passes — same run, same timeline."""
    cfg, params = tiny
    clock = FakeClock()

    class SteppingClock:
        """Advances 1s per read so every timeline edge is distinct."""

        def __call__(self):
            clock.advance(1.0)
            return clock.now

    pol = SLOPolicy(default=SLOTargets(ttft_s=1e-6))
    server = _server(cfg, params, clock=SteppingClock(),
                     slo_policy=pol)
    server.generate([[1, 2, 3]], max_new_tokens=3)
    st = server.stats()["slo"]
    assert st["by_priority"][0]["ttft_missed"] == 1
    assert st["goodput_ratio"] == 0.0
    assert st["total_tokens"] == 3


# -- auto-dump paths -------------------------------------------------------


def test_audit_failure_dumps_bundle(tiny, tmp_path):
    cfg, params = tiny
    pm = str(tmp_path / "pm")
    server = _server(cfg, params, postmortem_dir=pm)
    assert server.recorder.enabled        # resolved on by the dir
    server.generate([[1, 2, 3]], max_new_tokens=2)
    server.audit()                        # healthy: no dump
    assert not os.path.exists(pm) or not os.listdir(pm)
    # corrupt the free-list mirror so the audit genuinely trips
    alloc = server.engine.allocator
    alloc._free_set.discard(alloc._free[0])
    with pytest.raises(AssertionError):
        server.audit()
    bundles = os.listdir(pm)
    assert len(bundles) == 1 and bundles[0].startswith("audit_failure")
    man = json.load(open(os.path.join(pm, bundles[0],
                                      "manifest.json")))
    assert man["reason"] == "audit_failure"
    assert "error" in man["extra"]


def test_breaker_open_transition_dumps_bundle(tiny, tmp_path):
    """A breaker trip is the canonical 'what led up to this' moment:
    the open transition must leave a bundle holding the preceding
    steps."""
    cfg, params = tiny
    pm = str(tmp_path / "pm")
    clock = FakeClock()
    breaker = CircuitBreaker(failure_threshold=1, recovery_time=1e9,
                             clock=clock)

    class PoisonEngine:
        """Delegates everything; poisons decode logits to NaN."""

        def __init__(self, inner):
            self.inner = inner

        def decode(self, *a, **kw):
            import numpy as np
            out = np.asarray(self.inner.decode(*a, **kw))
            return out * float("nan")

        def __getattr__(self, name):
            return getattr(self.inner, name)

    # pipeline off: PoisonEngine poisons decode logits, which the
    # pipelined loop bypasses via the fused sampled program
    server = _server(cfg, params, clock=clock, breaker=breaker,
                     postmortem_dir=pm, enable_speculation=False,
                     enable_pipeline=False)
    server.engine = PoisonEngine(server.engine)
    server.submit([1, 2, 3], max_new_tokens=4)
    while server.scheduler.has_work:
        server.step()
    assert server.breaker.state == "open"
    bundles = [d for d in os.listdir(pm)
               if d.startswith("breaker_open")]
    assert len(bundles) == 1
    steps = [json.loads(ln) for ln in
             open(os.path.join(pm, bundles[0], "flight.jsonl"))]
    assert steps and steps[-1]["breaker"] == "open"


def test_dump_postmortem_on_demand(tiny, tmp_path):
    cfg, params = tiny
    server = _server(cfg, params, flight_recorder=FlightRecorder())
    server.generate([[1, 2, 3]], max_new_tokens=2)
    man = server.dump_postmortem(str(tmp_path / "b"), reason="debug",
                                 extra={"note": "x"})
    assert man["reason"] == "debug"
    assert man["extra"]["note"] == "x"
    assert man["extra"]["engine"]["blocks_usable"] == \
        server.engine.allocator.cfg.num_blocks - 1
    assert man["steps_in_bundle"] == len(server.recorder.records())


def test_reset_meters_realigns_flight_window(tiny, tmp_path):
    """reset_meters() must clear the flight ring along with the step
    histograms — otherwise a post-reset bundle's step accounting can
    never reconcile against serving_step_s (the --assert-complete
    contract)."""
    cfg, params = tiny
    server = _server(cfg, params, flight_recorder=FlightRecorder())
    server.generate([[1, 2, 3]], max_new_tokens=3)
    assert server.recorder.steps_recorded > 0
    server.reset_meters()
    assert server.recorder.steps_recorded == 0
    server.generate([[4, 5, 6]], max_new_tokens=3)
    man = server.dump_postmortem(str(tmp_path / "b"))
    metrics = json.load(open(tmp_path / "b" / "metrics.json"))
    assert metrics["serving_step_s"]["count"] == man["steps_recorded"]
    import postmortem as pm_cli
    assert pm_cli.main([str(tmp_path / "b"),
                        "--assert-complete"]) == 0


# -- phase-composition split (disaggregation observability) ----------------


_PHASE_FAMILIES = {
    "prefill_launches": {"chunk_prefill", "chunk_prefill_sampled",
                         "chunk_prefill_stoch"},
    "decode_launches": {"decode", "decode_sampled", "decode_stoch"},
    "verify_launches": {"verify", "verify_sampled", "verify_stoch"},
}


def test_phase_split_recorded_and_reconciles_with_programs(tiny):
    """Every recorded step carries a ``phase`` composition block
    (prefill tokens vs decode tokens vs verify columns), and the
    per-family launch sums reconcile EXACTLY with the per-program
    accounting — the recorder and ``stats()["programs"]`` each saw
    every launch once (tools/postmortem.py --assert-complete runs the
    same check on bundles)."""
    cfg, params = tiny
    server = _server(cfg, params, flight_recorder=FlightRecorder())
    prompts = [[1, 2, 3] * 6, [5, 6, 7, 8], [9] * 11]
    server.generate(prompts, max_new_tokens=8)
    steps = server.recorder.records()
    assert steps and all(isinstance(r.get("phase"), dict)
                         for r in steps)
    # token-level sanity: every prompt token went through a prefill
    # program exactly once (no preemption in this roomy run)
    assert sum(r["phase"]["prefill_tokens"] for r in steps) == \
        sum(len(p) for p in prompts)
    table = server.programs.table()
    for field, fams in _PHASE_FAMILIES.items():
        flight_n = sum(r["phase"][field] for r in steps)
        calls = sum(row["calls"] for key, row in table.items()
                    if key.split("[")[0] in fams)
        assert flight_n == calls, (field, flight_n, calls)
    # decode+verify actually decoded every generated token
    assert sum(r["phase"]["decode_tokens"] for r in steps) > 0


def test_phase_split_off_with_null_recorder(tiny):
    """The disabled path binds no phase dict at all (the zero-alloc
    contract extends to the new block)."""
    cfg, params = tiny
    server = _server(cfg, params)
    assert server.recorder is NULL_FLIGHT_RECORDER
    server.generate([[1, 2, 3]], max_new_tokens=3)
    assert server._phase is None


# -- inter-token-latency SLO bound ----------------------------------------


def test_slo_itl_p99_bound_classifies():
    """The ITL attainment bound: a request whose per-token gap p99
    exceeds its class bound misses (itl_missed), one within it
    attains — independently of the per-request-average decode bound
    (head-of-line interference breaks the tail first)."""
    pol = SLOPolicy(targets={0: SLOTargets(itl_p99_s=0.1)})
    tr = SLOTracker(pol)

    def req_with_gaps(gaps):
        r = Request(prompt=[1], max_new_tokens=4)
        r.generated = [1, 2, 3]
        r.finished = True
        r.finish_reason = "length"
        r.submitted_at, r.admitted_at = 0.0, 0.0
        r.first_token_at, r.finished_at = 0.1, 1.0
        r.itl_gaps = list(gaps)
        return r

    good = req_with_gaps([0.01] * 60)
    assert "itl_p99_s" in good.timeline()
    assert tr.observe(good) is True
    bad = req_with_gaps([0.01] * 10 + [0.5])   # p99 == the 0.5 tail
    assert bad.timeline()["itl_p99_s"] == pytest.approx(0.5)
    assert tr.observe(bad) is False
    cls = tr.as_stats()["by_priority"][0]
    assert cls["itl_p99_target_s"] == 0.1
    assert (cls["itl_met"], cls["itl_missed"]) == (1, 1)
    assert cls["attained"] == 1
    # one long gap among MANY short ones sits under p99: attains
    ok_tail = req_with_gaps([0.01] * 199 + [0.5])
    assert ok_tail.timeline()["itl_p99_s"] == pytest.approx(0.01)
    assert tr.observe(ok_tail) is True


def test_server_records_itl_and_slo_itl_attainment(tiny):
    """End-to-end: the server stamps per-token gaps on the request
    timeline and ``stats()`` carries both the itl_ms histogram and the
    per-class ITL attainment against a configured bound."""
    cfg, params = tiny
    pol = SLOPolicy(default=SLOTargets(itl_p99_s=1e9))
    server = _server(cfg, params, slo_policy=pol)
    reqs = server.generate([[1, 2, 3], [4, 5, 6, 7]],
                           max_new_tokens=6, return_requests=True)
    for r in reqs:
        tl = r.timeline()
        assert "itl_p99_s" in tl and "itl_max_s" in tl
        assert len(r.itl_gaps) == len(r.generated) - 1
    st = server.stats()
    assert st["latency"]["itl_ms"]["count"] == \
        sum(len(r.itl_gaps) for r in reqs)
    cls = st["slo"]["by_priority"][0]
    assert cls["itl_met"] == 2 and cls["itl_missed"] == 0
