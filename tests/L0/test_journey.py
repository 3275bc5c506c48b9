"""Fleet-wide request journeys: the cross-replica correlation plane.

The load-bearing property is CAUSAL MERGE DETERMINISM: every hop's
sequence number is issued by the ONE :class:`JourneyContext` that
travels with the request, so merging per-replica logs sorts on
``seq`` alone — no wall-clock comparison across replicas, identical
output under any clock skew and any log iteration order.  A COMPLETE
journey has exactly one ``finish`` hop and a gap-free ``1..N``
sequence — the exactly-once reconciliation the chaos soaks assert per
finished rid (``docs/observability.md``, "Request journeys &
exemplars").

Integration halves ride the serving oracles this plane instruments:
a forced replica kill must leave the moved request's journey with an
adjacent ``evacuate`` -> ``reenqueue`` hop pair (and stay complete),
a torn cross-replica hand-off must journal ``handoff_torn`` ->
``handoff_fallback`` and still reconcile, an offload promote stamps
its block count, and the TTFT/ITL exemplar tables must resolve their
worst-bucket rids to renderable journeys.  The disabled path is
pinned zero-allocation (``NULL_JOURNEY_LOG``), and
``stats()["journeys"]`` keeps its pinned shape either way.

Tier budget: the fleet-building tests (torn hand-off, ops endpoint,
fleet metrics) are ``slow``-marked — the build-matrix ``journey``
axis runs this file WITHOUT the marker filter, so they gate every
build anyway.
"""

import json
import tracemalloc
import urllib.error
import urllib.request

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from apex_tpu import models
from apex_tpu.observability import (
    JourneyContext,
    JourneyLog,
    NULL_JOURNEY_LOG,
    NullJourneyLog,
    dump_journeys,
    journeys_census,
    merge_exemplars,
    merge_journeys,
    resolve_journeys,
)
from apex_tpu.resilience.chaos import ReplicaKillSwitch
from apex_tpu.serving import InferenceServer, RouterFleet

pytestmark = pytest.mark.serving

VOCAB = 61

CENSUS_KEYS = {"enabled", "started", "finished", "open", "hops",
               "dropped", "exemplars"}


class FakeClock:
    def __init__(self, start=0.0):
        self.now = float(start)

    def __call__(self):
        return self.now

    def advance(self, dt):
        self.now += dt


# -- the context + log units (no jax) --------------------------------------


def test_context_issues_contiguous_seqs_and_log_stamps_core_fields():
    clock = FakeClock(5.0)
    it = [3]
    log = JourneyLog(replica="replica7", iter_source=lambda: it[0],
                     clock=clock)
    ctx = log.start(42)
    assert isinstance(ctx, JourneyContext)
    assert ctx.rid == 42 and ctx.seq == 0
    log.hop(ctx, "submit", priority=1)
    it[0] = 4
    clock.advance(1.5)
    log.hop(ctx, "route", to="replica1")
    hops = log.hops_for(42)
    assert [h["seq"] for h in hops] == [1, 2]
    assert hops[0] == {"rid": 42, "seq": 1, "replica": "replica7",
                       "iter": 3, "t": 5.0, "kind": "submit",
                       "priority": 1}
    # detail keys ride along WITHOUT clobbering the core fields — the
    # recording convention is to=/src=, never replica=/rid=/seq=
    assert hops[1]["replica"] == "replica7"
    assert hops[1]["to"] == "replica1"
    assert hops[1]["iter"] == 4 and hops[1]["t"] == 6.5
    # finish closes the journey in the census
    log.hop(ctx, "finish", reason="eos")
    c = log.census()
    assert c["started"] == 1 and c["finished"] == 1 and c["open"] == 0
    assert c["hops"] == 3 and c["dropped"] == 0


def test_merge_orders_by_seq_never_by_clock():
    """Adversarial clocks: the replica's injected clock runs BEHIND
    the router's, so wall-time ordering would interleave the journey
    wrong.  The merge must order on the context-issued seq alone and
    be byte-identical under any log order."""
    router = JourneyLog(replica="router", clock=FakeClock(100.0))
    replica = JourneyLog(replica="replica0", clock=FakeClock(1.0))
    ctx = router.start(7)
    router.hop(ctx, "submit")                  # seq 1 @ t=100
    router.hop(ctx, "route", to="replica0")    # seq 2 @ t=100
    replica.hop(ctx, "enqueue", uid=0)         # seq 3 @ t=1 (!)
    replica.hop(ctx, "admit", uid=0)           # seq 4 @ t=1
    replica.hop(ctx, "finish", reason="eos")   # seq 5 @ t=1
    a = merge_journeys([router, replica])
    b = merge_journeys([replica, router])
    assert list(a) == [7] and list(b) == [7]
    assert json.dumps(a[7].as_dict(), sort_keys=True) == \
        json.dumps(b[7].as_dict(), sort_keys=True)
    j = a[7]
    assert [h["seq"] for h in j.hops] == [1, 2, 3, 4, 5]
    assert [h["kind"] for h in j.hops] == \
        ["submit", "route", "enqueue", "admit", "finish"]
    assert j.complete
    assert j.finish_reason == "eos"
    assert j.replicas == ["router", "replica0"]
    # rid filter returns just the one journey
    only = merge_journeys([router, replica], rid=7)
    assert list(only) == [7]
    assert merge_journeys([router, replica], rid=99) == {}
    # null logs contribute nothing
    assert merge_journeys([NULL_JOURNEY_LOG]) == {}


def test_completeness_detects_gaps_and_double_finish():
    log = JourneyLog(replica="r")
    ctx = log.start(1)
    log.hop(ctx, "submit")
    log.hop(ctx, "finish", reason="eos")
    assert merge_journeys([log])[1].complete
    # a torn journey: a hop drawn from the context but recorded on a
    # replica whose log we lost — the seq gap must read INCOMPLETE
    torn = JourneyLog(replica="r")
    tctx = torn.start(2)
    torn.hop(tctx, "submit")
    tctx.next_hop()                           # a hop that went missing
    torn.hop(tctx, "finish", reason="eos")
    assert not merge_journeys([torn])[2].complete
    # two finishes (a double-terminal bug) must also read INCOMPLETE
    dbl = JourneyLog(replica="r")
    dctx = dbl.start(3)
    dbl.hop(dctx, "finish", reason="eos")
    dbl.hop(dctx, "finish", reason="eos")
    assert not merge_journeys([dbl])[3].complete
    # and a journey with no finish at all
    open_ = JourneyLog(replica="r")
    octx = open_.start(4)
    open_.hop(octx, "submit")
    assert not merge_journeys([open_])[4].complete


def test_capacity_evicts_oldest_and_counts_drops():
    log = JourneyLog(replica="r", capacity=2)
    for rid in (1, 2, 3):
        log.hop(log.start(rid), "submit")
    assert log.rids() == [2, 3]
    assert log.hops_for(1) == []
    assert log.census()["dropped"] == 1
    with pytest.raises(ValueError):
        JourneyLog(capacity=0)


def test_exemplar_worst_wins_per_bucket_and_merges():
    a = JourneyLog(replica="a")
    a.exemplar("ttft", 4, 0.5, rid=1)
    a.exemplar("ttft", 4, 0.9, rid=2)    # worse -> wins
    a.exemplar("ttft", 4, 0.7, rid=3)    # better -> ignored
    a.exemplar("ttft", 9, 3.0, rid=4)
    b = JourneyLog(replica="b")
    b.exemplar("ttft", 4, 1.1, rid=5)    # fleet-wide worst for b4
    b.exemplar("itl", 2, 0.1, rid=6)
    assert a.exemplars()["ttft"]["4"] == {"value": 0.9, "rid": 2}
    merged = merge_exemplars([a, b])
    assert merged["ttft"]["4"] == {"value": 1.1, "rid": 5}
    assert merged["ttft"]["9"] == {"value": 3.0, "rid": 4}
    assert merged["itl"]["2"] == {"value": 0.1, "rid": 6}


def test_census_shape_pinned_enabled_and_disabled():
    assert set(JourneyLog().census()) == CENSUS_KEYS
    null = NullJourneyLog().census()
    assert set(null) == CENSUS_KEYS
    assert null["enabled"] is False
    # the aggregate census keeps the same pinned shape, and
    # all-disabled collapses to the null census
    log = JourneyLog(replica="r")
    log.hop(log.start(1), "finish")
    agg = journeys_census([log, NULL_JOURNEY_LOG])
    assert set(agg) == CENSUS_KEYS
    assert agg["started"] == 1 and agg["finished"] == 1
    assert journeys_census([NULL_JOURNEY_LOG]) == null
    # the bundle member carries census + stringified-rid journeys
    d = dump_journeys([log])
    assert set(d) == {"census", "journeys"}
    assert d["journeys"]["1"]["complete"]


def test_resolve_journeys_values():
    for v in (None, "", "0", "off", "none", "false", "no", False):
        assert resolve_journeys(v) is False
    for v in ("1", "on", "true", "yes", True):
        assert resolve_journeys(v) is True
    with pytest.raises(ValueError):
        resolve_journeys("maybe")


def test_disabled_path_allocates_nothing_per_hop():
    """The journeys-off hot path: every stamping site short-circuits
    on ``enabled``/``ctx is None`` before building anything, and the
    null log itself allocates nothing per call."""
    null = NULL_JOURNEY_LOG
    assert null.start(1) is None
    assert null.enabled is False
    assert null.census()["enabled"] is False
    # warm up any lazy interpreter state first
    for _ in range(10):
        null.hop(None, "enqueue", uid=1)
        null.exemplar("ttft", 3, 0.5, 1)
    # the hot loop holds no per-hop memory (the NULL_TRACER pin's
    # shape): retained growth over 10k disabled hops stays under one
    # small transient object
    tracemalloc.start()
    base = tracemalloc.get_traced_memory()[0]
    for i in range(10_000):
        if null.enabled:                   # the call-site guard shape
            null.hop(None, "enqueue", uid=i)
        null.exemplar("ttft", 3, 0.5, i)
        null.start(i)
    cur, peak = tracemalloc.get_traced_memory()
    tracemalloc.stop()
    assert cur - base < 2048, "disabled journey log retained memory"
    assert peak - base < 8192, "disabled journey log allocated per hop"


# -- serving integration ---------------------------------------------------


@pytest.fixture(scope="module")
def tiny():
    cfg = models.GPTConfig(
        vocab_size=VOCAB, hidden_size=32, num_hidden_layers=2,
        num_attention_heads=2, intermediate_size=64,
        max_position_embeddings=160, hidden_dropout_prob=0.0,
        attention_probs_dropout_prob=0.0)
    m = models.GPTLMHeadModel(cfg)
    params = m.init(jax.random.PRNGKey(1),
                    jnp.ones((1, 8), jnp.int32))["params"]
    return cfg, params


def _single(cfg, params, **kw):
    kw.setdefault("cache_dtype", jnp.float32)
    kw.setdefault("max_batch_size", 4)
    kw.setdefault("max_context", 128)
    kw.setdefault("block_size", 8)
    return InferenceServer(cfg, params, **kw)


def _fleet(cfg, params, n=3, **kw):
    kw.setdefault("cache_dtype", jnp.float32)
    kw.setdefault("max_batch_size", 2)
    kw.setdefault("max_context", 128)
    kw.setdefault("block_size", 8)
    kw.setdefault("enable_speculation", False)
    kw.setdefault("enable_journeys", True)
    return RouterFleet(cfg, params, replicas=n, **kw)


def _prompts(seed, n, lo=4, hi=16):
    rng = np.random.RandomState(seed)
    return [list(rng.randint(0, VOCAB, size=int(rng.randint(lo, hi))))
            for _ in range(n)]


def test_single_server_journey_end_to_end(tiny):
    """Bare-server journeys: submit -> enqueue/admit/first_token/
    finish, rid == uid, complete, census reconciles, and the
    request's timeline carries the rid."""
    cfg, params = tiny
    server = _single(cfg, params, enable_journeys=True)
    reqs = [server.submit(p, 6) for p in _prompts(3, 3)]
    while server.has_work:
        server.step()
    cen = server.stats()["journeys"]
    assert set(cen) == CENSUS_KEYS
    assert cen["enabled"] is True
    assert cen["started"] == 3 and cen["finished"] == 3
    assert cen["open"] == 0 and cen["dropped"] == 0
    for req in reqs:
        j = server.journey(req.uid)
        assert j is not None and j["complete"], j
        kinds = [h["kind"] for h in j["hops"]]
        assert kinds[0] == "enqueue"
        assert "admit" in kinds and "first_token" in kinds
        assert kinds[-1] == "finish"
        assert j["finish_reason"] == req.finish_reason
        assert req.timeline()["rid"] == req.uid
    assert server.journey(10 ** 9) is None
    # exemplars link the worst TTFT/ITL bucket to a renderable journey
    ex = cen["exemplars"]
    assert "ttft" in ex and ex["ttft"], ex
    for obs in ex["ttft"].values():
        linked = server.journey(obs["rid"])
        assert linked is not None and linked["complete"]


def test_journeys_off_leaves_legacy_shapes_alone(tiny):
    """The default server: no journey context on requests, no "rid"
    in timelines, and the pinned census reads disabled — shape-stable
    but inert."""
    cfg, params = tiny
    server = _single(cfg, params)
    req = server.submit(_prompts(4, 1)[0], 4)
    while server.has_work:
        server.step()
    assert req.journey is None
    assert "rid" not in req.timeline()
    cen = server.stats()["journeys"]
    assert set(cen) == CENSUS_KEYS
    assert cen["enabled"] is False and cen["hops"] == 0


def test_failover_journey_records_evacuate_reenqueue_pair(tiny):
    """Kill a replica holding queued work: the re-enqueued request's
    merged journey must carry an ADJACENT evacuate -> reenqueue hop
    pair naming the victim and the survivor, stay complete, and the
    mid-stream victims' journeys must finish ``replica_failed`` —
    the acceptance scenario of the journey plane."""
    cfg, params = tiny
    fleet = _fleet(cfg, params)
    kills = []
    for rep in fleet.replicas:
        kill = ReplicaKillSwitch(rep.server.engine)
        rep.server.engine = kill
        kills.append(kill)
    reqs = [fleet.submit(p, 24) for p in _prompts(1, 9, lo=5, hi=14)]
    for _ in range(3):
        fleet.step()
    victim = next(i for i, rep in enumerate(fleet.replicas)
                  if rep.server.scheduler.num_waiting
                  and rep.server.scheduler.num_running)
    victim_name = fleet.replicas[victim].name
    kills[victim].dead = True
    while fleet.has_work:
        fleet.step()
    st = fleet.stats()
    assert st["router"]["reenqueued"] >= 1
    moved = failed = 0
    for rr in reqs:
        j = fleet.journey(rr.rid)
        assert j is not None, f"rid {rr.rid} has no journey"
        assert j["complete"], (rr.rid, j)
        kinds = [h["kind"] for h in j["hops"]]
        if "reenqueue" in kinds:
            i = kinds.index("reenqueue")
            assert kinds[i - 1] == "evacuate", kinds
            assert j["hops"][i - 1]["src"] == victim_name
            assert j["hops"][i]["to"] != victim_name
            # the journey spans router + both replicas it touched
            assert victim_name in j["replicas"]
            assert j["hops"][i]["to"] in j["replicas"]
            moved += 1
        if j["finish_reason"] == "replica_failed":
            failed += 1
    assert moved >= 1, "no journey recorded the failover hop pair"
    assert failed >= 1, "no victim journey finished replica_failed"
    # census reconciles: every submitted rid started AND finished
    cen = st["journeys"]
    assert cen["started"] == len(reqs)
    assert cen["finished"] == len(reqs)
    fleet.close()


@pytest.mark.slow
def test_torn_handoff_journey_reconciles(tiny):
    """A torn cross-replica hand-off payload: the journey journals
    handoff_torn then handoff_fallback (monolithic re-placement) and
    still reconciles to ONE complete journey — the torn-transfer
    half of the exactly-once reconciliation."""
    cfg, params = tiny
    fleet = RouterFleet(cfg, params, replicas=2, disagg_prefill=1,
                        max_batch_size=4, max_context=64,
                        block_size=4, cache_dtype=jnp.float32,
                        enable_journeys=True)
    pe = fleet.replicas[0].server.prefill_engine
    real = pe.export_blocks

    def corrupt(ids):
        p = real(ids)
        name = next(iter(p["leaves"]))
        p["leaves"][name] = p["leaves"][name].copy()
        p["leaves"][name].flat[0] += 1
        return p

    pe.export_blocks = corrupt
    rng = np.random.RandomState(10)
    longs = [list(rng.randint(0, VOCAB, size=30)) for _ in range(4)]
    fleet.generate(longs, max_new_tokens=8)
    st = fleet.stats()
    assert st["router"]["handoff_torn"] >= 1
    journeys = merge_journeys(fleet._journey_logs())
    torn = [j for j in journeys.values()
            if "handoff_torn" in j.counts()]
    assert torn, "no journey recorded the torn hand-off"
    for j in torn:
        assert j.complete, j.as_dict()
        kinds = [h["kind"] for h in j.hops]
        i = kinds.index("handoff_torn")
        assert "handoff_fallback" in kinds[i:], kinds
    # every journey in the run reconciled exactly once
    assert all(j.complete for j in journeys.values())
    assert sum(j.counts().get("handoff_torn", 0)
               for j in journeys.values()) \
        == st["router"]["handoff_torn"]
    fleet.close()


def test_offload_promote_journey_stamps_block_counts(tiny):
    """Session-resume traffic over a tiny offload-backed pool: the
    resumed sessions' journeys must carry offload_promote hops whose
    block counts sum to the tier's promote counters."""
    cfg, params = tiny
    server = _single(
        cfg, params, max_batch_size=2, num_blocks=13,
        enable_prefix_cache=True,
        enable_kv_offload=True, kv_offload_host_bytes=8 << 20,
        enable_journeys=True)
    rng = np.random.RandomState(7)
    prompts = [list(rng.randint(0, VOCAB, size=43)) for _ in range(4)]
    for _pass in range(2):
        for p in prompts:
            server.submit(p, 6)
            while server.has_work:
                server.step()
    off = server.stats()["offload"]
    assert off["promotes_host"] > 0, "workload never promoted"
    journeys = merge_journeys([server.journeys])
    promoted = [j for j in journeys.values()
                if "offload_promote" in j.counts()]
    assert promoted, "no journey recorded a promote hop"
    assert all(j.complete for j in journeys.values())
    stamped = sum(h.get("blocks", 0) for j in journeys.values()
                  for h in j.hops if h["kind"] == "offload_promote")
    assert stamped == off["promotes_host"] + off["promotes_disk"]


@pytest.mark.slow
def test_fleet_ops_journey_endpoint_and_fleet_metrics(tiny):
    """The ops-plane surfaces: GET /debug/journey/<rid> renders the
    merged journey (404 unknown, 400 malformed), /statusz carries the
    fleet journey census, and /metrics/fleet merges every replica's
    registry under per-replica labels with ONE HELP/TYPE per family
    (the Prometheus-valid fleet aggregation)."""
    import os
    import sys
    sys.path.insert(0, os.path.join(os.path.dirname(os.path.dirname(
        os.path.dirname(os.path.abspath(__file__)))), "tools"))
    from ops_probe import check_prometheus_text

    cfg, params = tiny
    fleet = _fleet(cfg, params, ops_port=0)
    try:
        base = f"http://127.0.0.1:{fleet.ops.port}"
        reqs = [fleet.submit(p, 6) for p in _prompts(8, 3)]
        while fleet.has_work:
            fleet.step()
        with urllib.request.urlopen(
                f"{base}/debug/journey/{reqs[0].rid}") as r:
            j = json.loads(r.read())
        assert j["rid"] == reqs[0].rid and j["complete"]
        assert [h["kind"] for h in j["hops"]][0] == "submit"
        for path, code in (("/debug/journey/999999", 404),
                           ("/debug/journey/zzz", 400)):
            with pytest.raises(urllib.error.HTTPError) as ei:
                urllib.request.urlopen(base + path)
            assert ei.value.code == code
        with urllib.request.urlopen(base + "/statusz") as r:
            stats = json.loads(r.read())
        assert set(stats["journeys"]) == CENSUS_KEYS
        assert stats["journeys"]["started"] == 3
        with urllib.request.urlopen(base + "/metrics/fleet") as r:
            assert "version=0.0.4" in r.headers.get("Content-Type")
            text = r.read().decode()
        assert check_prometheus_text(text) == []
        assert 'replica="replica0"' in text
        assert 'replica="replica2"' in text
        assert "router_pressure" in text
    finally:
        fleet.close()


@pytest.mark.slow
def test_journeys_disabled_fleet_ops_endpoint_answers_409(tiny):
    cfg, params = tiny
    fleet = _fleet(cfg, params, n=2, enable_journeys=False,
                   ops_port=0)
    try:
        base = f"http://127.0.0.1:{fleet.ops.port}"
        with pytest.raises(urllib.error.HTTPError) as ei:
            urllib.request.urlopen(base + "/debug/journey/0")
        assert ei.value.code == 409
        assert b"disabled" in ei.value.read()
    finally:
        fleet.close()
