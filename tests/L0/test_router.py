"""Multi-replica front door: routing must move WORK, never change
TOKENS.

The load-bearing oracle is 64-token greedy parity between a 3-replica
:class:`~apex_tpu.serving.RouterFleet` and the single-replica
``InferenceServer`` over the same prompts — under plain routing, a
FORCED replica failure mid-stream (queued work re-enqueued and
completed on the survivors; mid-stream victims fail
``replica_failed`` with bit-exact partial prefixes), and a rolling
``drain()`` of one replica with zero healthy-request loss.  Every
fleet step runs each replica's scheduler ``audit()`` — failover
surgery (evacuation, preempt-withdraw, re-enqueue) must leave each
replica's refcounts exactly as consistent as normal traffic does.

Router x TP (the replicas-of-shards topology): a 2-replica x tp=2
fleet — each replica GSPMD-sharded over its own disjoint 2-device
slice of the emulated 8-device mesh — must pass the same parity
oracle.

Satellites pinned here: the ``stats()["router"]`` block's exact
shape (per-replica pressure/live/finished, affinity
hit/spill/re-enqueue counters, per-replica breaker snapshots), the
:meth:`CircuitBreaker.state_snapshot` contract, the affinity index's
radix/LRU/cascade semantics, and the router chaos soak's invariants
at mini scale.

Tier budget: the tier-1 suite's 870 s wall budget is saturated, so
the non-acceptance-critical tests here (placement-policy behaviors,
threaded stepping, the ops aggregate, revive, the mini soak, the
Router x TP oracle) are ``slow``-marked — the build-matrix ``router`` axis runs this file
WITHOUT the marker filter, so they gate every build anyway.
"""

import json
import urllib.request

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from apex_tpu import models
from apex_tpu.resilience.breaker import CircuitBreaker
from apex_tpu.resilience.chaos import (
    ChaosConfig,
    ReplicaKillSwitch,
    run_router_soak,
)
from apex_tpu.serving import InferenceServer, RouterFleet, RouterPolicy
from apex_tpu.serving.router import AffinityIndex

pytestmark = pytest.mark.serving

# divisible by tp=2 (the Router x TP test vocab-shards the tied wte)
VOCAB = 64


@pytest.fixture(scope="module")
def tiny():
    cfg = models.GPTConfig(
        vocab_size=VOCAB, hidden_size=32, num_hidden_layers=2,
        num_attention_heads=4, intermediate_size=64,
        max_position_embeddings=160, hidden_dropout_prob=0.0,
        attention_probs_dropout_prob=0.0)
    m = models.GPTLMHeadModel(cfg)
    params = m.init(jax.random.PRNGKey(1),
                    jnp.ones((1, 8), jnp.int32))["params"]
    return cfg, params


@pytest.fixture(scope="module")
def oracle(tiny):
    """ONE shared single-replica reference server: every test's
    parity baseline without re-paying its compiles per test."""
    cfg, params = tiny
    server = _single(cfg, params)

    def ref(prompts, n):
        return server.generate(prompts, max_new_tokens=n)

    return ref


def _prompts(seed, n, lo=4, hi=16, shared_groups=0, shared_len=16):
    """Mixed traffic: random prompts, optionally with shared-prefix
    groups so affinity and the replica caches both engage."""
    rng = np.random.RandomState(seed)
    out = [list(rng.randint(0, VOCAB, size=int(rng.randint(lo, hi))))
           for _ in range(n)]
    for g in range(shared_groups):
        prefix = list(rng.randint(0, VOCAB, size=shared_len))
        for i in range(g, n, max(1, shared_groups)):
            out[i] = prefix + out[i][:6]
    return out


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
    # speculation is output-neutral by construction; leaving it off
    # here skips N verify-program compiles per fleet (the tier-1 wall
    # budget is saturated).  The headline parity and TP tests run the
    # FULL default stack explicitly.
    kw.setdefault("enable_speculation", False)
    return RouterFleet(cfg, params, replicas=n, **kw)


def _run_audited(fleet):
    while fleet.has_work:
        fleet.step()
        for rep in fleet.replicas:
            rep.server.scheduler.audit()


# -- the headline oracle ----------------------------------------------------


def test_three_replica_parity_64_tokens(tiny, oracle):
    """Every request routed through a 3-replica fleet produces output
    bit-exact to the single-replica engine — 64 generated tokens,
    shared-prefix groups included (so affinity placement and the
    per-replica prefix caches both fire), per-replica audits every
    step."""
    cfg, params = tiny
    prompts = _prompts(0, 9, shared_groups=3)
    ref = oracle(prompts, 64)
    fleet = _fleet(cfg, params, enable_speculation=True)
    reqs = [fleet.submit(p, 64) for p in prompts]
    _run_audited(fleet)
    st = fleet.stats()
    for i, (rr, want) in enumerate(zip(reqs, ref)):
        assert rr.finish_reason == "length"
        assert list(rr.generated) == want, \
            f"request {i} (replica {rr.replica}) diverged"
    # work actually spread: more than one replica served requests
    served = [r["finished"] for r in
              st["router"]["per_replica"].values()]
    assert sum(served) == len(prompts) and max(served) < len(prompts)
    # shared-prefix groups engaged the affinity index
    assert st["router"]["affinity"]["hits"] > 0
    fleet.close()


def test_forced_replica_failure_midstream(tiny, oracle):
    """Kill one replica's engine mid-stream: its queued work
    re-enqueues and COMPLETES bit-exactly on the survivors, its
    mid-stream requests fail ``replica_failed`` with bit-exact
    partial prefixes, every request reaches exactly one terminal
    state, and the per-replica audits stay clean through the
    evacuation."""
    cfg, params = tiny
    prompts = _prompts(1, 9, lo=5, hi=14)
    ref = oracle(prompts, 64)
    fleet = _fleet(cfg, params)
    kills = []
    for rep in fleet.replicas:
        kill = ReplicaKillSwitch(rep.server.engine)
        rep.server.engine = kill
        kills.append(kill)
    reqs = [fleet.submit(p, 64) for p in prompts]
    for _ in range(3):
        fleet.step()
    # kill a replica that holds BOTH running and queued work, so the
    # failover exercises re-enqueue and replica_failed in one shot
    victim = next(i for i, rep in enumerate(fleet.replicas)
                  if rep.server.scheduler.num_waiting
                  and rep.server.scheduler.num_running)
    kills[victim].dead = True
    _run_audited(fleet)
    st = fleet.stats()["router"]
    assert st["failovers"] >= 1
    assert st["reenqueued"] >= 1, "no queued work was re-enqueued"
    assert st["replica_failed"] >= 1, "no mid-stream victim failed"
    assert st["unplaced"] == 0
    healthy = moved = failed = 0
    for rr, want in zip(reqs, ref):
        assert rr.finished, f"request {rr.rid} never finished"
        if rr.finish_reason == "length":
            assert list(rr.generated) == want, \
                f"healthy request {rr.rid} diverged after failover"
            healthy += 1
            if rr.moves:
                moved += 1
        else:
            assert rr.finish_reason == "replica_failed"
            assert list(rr.generated) == want[:len(rr.generated)], \
                f"victim {rr.rid}'s partial output is not a prefix"
            assert rr.generated, \
                "zero-token requests must re-enqueue, not fail"
            failed += 1
    assert healthy + failed == len(prompts)
    assert moved >= 1, \
        "a re-enqueued request should have completed on a survivor"
    # terminal exactly once, on exactly one replica
    assert sum(len(rep.server.scheduler.finished)
               for rep in fleet.replicas) == len(prompts)
    assert not fleet.replicas[victim].alive


def test_rolling_drain_zero_loss(tiny, oracle):
    """Rolling restart, first half: ``drain_replica()`` moves the
    victim's queued work to the survivors and lets its in-flight work
    finish in place — ZERO healthy-request loss, all outputs
    bit-exact."""
    cfg, params = tiny
    prompts = _prompts(2, 9, lo=5, hi=14)
    ref = oracle(prompts, 64)
    fleet = _fleet(cfg, params)
    reqs = [fleet.submit(p, 64) for p in prompts]
    for _ in range(3):
        fleet.step()
    victim = next(i for i, rep in enumerate(fleet.replicas)
                  if rep.server.scheduler.num_waiting
                  and rep.server.scheduler.num_running)
    moved = fleet.drain_replica(victim)
    assert moved >= 1, "the victim had queued work to move"
    _run_audited(fleet)
    for rr, want in zip(reqs, ref):
        assert rr.finish_reason == "length", \
            f"request {rr.rid} lost to a GRACEFUL drain: " \
            f"{rr.finish_reason}"
        assert list(rr.generated) == want
    assert fleet.replica_drained(victim)
    assert fleet.stats()["router"]["replica_failed"] == 0
    fleet.close()


@pytest.mark.slow
def test_revive_with_fresh_server(tiny, oracle):
    """Rolling restart, second half: ``revive()`` with a fresh server
    returns the drained slot to rotation and it serves again."""
    cfg, params = tiny
    fleet = _fleet(cfg, params)
    fleet.generate(_prompts(2, 3), max_new_tokens=8)
    victim = 0
    fleet.drain_replica(victim)
    assert fleet.replica_drained(victim)
    fresh = _single(cfg, params, max_batch_size=2)
    fleet.revive(victim, fresh)
    assert fleet.replicas[victim].server is fresh
    assert fleet.replicas[victim].alive
    more = _prompts(3, 4)
    outs2 = fleet.generate(more, max_new_tokens=16)
    assert outs2 == oracle(more, 16)
    fleet.close()


@pytest.mark.slow
def test_router_tp_composition(tiny, oracle):
    """Router x TP (replicas-of-shards): a 2-replica fleet whose
    replicas are each GSPMD-sharded tp=2 over DISJOINT device slices
    of the emulated 8-device mesh passes the 64-token parity oracle
    vs the unsharded single-replica engine."""
    cfg, params = tiny
    prompts = _prompts(4, 6, shared_groups=2)
    ref = oracle(prompts, 64)
    fleet = _fleet(cfg, params, n=2, tp=2, enable_speculation=True)
    shard_sets = [set(rep.server.engine.mesh.devices.flat)
                  for rep in fleet.replicas]
    assert not (shard_sets[0] & shard_sets[1]), \
        "replica meshes must be disjoint device slices"
    for rep in fleet.replicas:
        assert rep.server.stats()["sharding"]["tp"] == 2
    reqs = [fleet.submit(p, 64) for p in prompts]
    _run_audited(fleet)
    for i, (rr, want) in enumerate(zip(reqs, ref)):
        assert list(rr.generated) == want, \
            f"request {i} diverged through the sharded fleet"
    fleet.close()


@pytest.mark.slow
def test_threaded_step_parity(tiny, oracle):
    """``threaded=True`` steps replicas on a thread pool; routing
    decisions and tokens are identical to sequential stepping."""
    cfg, params = tiny
    prompts = _prompts(5, 6)
    ref = oracle(prompts, 24)
    fleet = _fleet(cfg, params, threaded=True)
    outs = fleet.generate(prompts, max_new_tokens=24)
    assert outs == ref
    assert fleet.stats()["router"]["threaded"] is True
    fleet.close()


# -- placement policy -------------------------------------------------------


@pytest.mark.slow
def test_affinity_hits_spills_and_dead(tiny):
    """Affinity routes a shared-prefix follow-up to the replica that
    served the prefix; a hot target (pressure >= spill_threshold)
    SPILLS to least-pressure; a draining target counts dead and falls
    back."""
    cfg, params = tiny
    prefix = list(np.random.RandomState(6).randint(0, VOCAB, size=24))

    fleet = _fleet(cfg, params)
    a = fleet.submit(prefix + [1, 2, 3], 8)
    b = fleet.submit(prefix + [4, 5, 6], 8)
    assert b.replica == a.replica, "affinity did not stick"
    st = fleet.stats()["router"]
    assert st["placements"]["affinity_hit"] == 1
    assert st["placements"]["affinity_miss"] == 1
    _run_audited(fleet)
    fleet.close()

    # spill: anything live on the target replica clears a tiny
    # threshold, so the follow-up must land elsewhere
    fleet = _fleet(cfg, params,
                   policy=RouterPolicy(spill_threshold=0.01,
                                       affinity_block=8))
    a = fleet.submit(prefix + [1, 2, 3], 8)
    fleet.step()
    b = fleet.submit(prefix + [4, 5, 6], 8)
    assert b.replica != a.replica, "hot target must spill"
    assert fleet.stats()["router"]["affinity"]["spills"] == 1
    _run_audited(fleet)
    fleet.close()

    # dead: the index points at a draining replica (its work already
    # finished there, so nothing re-enqueues/repoints) — the match is
    # counted dead and placement falls back to a healthy replica
    fleet = _fleet(cfg, params)
    a = fleet.submit(prefix + [1, 2, 3], 8)
    _run_audited(fleet)                  # a completes on its replica
    fleet.drain_replica(a.replica)
    b = fleet.submit(prefix + [4, 5, 6], 8)
    assert b.replica != a.replica
    assert fleet.stats()["router"]["affinity"]["dead"] == 1
    _run_audited(fleet)
    fleet.close()


def test_no_placeable_replica_fast_fails(tiny):
    """All replicas draining: submit comes back already finished
    ``breaker_open`` without touching any replica, counted
    unplaced."""
    cfg, params = tiny
    fleet = _fleet(cfg, params)
    for i in range(len(fleet.replicas)):
        fleet.drain_replica(i)
    rr = fleet.submit([1, 2, 3], 8)
    assert rr.finished and rr.finish_reason == "breaker_open"
    assert rr.replica is None
    st = fleet.stats()
    assert st["requests_unplaced"] == 1
    assert all(len(rep.server.scheduler.finished) == 0
               for rep in fleet.replicas)
    fleet.close()


def test_router_policy_validation():
    """Bad policy knobs fail loudly at construction, not at the first
    placement."""
    with pytest.raises(ValueError, match="unknown placement kind"):
        RouterPolicy(kind="round_robin")
    with pytest.raises(ValueError, match="affinity_block"):
        RouterPolicy(affinity_block=0)
    with pytest.raises(ValueError, match="max_entries"):
        RouterPolicy(max_entries=0)
    # the stock policy is affinity with a sane spill threshold
    p = RouterPolicy()
    assert p.kind == "affinity" and 0.0 < p.spill_threshold


def test_fleet_constructor_validation(tiny):
    """Fleet misconfiguration fails before any replica is built."""
    cfg, params = tiny
    with pytest.raises(ValueError, match="replicas must be >= 1"):
        RouterFleet(cfg, params, replicas=0)
    with pytest.raises(ValueError, match="make_server= or tp="):
        RouterFleet(cfg, params, replicas=2, tp=2,
                    make_server=lambda i: None)


def test_affinity_index_record_counts_and_partial_chunks():
    """record() registers only FULL chunks and reports how many; a
    sub-chunk prompt registers nothing and can never match."""
    idx = AffinityIndex(block=4)
    assert idx.record([1, 2, 3], replica=0) == 0
    assert len(idx) == 0
    assert idx.record([1, 2, 3, 4, 5], replica=0) == 1
    assert idx.match([1, 2, 3, 4, 9, 9, 9, 9]) == (0, 4)
    assert idx.match([1, 2, 3]) == (None, 0)


def test_affinity_index_lru_eviction_respects_touch():
    """A chain touched by match() survives eviction longer than an
    untouched one (the LRU is recency-of-use, not insertion)."""
    idx = AffinityIndex(block=2, max_entries=2)
    idx.record([1, 1], replica=0)
    idx.record([2, 2], replica=1)
    assert idx.match([1, 1]) == (0, 2)       # touch the older chain
    idx.record([3, 3], replica=2)            # evicts the UNtouched one
    assert idx.match([1, 1]) == (0, 2)
    assert idx.match([2, 2]) == (None, 0)


def test_affinity_index_drop_replica_empty_and_missing():
    idx = AffinityIndex(block=2)
    assert idx.drop_replica(0) == 0
    idx.record([1, 1], replica=1)
    assert idx.drop_replica(0) == 0          # nothing points at 0
    assert idx.drop_replica(1) == 1
    assert len(idx) == 0


def test_replica_kill_switch_passthrough_and_refusals():
    """Alive: gated calls delegate; dead: they raise and are counted.
    Non-engine attributes always pass through."""
    class FakeEngine:
        block_size = 8

        def decode(self, *a):
            return "logits"

        def chunk_prefill(self, *a):
            return "pre"

    kill = ReplicaKillSwitch(FakeEngine())
    assert kill.decode() == "logits"
    assert kill.block_size == 8
    assert kill.kills == 0
    kill.dead = True
    with pytest.raises(RuntimeError, match="replica killed"):
        kill.decode()
    with pytest.raises(RuntimeError, match="replica killed"):
        kill.chunk_prefill()
    assert kill.kills == 2
    kill.dead = False
    assert kill.chunk_prefill() == "pre"


def test_breaker_snapshot_after_reset():
    """reset() force-closes without counting a transition; the
    snapshot reflects cleared streaks and probe state."""
    t = {"now": 0.0}
    br = CircuitBreaker(failure_threshold=1, recovery_time=5.0,
                        clock=lambda: t["now"])
    br.record_failure()
    assert br.state_snapshot()["state"] == "open"
    br.reset()
    snap = br.state_snapshot()
    assert snap["state"] == "closed"
    assert snap["failure_streak"] == 0
    assert snap["probes_out"] == 0
    # the open transition stays in the lifetime tally (reset is an
    # operator override, not history rewriting)
    assert snap["transitions"]["opened"] == 1


def test_breaker_probe_quota_defaults_to_probe_successes():
    br = CircuitBreaker(probe_successes=3)
    assert br.probe_quota == 3
    assert br.state_snapshot()["probe_quota"] == 3
    br2 = CircuitBreaker(probe_successes=2, probe_quota=5)
    assert br2.probe_quota == 5


def test_router_request_proxy_delegation():
    """The proxy mirrors the CURRENT underlying request — rebinding
    `.inner` (what failover does) switches every delegated view."""
    from apex_tpu.serving import Request
    from apex_tpu.serving.router import RouterRequest

    a = Request(prompt=[1, 2], max_new_tokens=4, priority=1)
    rr = RouterRequest(a, replica=0)
    assert rr.prompt == [1, 2] and rr.priority == 1
    assert not rr.finished and rr.replica == 0
    b = Request(prompt=[1, 2], max_new_tokens=4)
    b.record_token(7)
    b.finished = True
    b.finish_reason = "length"
    rr.inner = b
    rr.replica = 2
    rr.moves += 1
    assert rr.generated == [7]
    assert rr.finished and rr.finish_reason == "length"
    assert rr.timeline()["uid"] == b.uid
    assert "moves=1" in repr(rr)
    # rids are router-level and unique even across rebinds
    assert RouterRequest(a, None).rid != rr.rid


def test_affinity_index_units():
    """Radix semantics: chain matching, repointing, LRU bound with
    descendant cascade, drop_replica."""
    idx = AffinityIndex(block=4, max_entries=4)
    a = [1, 2, 3, 4, 5, 6, 7, 8]
    assert idx.match(a) == (None, 0)
    idx.record(a, replica=0)
    assert idx.match(a) == (0, 8)
    # deepest-match wins; partial chunk never matches
    assert idx.match(a[:7]) == (0, 4)
    assert idx.match([9] * 8) == (None, 0)
    # divergent second chunk chains off the shared first
    b = [1, 2, 3, 4, 9, 9, 9, 9]
    idx.record(b, replica=1)
    assert idx.match(b) == (1, 8)
    assert idx.match(a) == (0, 8)       # untouched
    # repoint: most recent placement wins
    idx.record(a, replica=2)
    assert idx.match(a) == (2, 8)
    # shared root chunk was repointed too
    assert idx.match(a[:4]) == (2, 4)
    # LRU bound: adding a 4th chain (root is shared, so 3 entries so
    # far) evicts the oldest; evicting the shared root cascades over
    # its descendants
    idx.record([7, 7, 7, 7, 8, 8, 8, 8], replica=0)
    assert len(idx) <= 4
    # drop_replica removes its chains (cascade keeps the map sane)
    dropped = idx.drop_replica(2)
    assert dropped >= 1
    assert idx.match(a)[0] != 2
    assert len(idx) == len(idx._map)


# -- pinned stats / snapshots ----------------------------------------------


def test_pinned_router_stats_block(tiny):
    """The exact shape of ``stats()`` and ``stats()["router"]`` —
    what the bench, the chaos soak, and the aggregate ops plane key
    on."""
    cfg, params = tiny
    fleet = _fleet(cfg, params, n=1)
    fleet.generate(_prompts(7, 2), max_new_tokens=6)
    st = fleet.stats()
    assert set(st) == {
        "router", "requests_finished", "requests_unplaced",
        "tokens_generated", "prefix_hit_tokens", "prefix_miss_tokens",
        "prefix_hit_rate", "pressure", "pressure_peak", "draining",
        "streams", "elastic", "journeys", "transport"}
    # journeys OFF: the census stays shape-stable but reads disabled
    assert st["journeys"]["enabled"] is False
    assert st["journeys"]["started"] == 0
    # elastic OFF: the minimal pinned shape (no autoscaler state)
    assert set(st["elastic"]) == {"enabled", "weights_versions",
                                  "last_rollout"}
    assert st["elastic"]["enabled"] is False
    assert st["elastic"]["weights_versions"] == {"initial": 1}
    r = st["router"]
    assert set(r) == {
        "replicas", "alive", "policy", "placements", "affinity",
        "reenqueued", "failovers", "replica_failed", "unplaced",
        "handoffs", "handoff_fallback", "handoff_torn",
        "handoff_kept_local", "handoff_transport_failed",
        "handoff_cancelled", "disagg_prefill_threshold",
        "per_replica", "steps", "threaded"}
    assert set(r["policy"]) == {"kind", "spill_threshold",
                                "affinity_block", "index_entries"}
    assert set(r["affinity"]) == {"hits", "misses", "spills", "dead",
                                  "hit_rate"}
    assert r["replicas"] == 1 and r["alive"] == 1
    # KV transport: backend-tagged, one peer per replica, envelope
    # totals present (zero on an idle in-process fleet)
    t = st["transport"]
    assert t["backend"] == "inprocess"
    assert t["peers"] == 1
    assert "replica0" in t["per_peer"]
    for key in ("attempts", "retries", "delivered", "failures",
                "dedup_hits", "deadline_exceeded", "breaker_fastfail"):
        assert t[key] == 0
    assert st["requests_finished"] == 2
    assert st["tokens_generated"] == 2 * 6
    row = r["per_replica"]["replica0"]
    assert set(row) == {
        "name", "role", "alive", "draining", "pressure",
        "live_requests", "waiting", "running", "finished", "steps",
        "step_failures", "last_error", "weights_version", "breaker"}
    assert set(row["breaker"]) == {
        "state", "failure_streak", "failure_threshold", "probes_out",
        "probe_ok", "probe_quota", "recovery_time", "current_backoff",
        "transitions"}
    assert set(row["breaker"]["transitions"]) == {
        "opened", "half_open", "closed"}
    # placements partition the submissions
    assert sum(r["placements"].values()) == 2
    fleet.close()


def test_breaker_state_snapshot():
    """The satellite contract: the snapshot tracks state, streaks,
    probe budget, and transition counts through a full
    closed -> open -> half-open -> closed episode — without a
    CounterMeter attached."""
    t = {"now": 0.0}
    br = CircuitBreaker(failure_threshold=2, recovery_time=10.0,
                        probe_successes=1, clock=lambda: t["now"])
    snap = br.state_snapshot()
    assert snap["state"] == "closed"
    assert snap["failure_streak"] == 0
    assert snap["transitions"] == {"opened": 0, "half_open": 0,
                                   "closed": 0}
    br.record_failure()
    assert br.state_snapshot()["failure_streak"] == 1
    br.record_failure()
    snap = br.state_snapshot()
    assert snap["state"] == "open"
    assert snap["transitions"]["opened"] == 1
    t["now"] = 11.0
    snap = br.state_snapshot()      # reading advances the cooldown
    assert snap["state"] == "half_open"
    assert snap["transitions"]["half_open"] == 1
    assert br.allow()
    snap = br.state_snapshot()
    assert snap["probes_out"] == 1 and snap["probe_quota"] == 1
    assert not br.allow()           # quota spent
    br.record_success()
    snap = br.state_snapshot()
    assert snap["state"] == "closed"
    assert snap["probe_ok"] == 1
    assert snap["transitions"] == {"opened": 1, "half_open": 1,
                                   "closed": 1}
    # snapshot is JSON-safe (it rides in stats() and ops bundles)
    json.dumps(snap)


# -- aggregate ops plane ----------------------------------------------------


@pytest.mark.slow
def test_fleet_ops_plane_aggregate(tiny):
    """The fleet's own ops endpoint: /healthz answers for the fleet
    (with the pressure/draining/live_requests trio), /statusz carries
    the pinned router block, /metrics exposes the router registry."""
    cfg, params = tiny
    fleet = _fleet(cfg, params, ops_port=0)
    try:
        base = f"http://127.0.0.1:{fleet.ops.port}"
        fleet.generate(_prompts(8, 3), max_new_tokens=6)
        with urllib.request.urlopen(base + "/healthz") as r:
            health = json.loads(r.read())
        assert health["status"] == "ok"
        assert health["draining"] is False
        assert health["live_requests"] == 0
        with urllib.request.urlopen(base + "/statusz") as r:
            stats = json.loads(r.read())
        assert stats["router"]["replicas"] == 3
        assert stats["requests_finished"] == 3
        with urllib.request.urlopen(base + "/metrics") as r:
            text = r.read().decode()
        assert "router_pressure" in text
        assert 'router_placements{' in text
        assert 'router_replica_pressure{replica="replica0"}' in text
    finally:
        fleet.close()


# -- the chaos soak, mini --------------------------------------------------


@pytest.mark.slow
def test_mini_router_soak(tiny):
    """The router chaos invariants at L0 scale: 160 seeded iterations
    over a killed-then-recovered replica — exactly-once terminals,
    per-replica-finished == injected, bit-exact replay, failover
    fired, victim recovered."""
    cfg, params = tiny

    def make_fleet(clock):
        return RouterFleet(
            cfg, params, replicas=3, max_batch_size=2,
            max_context=64, block_size=8, num_blocks=24,
            cache_dtype=jnp.float32, max_waiting=8, clock=clock,
            breaker_factory=lambda i: CircuitBreaker(
                failure_threshold=3, recovery_time=20.0,
                clock=clock))

    def make_replay(clock):
        return InferenceServer(
            cfg, params, max_batch_size=4, max_context=64,
            block_size=8, cache_dtype=jnp.float32, clock=clock)

    chaos_cfg = ChaosConfig(iters=160, vocab=VOCAB,
                            nonfinite_rate=0.0, oom_rate=0.0,
                            crash_every=0)
    report = run_router_soak(make_fleet, chaos_cfg, seed=0,
                             kill_iter=40, recover_iter=80,
                             make_replay=make_replay)
    assert report["failovers"] >= 1
    assert report["unplaced"] == 0
    assert sum(report["per_replica_finished"].values()) \
        == report["submitted"]
    assert report["bit_exact_checked"] > 0
    assert report["victim_breaker"]["state"] == "closed"
