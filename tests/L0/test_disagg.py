"""Disaggregated prefill/decode pools: the phase-separation oracle.

The headline contract is BIT-EXACT greedy parity: a server with
``enable_disagg=True`` — every prefill in a dedicated prefill pool,
finished KV handed to the decode pool through the cross-pool block
copy — must generate token-for-token what the monolithic engine
generates, across chunked prefills, shared-prefix COW hits, forced
preemption, hand-off deferral under a starved decode pool, and torn /
delayed hand-off transfers.  The copy is byte-preserving and attention
only ever reads a request's own context, so any divergence means a
block moved wrong, not a tolerance.

The cross-replica half rides the same oracle: a prefill-role replica
exports checksummed block payloads, a decode replica ingests them
(``InferenceServer.ingest_handoff``), and a torn payload must be
DETECTED whole and fall back to a bit-identical monolithic placement
(``docs/serving.md``, "Disaggregated prefill/decode").
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from apex_tpu import models
from apex_tpu.serving import InferenceServer, RouterFleet
from apex_tpu.serving.kv_cache import block_slots, read_slots

pytestmark = pytest.mark.serving

VOCAB = 61


@pytest.fixture(scope="module")
def tiny():
    cfg = models.GPTConfig(
        vocab_size=VOCAB, hidden_size=32, num_hidden_layers=2,
        num_attention_heads=2, intermediate_size=64,
        max_position_embeddings=64, hidden_dropout_prob=0.0,
        attention_probs_dropout_prob=0.0)
    m = models.GPTLMHeadModel(cfg)
    params = m.init(jax.random.PRNGKey(1),
                    jnp.ones((1, 8), jnp.int32))["params"]
    return cfg, params


def _server(cfg, params, disagg, **kw):
    kw.setdefault("max_batch_size", 3)
    kw.setdefault("max_context", 64)
    kw.setdefault("block_size", 4)
    kw.setdefault("cache_dtype", jnp.float32)
    if disagg:
        kw.setdefault("disagg_prefill_blocks", 20)
    return InferenceServer(cfg, params, enable_disagg=disagg, **kw)


def _prompts(seed=0, n=6, shared=0):
    rng = np.random.RandomState(seed)
    head = list(rng.randint(0, VOCAB, size=shared)) if shared else []
    return [head + list(rng.randint(0, VOCAB,
                                    size=int(rng.randint(2, 24))))
            for _ in range(n)]


def _audited_generate(server, prompts, max_new, eos_id=None):
    reqs = [server.submit(p, max_new, eos_id) for p in prompts]
    while server.has_work:
        server.step()
        server.audit()
    return [list(r.generated) for r in reqs]


# -- same-host: bit-exact parity ------------------------------------------


def test_disagg_parity_vs_monolithic(tiny):
    """64 tokens of greedy decode through the disaggregated pools ==
    the monolithic engine, with both pools' refcount audits after
    every step (pipelined default stack on both sides)."""
    cfg, params = tiny
    prompts = _prompts(0, n=6)
    want = _audited_generate(_server(cfg, params, False), prompts, 12,
                             eos_id=7)
    got = _audited_generate(_server(cfg, params, True), prompts, 12,
                            eos_id=7)
    assert got == want
    # and the hand-off actually ran (this is not monolithic in
    # disguise): every surviving multi-token request moved pools
    srv = _server(cfg, params, True)
    _audited_generate(srv, prompts, 12, eos_id=7)
    st = srv.stats()
    assert st["disagg"]["enabled"] is True
    assert st["disagg"]["handoff"]["requests"] >= 1


@pytest.mark.parametrize("pipeline,speculation", [(True, False),
                                                  (False, True),
                                                  (False, False)])
def test_disagg_parity_across_fast_path_corners(tiny, pipeline,
                                                speculation):
    """The decode pool keeps its fast paths: parity holds with the
    pipelined loop and speculation toggled independently (the (True,
    True) corner is the default stack, covered above)."""
    cfg, params = tiny
    prompts = _prompts(1, n=4)
    kw = dict(enable_pipeline=pipeline,
              enable_speculation=speculation)
    want = _audited_generate(_server(cfg, params, False, **kw),
                             prompts, 10)
    got = _audited_generate(_server(cfg, params, True, **kw),
                            prompts, 10)
    assert got == want


def test_disagg_shared_prefix_cow_and_cache_retention(tiny):
    """The prefill pool doubles as the warm shared-prefix cache:
    handed-off blocks survive as evictable holds, a repeat submission
    prefix-hits them (incl. the whole-context COW corner), and parity
    holds throughout."""
    cfg, params = tiny
    shared = list(range(1, 13))          # 3 full blocks at bs=4
    prompts = [shared + [20 + i] for i in range(4)] + [shared, shared]
    want = _audited_generate(_server(cfg, params, False), prompts, 8)
    srv = _server(cfg, params, True)
    got = _audited_generate(srv, prompts, 8)
    assert got == want
    st = srv.stats()
    assert st["prefix_hit_requests"] >= 1
    assert st["prefix_cow_blocks"] >= 1
    # the holds live in the PREFILL pool (the decode pool reports a
    # clean free/live partition of its own)
    assert st["disagg"]["prefill_blocks_evictable"] >= 1
    assert st["memory"]["blocks_evictable"] == 0


def test_disagg_handoff_defers_until_decode_pool_has_room(tiny):
    """A starved decode pool defers the hand-off — blocks stay intact
    on the prefill side, the queue drains FIFO as slots free — and
    output is still bit-exact."""
    cfg, params = tiny
    prompts = _prompts(2, n=6)
    want = _audited_generate(_server(cfg, params, False), prompts, 10)
    # decode pool: 2 slots, barely more blocks than 2 live requests
    srv = _server(cfg, params, True, max_batch_size=2, num_blocks=16)
    got = _audited_generate(srv, prompts, 10)
    assert got == want
    assert srv.stats()["disagg"]["handoff"].get("deferred", 0) >= 1


def test_disagg_preempted_decode_request_reprefills(tiny):
    """A decode-pool preemption victim re-enters through the PREFILL
    pool's queue and resumes bit-identically (recompute preemption,
    cross-pool edition)."""
    cfg, params = tiny
    prompts = _prompts(3, n=4)
    kw = dict(enable_speculation=False)   # one token per step, so the
    #                                       victim is still mid-stream
    want = _audited_generate(_server(cfg, params, False, **kw),
                             prompts, 10)
    srv = _server(cfg, params, True, **kw)
    reqs = [srv.submit(p, 10) for p in prompts]
    # let someone reach the decode pool, then forcibly preempt a
    # mid-stream decode-pool request
    victim = None
    while victim is None:
        srv.step()
        srv.audit()
        victim = next((r for r in srv.scheduler.running.values()
                       if r.generated and not r.prefilling), None)
    if victim.uid in srv.scheduler.inflight:
        srv._flush_window()          # can't preempt a launched row
    if victim.running:
        srv.scheduler.preempt(victim)
        # the disagg loop moves decode-pool waiting into the prefill
        # queue at the next step; nothing to do here
    while srv.has_work:
        srv.step()
        srv.audit()
    assert [list(r.generated) for r in reqs] == want
    assert victim.preemptions >= 1


def test_disagg_torn_and_delayed_handoff_copy_is_bit_stable(tiny):
    """The hand-off fault class: a torn cross-pool copy (a PREFIX of
    the blocks really moves, then MemoryError) and a delayed one
    (nothing moves) must both retry whole next step with no token
    corruption — the copy is idempotent over the full table."""
    cfg, params = tiny
    prompts = _prompts(4, n=4)
    want = _audited_generate(_server(cfg, params, False), prompts, 10)
    srv = _server(cfg, params, True)
    real = srv.engine.copy_blocks_from
    faults = {"torn": 2, "delayed": 2}

    def faulty(src_engine, pairs):
        if faults["torn"] > 0:
            faults["torn"] -= 1
            if len(pairs) > 1:
                real(src_engine, pairs[:len(pairs) // 2])
            raise MemoryError("test: torn hand-off")
        if faults["delayed"] > 0:
            faults["delayed"] -= 1
            raise MemoryError("test: delayed hand-off")
        return real(src_engine, pairs)

    srv.engine.copy_blocks_from = faulty
    got = _audited_generate(srv, prompts, 10)
    assert got == want
    assert faults == {"torn": 0, "delayed": 0}
    assert srv.stats()["oom_events"] == 4


def test_disagg_drain_and_evacuate(tiny):
    """Lifecycle across the pools: a mid-flight drain finishes every
    request bit-identically; evacuate() re-queues zero-token work
    (incl. prefill-pool requests), fails mid-stream work, and leaves
    both pools audit-clean."""
    cfg, params = tiny
    prompts = _prompts(5, n=6)
    want = _audited_generate(_server(cfg, params, False), prompts, 10)
    srv = _server(cfg, params, True)
    reqs = [srv.submit(p, 10) for p in prompts]
    for _ in range(3):
        srv.step()
    srv.drain()
    assert [list(r.generated) for r in reqs] == want
    srv2 = _server(cfg, params, True)
    reqs2 = [srv2.submit(p, 10) for p in prompts]
    for _ in range(4):
        srv2.step()
    requeueable, failed = srv2.evacuate()
    srv2.audit()
    assert len(requeueable) + len(failed) + \
        sum(1 for r in reqs2 if r.finished
            and r.finish_reason != "replica_failed") == len(reqs2)
    for r in requeueable:
        assert not r.generated and not r.finished
    for r in failed:
        assert r.finish_reason == "replica_failed"
    assert not srv2._handoff


def test_disagg_stats_block_pinned(tiny):
    """The ``stats()["disagg"]`` surface the bench/dashboards key on —
    and ``{"enabled": False}`` (exactly) on a monolithic server."""
    cfg, params = tiny
    mono = _server(cfg, params, False)
    mono.generate(_prompts(6, n=2), max_new_tokens=4)
    assert mono.stats()["disagg"] == {"enabled": False}
    srv = _server(cfg, params, True)
    srv.generate(_prompts(6, n=2), max_new_tokens=4)
    st = srv.stats()["disagg"]
    assert not {"enabled", "prefill_max_concurrent",
                "prefill_blocks_usable", "prefill_blocks_free",
                "prefill_blocks_live", "prefill_blocks_live_peak",
                "prefill_blocks_evictable", "prefill_pool_bytes",
                "prefill_backlog_blocks", "handoff",
                "sink_attached"} - st.keys()
    assert st["enabled"] is True and st["sink_attached"] is False
    assert st["handoff"]["requests"] >= 1
    # ITL per-token latency rides stats()["latency"] for every server
    assert srv.stats()["latency"]["itl_ms"]["count"] >= 1


# -- cross-replica: export / ingest / failover ----------------------------


def test_export_import_blocks_roundtrip_and_torn_detection(tiny):
    """The transfer unit: export materializes checksummed leaves,
    import scatters them bit-exactly, and a corrupted payload is
    rejected WHOLE (ValueError, nothing imported)."""
    cfg, params = tiny
    srv = _server(cfg, params, False)
    srv.generate([_prompts(7, n=1)[0]], max_new_tokens=2)
    eng = srv.engine
    blocks = eng.allocator.alloc(3)
    # write recognizable content through a fake table: just export
    # whatever the pool holds for those blocks and round-trip it
    payload = eng.export_blocks(blocks)
    dst = eng.allocator.alloc(3)
    eng.import_blocks(dst, payload)
    heads = cfg.num_attention_heads
    src_rows = read_slots(eng.cache, block_slots(blocks, eng.block_size),
                          heads)
    dst_rows = read_slots(eng.cache, block_slots(dst, eng.block_size),
                          heads)
    for name in src_rows:
        assert (np.asarray(src_rows[name])
                == np.asarray(dst_rows[name])).all(), name
    torn = {**payload,
            "leaves": {k: v.copy() for k, v in
                       payload["leaves"].items()}}
    next(iter(torn["leaves"].values())).flat[0] += 1
    with pytest.raises(ValueError, match="torn"):
        eng.import_blocks(dst, torn)
    with pytest.raises(ValueError, match="geometry"):
        eng.import_blocks(dst[:2], payload)
    eng.allocator.free(blocks)
    eng.allocator.free(dst)


def test_ingest_handoff_continues_bit_exactly(tiny):
    """A prefill done on server A, shipped as a payload, and ingested
    by server B decodes the same stream the monolithic engine would
    have — the cross-replica hand-off in miniature."""
    cfg, params = tiny
    prompt = _prompts(8, n=1)[0]
    want = _server(cfg, params, False).generate([prompt],
                                                max_new_tokens=10)[0]
    # server A: disagg with NO local decode admission — grab the
    # request at the hand-off edge via a sink
    shipped = {}

    def sink(req, payload):
        shipped["req"] = req
        shipped["payload"] = payload
        return True

    a = _server(cfg, params, True, handoff_sink=sink)
    ra = a.submit(prompt, 10)
    while not shipped and a.has_work:
        a.step()
        a.audit()
    assert shipped, "hand-off sink never fired"
    assert ra.finish_reason == "handoff"
    assert ra.generated == want[:len(ra.generated)]
    b = _server(cfg, params, False)
    req = b.ingest_handoff(prompt, shipped["req"].generated,
                           shipped["payload"],
                           max_new_tokens=10,
                           num_cached=shipped["req"].num_cached)
    assert req is not None
    while b.has_work:
        b.step()
        b.audit()
    assert list(req.generated) == want


@pytest.mark.slow
def test_fleet_disagg_prefill_decode_roles(tiny):
    """Router tier: a prefill-role replica ships payloads to decode
    replicas; long prompts route phase-aware, short ones stay
    monolithic, and every stream equals the single-server baseline."""
    cfg, params = tiny
    rng = np.random.RandomState(9)
    longs = [list(rng.randint(0, VOCAB, size=30)) for _ in range(4)]
    shorts = [list(rng.randint(0, VOCAB, size=5)) for _ in range(4)]
    prompts = [p for pair in zip(longs, shorts) for p in pair]
    want = _server(cfg, params, False,
                   max_batch_size=4).generate(prompts,
                                              max_new_tokens=10,
                                              eos_id=7)
    fleet = RouterFleet(cfg, params, replicas=3, disagg_prefill=1,
                        max_batch_size=4, max_context=64,
                        block_size=4, cache_dtype=jnp.float32)
    got = fleet.generate(prompts, max_new_tokens=10, eos_id=7)
    assert got == want
    r = fleet.stats()["router"]
    assert r["handoffs"] >= 1
    assert r["per_replica"]["replica0"]["role"] == "prefill"
    for rep in fleet.replicas:
        rep.server.audit()
    fleet.close()


@pytest.mark.slow
def test_fleet_torn_payload_falls_back_to_monolithic(tiny):
    """A torn cross-replica payload is detected at ingest (checksum)
    and the request falls back to MONOLITHIC placement — a fresh
    prefill elsewhere, bit-identical by construction."""
    cfg, params = tiny
    rng = np.random.RandomState(10)
    longs = [list(rng.randint(0, VOCAB, size=30)) for _ in range(4)]
    want = _server(cfg, params, False,
                   max_batch_size=4).generate(longs, max_new_tokens=8)
    fleet = RouterFleet(cfg, params, replicas=2, disagg_prefill=1,
                        max_batch_size=4, max_context=64,
                        block_size=4, cache_dtype=jnp.float32)
    pe = fleet.replicas[0].server.prefill_engine
    real = pe.export_blocks

    def corrupt(ids):
        p = real(ids)
        name = next(iter(p["leaves"]))
        p["leaves"][name] = p["leaves"][name].copy()
        p["leaves"][name].flat[0] += 1
        return p

    pe.export_blocks = corrupt
    got = fleet.generate(longs, max_new_tokens=8)
    assert got == want
    r = fleet.stats()["router"]
    assert r["handoff_torn"] >= 1
    assert r["handoff_fallback"] >= 1
    assert r["handoffs"] == 0
    for rep in fleet.replicas:
        rep.server.audit()
    fleet.close()


@pytest.mark.slow
def test_disagg_mini_soak(tiny):
    """160 iterations of composed chaos (incl. torn/delayed hand-off
    transfers) over the disaggregated server, replayed against a
    monolithic oracle — the build-matrix axis runs the full 800."""
    from apex_tpu.resilience.chaos import ChaosConfig, run_soak

    cfg, params = tiny

    def make_server(clock):
        return InferenceServer(
            cfg, params, max_batch_size=4, max_context=64,
            block_size=4, num_blocks=40, cache_dtype=jnp.float32,
            max_waiting=8, clock=clock, enable_disagg=True,
            disagg_prefill_blocks=24)

    def make_replay(clock):
        return InferenceServer(
            cfg, params, max_batch_size=4, max_context=64,
            block_size=4, cache_dtype=jnp.float32, clock=clock)

    report = run_soak(
        make_server,
        ChaosConfig(iters=160, vocab=VOCAB, crash_every=0,
                    handoff_oom_rate=0.05, handoff_torn_rate=0.03),
        seed=3, make_replay=make_replay)
    assert report["submitted"] > 0
    assert report["disagg"] is True
    assert report["handoff"]["requests"] >= 1
