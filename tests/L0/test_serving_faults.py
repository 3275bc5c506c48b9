"""Serving failure isolation: one pathological request fails ALONE.

Before this layer, ``Scheduler.admit`` / ``ensure_decode_capacity``
raised ``MemoryError`` out of ``InferenceServer.generate``, killing
every in-flight request; a non-finite logits row would silently poison
sampling for the whole batch.  These tests pin the isolation contract
(``docs/resilience.md`` failure catalogue): under injected pool
exhaustion, expired deadlines, a full queue, or poisoned logits, every
HEALTHY request completes bit-identically to an undisturbed run and
only the affected request carries the failure ``finish_reason``
(``capacity`` / ``timeout`` / ``rejected`` / ``nonfinite``) — no
exception escapes the step loop.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from apex_tpu import models
from apex_tpu.serving import InferenceServer, QueueFullError
from apex_tpu.serving.kv_cache import BlockAllocator, KVCacheConfig
from apex_tpu.serving.scheduler import Request, Scheduler

pytestmark = pytest.mark.serving

VOCAB = 61


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
    return InferenceServer(cfg, params, **kw)


def _raw_scheduler(max_waiting=None, num_blocks=8, block_size=4,
                   max_context=32):
    alloc = BlockAllocator(KVCacheConfig(
        num_layers=1, num_heads=2, head_dim=4, num_blocks=num_blocks,
        block_size=block_size, dtype=jnp.float32))
    return Scheduler(alloc, max_batch_size=2, block_size=block_size,
                     max_context=max_context, max_waiting=max_waiting)


# -- capacity isolation ---------------------------------------------------

def test_never_fits_prompt_fails_alone(tiny):
    """Pool exhaustion by geometry: a prompt needing more blocks than
    the whole pool owns gets finish_reason='capacity'; every healthy
    request in the same generate() completes fully — the old code
    raised MemoryError out of generate(), killing all of them."""
    cfg, params = tiny
    server = _server(cfg, params, max_batch_size=2, max_context=64,
                     block_size=4, num_blocks=6)   # 5 usable = 20 tok
    huge = list(np.arange(30) % VOCAB)             # needs 8 > 5 blocks
    healthy = [[3, 1, 4, 1], [5, 9, 2, 6]]
    reqs = server.generate([huge] + healthy, max_new_tokens=6,
                           return_requests=True)
    assert reqs[0].finish_reason == "capacity"
    assert reqs[0].generated == []
    for r in reqs[1:]:
        assert r.finish_reason == "length"
        assert len(r.generated) == 6
    assert server.stats()["requests_failed"] == {
        "requests_failed_capacity": 1}
    # blocks and slots fully reclaimed (free or evictable cache holds)
    assert server.engine.allocator.num_free \
        + server.scheduler.prefix_cache.num_evictable == 5
    assert server.scheduler.num_running == 0
    server.scheduler.audit()


def test_midflight_outgrow_fails_alone_and_frees_pool(tiny):
    """A request alone in the pool that outgrows it mid-decode (no
    victim left to preempt) is finished with 'capacity', keeps its
    partial output, and returns every block."""
    cfg, params = tiny
    server = _server(cfg, params, max_batch_size=2, max_context=64,
                     block_size=4, num_blocks=4)   # 3 usable = 12 tok
    req = server.generate([[3, 1, 4, 1, 5, 9, 2, 6]],
                          max_new_tokens=20, return_requests=True)[0]
    assert req.finish_reason == "capacity"
    assert 0 < len(req.generated) < 20    # partial output survives
    assert server.engine.allocator.num_free \
        + server.scheduler.prefix_cache.num_evictable == 3
    assert server.scheduler.num_running == 0
    server.scheduler.audit()


# -- deadlines ------------------------------------------------------------

def test_iteration_deadline_times_out_only_that_request(tiny):
    cfg, params = tiny
    # speculation off: the deadline must expire MID-generation, which
    # needs the one-token-per-iteration pacing this test is written in
    server = _server(cfg, params, max_batch_size=2, max_context=64,
                     block_size=8, enable_speculation=False)
    slow = server.submit([3, 1, 4, 1], 10, deadline_iters=3)
    fast = server.submit([5, 9, 2, 6], 10)
    while server.scheduler.has_work:
        server.step()
    assert slow.finish_reason == "timeout"
    assert 0 < len(slow.generated) < 10   # partial output survives
    assert fast.finish_reason == "length"
    assert len(fast.generated) == 10
    assert server.failures.count("requests_failed_timeout") == 1


def test_wall_deadline_with_injected_clock(tiny):
    cfg, params = tiny
    clock = {"t": 0.0}
    # speculation off: one-token-per-iteration pacing (see above)
    server = _server(cfg, params, max_batch_size=2, max_context=64,
                     block_size=8, clock=lambda: clock["t"],
                     enable_speculation=False)
    doomed = server.submit([3, 1, 4, 1], 10, deadline_s=5.0)
    steady = server.submit([5, 9, 2, 6], 10)
    server.step()
    server.step()
    assert not doomed.finished
    clock["t"] = 10.0                     # budget expires mid-flight
    while server.scheduler.has_work:
        server.step()
    assert doomed.finish_reason == "timeout"
    assert steady.finish_reason == "length"
    assert len(steady.generated) == 10


def test_waiting_request_can_time_out(tiny):
    """Deadlines apply in the queue too: a request that never got a
    slot still expires instead of waiting forever."""
    cfg, params = tiny
    server = _server(cfg, params, max_batch_size=1, max_context=64,
                     block_size=8)
    hog = server.submit([3, 1, 4, 1], 12)
    queued = server.submit([5, 9, 2, 6], 12, deadline_iters=2)
    while server.scheduler.has_work:
        server.step()
    assert hog.finish_reason == "length"
    assert queued.finish_reason == "timeout"
    assert queued.generated == []


def test_queued_wall_deadline_expires_as_timeout_not_rejected(tiny):
    """Edge case: a QUEUED (never-admitted) request whose deadline_s
    expires finishes 'timeout' — not 'rejected' — and releases no
    blocks, because it never held any."""
    cfg, params = tiny
    clock = {"t": 0.0}
    server = _server(cfg, params, max_batch_size=1, max_context=64,
                     block_size=8, clock=lambda: clock["t"])
    hog = server.submit([3, 1, 4, 1], 12)
    queued = server.submit([5, 9, 2, 6], 12, deadline_s=3.0)
    server.step()                       # hog admitted; queued waits
    assert not queued.finished
    clock["t"] = 10.0                   # wall budget expires in queue
    server.step()
    assert queued.finish_reason == "timeout"
    assert queued.finish_reason != "rejected"
    assert queued.generated == [] and queued.block_table == []
    assert queued.admitted_at is None   # truly never admitted
    assert "queue_wait_s" not in queued.timeline()
    while server.scheduler.has_work:
        server.step()
    assert hog.finish_reason == "length"
    usable = server.engine.cache_cfg.num_blocks - 1
    assert server.engine.allocator.num_free \
        + server.scheduler.prefix_cache.num_evictable == usable
    server.scheduler.audit()
    assert server.failures.count("requests_failed_timeout") == 1
    assert server.failures.count("requests_failed_rejected") == 0


def test_iter_deadline_on_request_preempted_at_expiry(tiny):
    """Edge case: a request PREEMPTED right as its deadline_iters
    expires times out from the waiting queue — keeping its partial
    output, holding zero blocks, and never re-admitting."""
    cfg, params = tiny
    # speculation off: one-token-per-iteration pacing (see above)
    server = _server(cfg, params, max_batch_size=2, max_context=64,
                     block_size=8, enable_speculation=False)
    req = server.submit([3, 1, 4, 1], 10, deadline_iters=4)
    for _ in range(4):
        server.step()
    assert req.running and len(req.generated) > 0
    server.scheduler.preempt(req)       # evicted exactly at expiry
    assert req.block_table == []
    partial = list(req.generated)
    server.step()                       # expiry fires before re-admit
    assert req.finish_reason == "timeout"
    assert req.generated == partial     # partial output survives
    assert req.block_table == []
    assert not server.scheduler.has_work
    usable = server.engine.cache_cfg.num_blocks - 1
    assert server.engine.allocator.num_free \
        + server.scheduler.prefix_cache.num_evictable == usable
    server.scheduler.audit()


# -- bounded queue --------------------------------------------------------

def test_scheduler_bounded_queue_raises():
    sched = _raw_scheduler(max_waiting=2)
    sched.submit(Request(prompt=[1], max_new_tokens=4))
    sched.submit(Request(prompt=[2], max_new_tokens=4))
    with pytest.raises(QueueFullError, match="waiting queue full"):
        sched.submit(Request(prompt=[3], max_new_tokens=4))


def test_server_bounded_queue_rejects_explicitly(tiny):
    """The server front door converts queue-full into an explicitly
    rejected request (finish_reason='rejected') rather than an
    exception or a silent drop."""
    cfg, params = tiny
    server = _server(cfg, params, max_batch_size=1, max_context=64,
                     block_size=8, max_waiting=2)
    reqs = server.generate([[1, 2], [3, 4], [5, 6]], max_new_tokens=4,
                           return_requests=True)
    reasons = [r.finish_reason for r in reqs]
    assert reasons.count("rejected") == 1
    assert reasons.count("length") == 2
    rejected = reqs[reasons.index("rejected")]
    assert rejected.generated == []
    assert server.failures.count("requests_failed_rejected") == 1


# -- non-finite step guard ------------------------------------------------

def test_nonfinite_decode_row_evicts_only_poisoned_request(tiny):
    """Poison one slot's decode logits mid-run: that request is evicted
    with 'nonfinite'; the other completes token-for-token identical to
    an undisturbed run (isolation is bit-exact, not approximate)."""
    cfg, params = tiny
    prompts = [[3, 1, 4, 1, 5, 9, 2, 6], [2, 7, 1, 8, 2, 8, 1, 8]]

    # speculation off in both arms: the poison is injected through
    # engine.decode, which a speculating server bypasses (the verify
    # path's non-finite isolation has its own test in
    # tests/L0/test_speculative.py)
    # pipeline off in both arms too: the poison injects through
    # engine.decode, which the pipelined loop bypasses (finite-flag
    # poisoning of the fused path: tests/L0/test_pipeline.py)
    clean = _server(cfg, params, max_batch_size=2, max_context=64,
                    block_size=8, enable_speculation=False,
                    enable_pipeline=False)
    baseline = clean.generate(prompts, max_new_tokens=12)

    server = _server(cfg, params, max_batch_size=2, max_context=64,
                     block_size=8, enable_speculation=False,
                     enable_pipeline=False)
    victim = server.submit(prompts[0], 12)
    other = server.submit(prompts[1], 12)
    orig_decode = server.engine.decode
    calls = {"n": 0}

    def poisoned(tokens, positions, tables):
        out = np.array(orig_decode(tokens, positions, tables))
        calls["n"] += 1
        if calls["n"] == 3:
            out[victim.slot] = np.nan
        return out

    server.engine.decode = poisoned
    while server.scheduler.has_work:
        server.step()
    assert victim.finish_reason == "nonfinite"
    assert len(victim.generated) < 12
    assert other.finish_reason == "length"
    assert other.generated == baseline[1]
    assert server.failures.count("requests_failed_nonfinite") == 1
    # nothing leaked: every block is free or an evictable cache hold
    # (the two runs fail at different depths, so the free/held split
    # differs; the reclaimable total may not)
    usable = server.engine.cache_cfg.num_blocks - 1
    assert server.engine.allocator.num_free \
        + server.scheduler.prefix_cache.num_evictable == usable
    server.scheduler.audit()


def test_nonfinite_prefill_fails_request_before_first_token(tiny):
    # chunked prefill is the default path, so the fault injects there
    # (pipeline off: the pipelined loop samples prefills through the
    # fused chunk_prefill_sampled twin instead — covered by
    # tests/L0/test_pipeline.py)
    cfg, params = tiny
    server = _server(cfg, params, max_batch_size=2, max_context=64,
                     block_size=8, enable_pipeline=False)
    orig_chunk = server.engine.chunk_prefill

    def poisoned(tokens, start, block_table, pad_to=None):
        out = np.array(orig_chunk(tokens, start, block_table,
                                  pad_to=pad_to))
        if len(tokens) == 4:          # only the marked request
            out[...] = np.inf - np.inf
        return out

    server.engine.chunk_prefill = poisoned
    reqs = server.generate([[3, 1, 4, 1], [5, 9, 2, 6, 5, 3]],
                           max_new_tokens=5, return_requests=True)
    assert reqs[0].finish_reason == "nonfinite"
    assert reqs[0].generated == []
    assert reqs[1].finish_reason == "length"
    assert len(reqs[1].generated) == 5


# -- combined acceptance scenario -----------------------------------------

def test_mixed_failures_no_exception_escapes(tiny):
    """The acceptance scenario: pool exhaustion AND an expired deadline
    in one batch — generate() completes, healthy requests get full
    completions, and only the affected ones carry capacity/timeout."""
    cfg, params = tiny
    # speculation off: the deadline_iters=2 expiry below assumes
    # one-token-per-iteration pacing
    server = _server(cfg, params, max_batch_size=3, max_context=64,
                     block_size=4, num_blocks=10,  # 9 usable = 36 tok
                     enable_speculation=False)
    huge = list(np.arange(30) % VOCAB)             # needs 8 blocks; >
    doomed = server.submit([3, 1, 4, 1], 10, deadline_iters=2)
    capacity = server.submit(huge, 10)             # fits alone, but the
    healthy = [server.submit(p, 8) for p in
               ([5, 9, 2, 6], [2, 7, 1, 8])]
    while server.scheduler.has_work:               # running set forces
        server.step()                              # a capacity path
    assert doomed.finish_reason == "timeout"
    for r in healthy:
        assert r.finish_reason == "length"
        assert len(r.generated) == 8
    assert capacity.finish_reason in ("capacity", "length")
    stats = server.stats()
    assert stats["requests_failed_total"] >= 1
    assert server.scheduler.num_running == 0
    assert server.scheduler.num_waiting == 0


# -- submission validation (satellite) ------------------------------------

def test_scheduler_submit_validates_max_new_tokens():
    sched = _raw_scheduler()
    with pytest.raises(ValueError,
                       match=r"max_new_tokens must be >= 1, got 0"):
        sched.submit(Request(prompt=[1], max_new_tokens=0))
    with pytest.raises(ValueError,
                       match=r"max_new_tokens must be >= 1, got -3"):
        sched.submit(Request(prompt=[1], max_new_tokens=-3))


def test_server_submit_rejects_no_room_prompt(tiny):
    cfg, params = tiny
    server = _server(cfg, params, max_batch_size=1, max_context=32,
                     block_size=8)
    with pytest.raises(ValueError,
                       match=r"leaves no room to generate within "
                             r"max_context=32"):
        server.submit(list(range(32)), 4)
    with pytest.raises(ValueError,
                       match=r"max_new_tokens must be >= 1"):
        server.submit([1, 2, 3], 0)
    # a merely over-long budget is still capped to fit, not rejected
    req = server.submit([1, 2, 3], 1000)
    assert req.max_new_tokens == 29
