"""Prefix caching + chunked prefill: the serving-perf layer's oracle.

The headline contract is BIT-EXACT greedy-argmax parity: a server with
prefix caching and chunked prefill enabled must generate token-for-
token what the same params generate with both features disabled —
across shared-prefix traffic, multi-chunk prompts, forced preemption,
forced cache eviction, and whole-context COW hits.  One wrong shared
block, chunk bias, or refcount diverges the sequence within a few
tokens and the parity loop names the first mismatch.

The second pillar is the refcount invariant, asserted after EVERY
scheduler step (``Scheduler.audit``): each block's refcount equals the
number of running tables referencing it, ref-0 blocks are exactly free
XOR cache-held, and the free list/set mirror each other.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from apex_tpu import models
from apex_tpu.serving import InferenceServer
from apex_tpu.serving.kv_cache import BlockAllocator, KVCacheConfig
from apex_tpu.serving.prefix_cache import ROOT, PrefixCache

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


def _server(cfg, params, on=True, **kw):
    """``on=False`` is the baseline the parity tests compare with: no
    prefix cache, and (unless the test names a ``prefill_chunk``) a
    chunk as wide as the context, so every prompt goes in whole."""
    kw.setdefault("cache_dtype", jnp.float32)
    kw.setdefault("max_context", 128)
    kw.setdefault("block_size", 8)
    return InferenceServer(cfg, params, enable_prefix_cache=on, **kw)


def _audited_generate(server, prompts, max_new, eos_id=None):
    """generate() driven step-by-step with the refcount invariant
    asserted after every scheduler iteration."""
    reqs = [server.submit(p, max_new, eos_id) for p in prompts]
    while server.scheduler.has_work:
        server.step()
        server.scheduler.audit()
    return [list(r.generated) for r in reqs]


def _assert_parity(got, want, tag):
    assert len(got) == len(want)
    for i, (a, b) in enumerate(zip(got, want)):
        assert len(a) == len(b), (tag, i, len(a), len(b))
        for t, (x, y) in enumerate(zip(a, b)):
            assert x == y, (f"{tag}: request {i} diverged at generated "
                            f"token {t}: cached={x} baseline={y}")


# -- allocator refcounts (unit) -------------------------------------------

def _alloc(num_blocks=8, block_size=4):
    return BlockAllocator(KVCacheConfig(
        num_layers=1, num_heads=2, head_dim=4, num_blocks=num_blocks,
        block_size=block_size, dtype=jnp.float32))


def test_refcount_shared_block_survives_first_free():
    alloc = _alloc()
    blocks = alloc.alloc(2)
    alloc.incref(blocks)                   # a second table shares both
    assert all(alloc.refs(b) == 2 for b in blocks)
    alloc.free(blocks)                     # first table releases
    assert all(alloc.refs(b) == 1 for b in blocks)
    assert alloc.num_free == 5             # NOT back on the free list
    alloc.free(blocks)                     # last ref drops
    assert alloc.num_free == 7
    assert all(alloc.refs(b) == 0 for b in blocks)


def test_refcount_free_set_mirrors_free_list():
    """The O(1)-free satellite: the set and list stay in lockstep
    through alloc/free churn (double-free detection reads the set)."""
    alloc = _alloc(num_blocks=16)
    a = alloc.alloc(5)
    b = alloc.alloc(4)
    alloc.free(a[1:3])
    alloc.free(b)
    c = alloc.alloc(3)
    assert set(alloc._free) == alloc._free_set
    assert len(alloc._free) == len(alloc._free_set) == alloc.num_free
    with pytest.raises(ValueError, match="double free"):
        alloc.free([a[1]])
    with pytest.raises(ValueError, match="unallocated"):
        alloc.incref([a[1]])
    del c


def test_adopt_and_release_to_free_guard_states():
    alloc = _alloc()
    (blk,) = alloc.alloc(1)
    with pytest.raises(ValueError):
        alloc.adopt(blk)                   # live, not cache-held
    hook_kept = []
    alloc.release_hook = lambda b: hook_kept.append(b) or True
    alloc.free([blk])                      # ref 0 -> hook holds it
    assert hook_kept == [blk]
    assert alloc.refs(blk) == 0 and blk not in alloc._free_set
    alloc.adopt(blk)                       # cache reactivates it
    assert alloc.refs(blk) == 1
    alloc.release_hook = None
    alloc.free([blk])
    with pytest.raises(ValueError):
        alloc.release_to_free(blk)         # already free


# -- prefix index (unit) --------------------------------------------------

def test_match_register_and_lru_reactivation():
    alloc = _alloc(num_blocks=10, block_size=4)
    cache = PrefixCache(alloc, 4)
    toks = list(range(11))                 # 2 full blocks + tail
    assert cache.match(toks) == []         # cold
    blocks = alloc.alloc(3)
    assert cache.register(ROOT, tuple(toks[0:4]), blocks[0])
    assert cache.register(blocks[0], tuple(toks[4:8]), blocks[1])
    got = cache.match(toks)
    assert got == blocks[:2]               # longest full-block chain
    assert alloc.refs(blocks[0]) == 2      # original + match
    cache.cancel(got)
    alloc.free(blocks)                     # original tables release
    assert cache.num_evictable == 2        # held, not freed
    assert alloc.num_free == 9 - 2 - 1 + 1  # only the tail block freed
    got2 = cache.match(toks)               # reactivates the holds
    assert got2 == blocks[:2]
    assert cache.num_evictable == 0
    assert all(alloc.refs(b) == 1 for b in got2)
    cache.audit()


def test_eviction_cascades_descendants_and_frees():
    alloc = _alloc(num_blocks=10, block_size=4)
    cache = PrefixCache(alloc, 4)
    blocks = alloc.alloc(3)
    chunks = [tuple(range(i * 4, (i + 1) * 4)) for i in range(3)]
    cache.register(ROOT, chunks[0], blocks[0])
    cache.register(blocks[0], chunks[1], blocks[1])
    cache.register(blocks[1], chunks[2], blocks[2])
    alloc.free(blocks)
    assert cache.num_evictable == 3
    freed = cache.evict(1)                 # root is LRU-oldest ->
    assert freed == 3                      # the whole chain cascades
    assert cache.num_cached_blocks == 0
    assert alloc.num_free == 9
    assert cache.counters.count("prefix_evicted_blocks") == 3
    cache.audit()


def test_register_first_wins_on_collision():
    alloc = _alloc(num_blocks=10, block_size=4)
    cache = PrefixCache(alloc, 4)
    a, b = alloc.alloc(2)
    chunk = (1, 2, 3, 4)
    assert cache.register(ROOT, chunk, a) is True
    assert cache.register(ROOT, chunk, b) is False   # duplicate content
    assert cache.match([1, 2, 3, 4, 9]) == [a]
    cache.cancel([a])
    with pytest.raises(ValueError, match="full block"):
        cache.register(ROOT, (1, 2), a)


# -- headline parity oracles ----------------------------------------------

def test_shared_prefix_parity_64_tokens_and_hits(tiny):
    """The acceptance oracle: shared-system-prompt traffic, >= 64
    generated tokens per request, features on vs off, invariant
    audited every step — and the cache actually HIT."""
    cfg, params = tiny
    prefix = [(7 * i + 3) % VOCAB for i in range(24)]   # 3 full blocks
    prompts = [prefix + [s, s + 1] for s in (5, 11, 17, 23)]

    base = _server(cfg, params, on=False, max_batch_size=2)
    want = _audited_generate(base, prompts, 64)

    srv = _server(cfg, params, on=True, max_batch_size=2,
                  prefill_chunk=8)
    got = _audited_generate(srv, prompts, 64)
    _assert_parity(got, want, "shared-prefix")
    st = srv.stats()
    assert st["prefix_hit_tokens"] >= 24       # later requests matched
    assert st["prefix_hit_requests"] >= 1
    assert 0.0 < st["prefix_hit_rate"] <= 1.0
    assert st["prefill_chunks"] > len(prompts)  # chunking actually ran
    # exactly ONE chunk program despite many chunk lengths (the
    # default pipelined loop compiles the fused sampled twin)
    assert (srv.engine._chunk_jit._cache_size()
            + srv.engine._chunk_sampled_jit._cache_size()) == 1


def test_multi_chunk_long_prompt_parity(tiny):
    """A prompt spanning many chunks (and several blocks) must carry
    its KV position across chunk boundaries exactly."""
    cfg, params = tiny
    rng = np.random.RandomState(3)
    prompts = [list(rng.randint(0, VOCAB, size=n)) for n in (50, 37, 9)]
    base = _server(cfg, params, on=False, max_batch_size=3)
    want = _audited_generate(base, prompts, 64)
    srv = _server(cfg, params, on=True, max_batch_size=3,
                  prefill_chunk=16)
    got = _audited_generate(srv, prompts, 64)
    _assert_parity(got, want, "multi-chunk")


def test_parity_under_forced_preemption(tiny):
    """A pool too small for the running set forces preemption while
    features are on; resumed requests re-match their own registered
    blocks and must still be bit-stable."""
    cfg, params = tiny
    prompts = [[3, 1, 4, 1, 5, 9, 2, 6],
               [2, 7, 1, 8, 2, 8, 1, 8],
               [9, 9, 8, 7, 6, 5, 4, 3]]
    base = _server(cfg, params, on=False, max_batch_size=3,
                   max_context=64, block_size=4, num_blocks=10)
    want = _audited_generate(base, prompts, 24)
    srv = _server(cfg, params, on=True, max_batch_size=3,
                  max_context=64, block_size=4, num_blocks=10,
                  prefill_chunk=8)
    got = _audited_generate(srv, prompts, 24)
    _assert_parity(got, want, "preemption")
    assert srv.stats()["preemptions"] >= 1     # pressure actually hit


def test_parity_under_forced_eviction(tiny):
    """Fill the index with one workload, then submit a different one
    whose blocks can only come from LRU eviction; then re-run the
    first workload (now a partial/total miss) — every phase stays
    bit-exact and audited."""
    cfg, params = tiny
    rng = np.random.RandomState(7)
    wave1 = [list(rng.randint(0, VOCAB, size=20)) for _ in range(2)]
    wave2 = [list(rng.randint(0, VOCAB, size=20)) for _ in range(2)]

    base = _server(cfg, params, on=False, max_batch_size=2,
                   max_context=64, block_size=4, num_blocks=20)
    want1 = _audited_generate(base, wave1, 16)
    want2 = _audited_generate(base, wave2, 16)
    want1b = _audited_generate(base, wave1, 16)

    # 19 usable blocks; each finished request holds ~9 (20 prompt + 16
    # generated tokens at bs=4), so wave2's admissions must evict
    srv = _server(cfg, params, on=True, max_batch_size=2,
                  max_context=64, block_size=4, num_blocks=20,
                  prefill_chunk=8)
    got1 = _audited_generate(srv, wave1, 16)
    got2 = _audited_generate(srv, wave2, 16)
    got1b = _audited_generate(srv, wave1, 16)
    _assert_parity(got1, want1, "eviction-wave1")
    _assert_parity(got2, want2, "eviction-wave2")
    _assert_parity(got1b, want1b, "eviction-wave1-rerun")
    assert srv.stats()["prefix_evicted_blocks"] > 0


def test_whole_context_hit_takes_cow_and_stays_exact(tiny):
    """A block-aligned prompt submitted twice: the second submission
    matches EVERY full block, so its final block is duplicated
    copy-on-write and only the last token recomputes — outputs must
    match the first run's continuation baseline exactly."""
    cfg, params = tiny
    prompt = [(3 * i + 1) % VOCAB for i in range(16)]   # 2 full blocks
    base = _server(cfg, params, on=False, max_batch_size=2)
    want = _audited_generate(base, [prompt], 32)[0]

    srv = _server(cfg, params, on=True, max_batch_size=2,
                  prefill_chunk=8)
    first = _audited_generate(srv, [prompt], 32)[0]
    assert first == want
    second = _audited_generate(srv, [prompt], 32)[0]
    assert second == want
    st = srv.stats()
    assert st["prefix_cow_blocks"] >= 1
    assert st["prefix_hit_tokens"] >= 16


def test_opt_out_flags_restore_cacheless_behavior(tiny):
    """enable_prefix_cache=False leaves no prefix structure behind:
    every prompt is prefilled whole, chunk by chunk, on every
    submission, and the outputs are those of the cached server."""
    cfg, params = tiny
    prompts = [[5, 4, 3, 2, 1] * 4, [1, 2, 3]]
    srv = _server(cfg, params, on=False, max_batch_size=2,
                  prefill_chunk=8)
    assert srv.prefix_cache is None
    assert srv.scheduler.prefix_cache is None
    out = _audited_generate(srv, prompts, 16)
    assert _audited_generate(srv, prompts, 16) == out   # a second wave
    st = srv.stats()
    assert "prefix_hit_tokens" not in st
    assert st["prefill_chunks"] == 2 * (3 + 1)   # 20 and 3 tokens by 8
    assert srv.engine.compile_counts()[0] == 1
    on = _server(cfg, params, on=True, max_batch_size=2,
                 prefill_chunk=8)
    _assert_parity(_audited_generate(on, prompts, 16), out, "opt-out")
    _assert_parity(_audited_generate(on, prompts, 16), out, "opt-out")
    st = on.stats()
    assert st["prefix_hit_tokens"] >= 16         # the second wave hit
    assert st["prefill_chunks"] < 2 * (3 + 1)


def test_chunked_prefill_interleaves_with_decode(tiny):
    """While a long prompt prefills chunk-by-chunk, an already-running
    request keeps producing one token per iteration — the head-of-line
    stall chunked prefill exists to remove (structurally, not by
    wall-clock)."""
    cfg, params = tiny
    # speculation off: the per-iteration "+1 token" probe below IS the
    # structural claim; a speculating server emits several tokens per
    # step and would blur it
    # pipeline off for the same pacing reason: retired-one-step-late
    # tokens would break the per-iteration "+1 token" probe
    srv = _server(cfg, params, on=True, max_batch_size=2,
                  prefill_chunk=8, enable_speculation=False,
                  enable_pipeline=False)
    short = srv.submit([1, 2, 3], 40)
    # get the short request decoding
    for _ in range(3):
        srv.step()
        srv.scheduler.audit()
    rng = np.random.RandomState(0)
    long_req = srv.submit(list(rng.randint(0, VOCAB, size=60)), 4)
    while long_req.prefilling or not long_req.generated:
        before = len(short.generated)
        srv.step()
        srv.scheduler.audit()
        if not short.finished:
            assert len(short.generated) == before + 1, \
                "decode stalled during a prefill chunk"
        if srv.scheduler.num_running == 0:
            break
    while srv.scheduler.has_work:
        srv.step()
        srv.scheduler.audit()
    assert long_req.finish_reason == "length"
    assert srv.stats()["chunk_iters_peak"] >= 1


def test_preempted_resume_is_a_cache_hit(tiny):
    """After preemption, re-admission re-matches the victim's OWN
    registered blocks (held evictable-LRU by the release path) —
    recovery prefills only the unregistered tail instead of the whole
    context, and the continuation stays bit-exact.  (Preemption is
    forced manually: under genuine pool pressure the victim's holds
    are immediately evicted by the same pressure that preempted it,
    so the ample-pool path is the one where resume-as-hit shows.)"""
    cfg, params = tiny
    prompt = [3, 1, 4, 1, 5, 9, 2, 6]
    # speculation off in both arms: the manual preempt below is aimed
    # at a request mid-generation after exactly 6 one-token steps
    base = _server(cfg, params, on=False, max_batch_size=2,
                   enable_speculation=False)
    want = _audited_generate(base, [prompt], 24)[0]

    srv = _server(cfg, params, on=True, max_batch_size=2,
                  block_size=4, prefill_chunk=8,
                  enable_speculation=False)
    req = srv.submit(prompt, 24)
    for _ in range(6):
        srv.step()
        srv.scheduler.audit()
    assert len(req.generated) >= 5
    srv.scheduler.preempt(req)
    srv.scheduler.audit()
    held = srv.scheduler.prefix_cache.num_evictable
    assert held >= 2        # the victim's full blocks became holds
    hits_before = srv.prefix.count("prefix_hit_tokens")
    while srv.scheduler.has_work:
        srv.step()
        srv.scheduler.audit()
    assert req.preemptions == 1
    assert req.generated == want
    # the resume re-matched registered blocks rather than re-prefilling
    assert srv.prefix.count("prefix_hit_tokens") >= hits_before + 8


# -- chunk/preemption interleaving ----------------------------------------


@pytest.mark.parametrize("cache_on", [True, False])
def test_preemption_between_prefill_chunks_resumes_carried_position(
        tiny, cache_on):
    """A request preempted BETWEEN chunks of its prefill (only
    forced-preemption-during-decode had an oracle before): its blocks
    free cleanly mid-chunk-sequence, re-admission resumes at the
    correct carried KV position — the registered full blocks match
    back as a cache hit when the cache is on, position 0 otherwise —
    and the final stream is bit-exact vs an undisturbed server, with
    refcounts audited every step."""
    cfg, params = tiny
    rng = np.random.RandomState(11)
    prompt = list(rng.randint(0, VOCAB, size=40))
    def mk():
        return InferenceServer(
            cfg, params, max_batch_size=2, max_context=128,
            block_size=8, cache_dtype=jnp.float32,
            enable_prefix_cache=cache_on, prefill_chunk=8,
            enable_speculation=False)
    want = _audited_generate(mk(), [prompt], 8)[0]

    server = mk()
    req = server.submit(prompt, 8)
    server.step()
    server.scheduler.audit()
    assert req.prefilling and req.num_cached == 8   # one chunk landed
    server.scheduler.preempt(req)
    server.scheduler.audit()
    assert req.num_cached == 0 and not req.block_table
    server.step()                                   # re-admits
    server.scheduler.audit()
    assert req.running and req.prefilling
    if cache_on:
        # the first chunk's registered block matched back: the resume
        # position carries the already-materialized KV
        assert req.cached_prefix_tokens == 8
        assert req.num_cached >= 8
    else:
        assert req.cached_prefix_tokens == 0
    while server.scheduler.has_work:
        server.step()
        server.scheduler.audit()
    assert list(req.generated) == want
    assert req.preemptions == 1
