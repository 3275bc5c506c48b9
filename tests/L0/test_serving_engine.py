"""serving engine + scheduler: cached decode must be a refactoring of
the full forward, not an approximation of it.

The load-bearing test is greedy argmax parity token-for-token over 64+
generated tokens against a full-recompute oracle — one wrong cache
slot, position embedding, or mask bit diverges the sequence within a
few tokens and the test names the first mismatch.  The oracle runs the
SAME params through the ordinary training forward at a fixed padded
length (one compile), so the comparison isolates the serving path.

The second pillar is compile discipline: traffic with many distinct
prompt lengths must compile at most one prefill program per bucket and
exactly one decode program (``DecodeEngine.compile_counts``) — shape-
driven recompiles are how serving throughput quietly dies on TPU.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from apex_tpu import models
from apex_tpu.serving import InferenceServer
from apex_tpu.serving.kv_cache import pool_dtype

pytestmark = pytest.mark.serving

VOCAB = 61


@pytest.fixture(scope="module")
def tiny():
    """(cfg, params, oracle_step): one model init + one oracle compile
    shared by every test in the module."""
    cfg = models.GPTConfig(
        vocab_size=VOCAB, hidden_size=32, num_hidden_layers=2,
        num_attention_heads=2, intermediate_size=64,
        max_position_embeddings=128, hidden_dropout_prob=0.0,
        attention_probs_dropout_prob=0.0)
    m = models.GPTLMHeadModel(cfg)
    params = m.init(jax.random.PRNGKey(1),
                    jnp.ones((1, 8), jnp.int32))["params"]

    @jax.jit
    def oracle_step(ids, mask):
        return m.apply({"params": params}, ids, attention_mask=mask)

    return cfg, params, oracle_step


def naive_generate(oracle_step, prompt, n, pad_to=128):
    """Greedy decode by full recompute at a FIXED padded length — the
    parity oracle (and the one-compile naive baseline the serving bench
    measures against)."""
    toks = list(prompt)
    ids = np.zeros((1, pad_to), np.int32)
    mask = np.zeros((1, pad_to), np.int32)
    for _ in range(n):
        ln = len(toks)
        ids[0, :ln] = toks
        mask[0, :ln] = 1
        logits = oracle_step(jnp.asarray(ids), jnp.asarray(mask))
        toks.append(int(np.argmax(np.asarray(logits[0, ln - 1]))))
    return toks[len(prompt):]


def test_cached_decode_matches_full_recompute(tiny):
    """>= 64 generated tokens, token-for-token (acceptance criterion).
    fp32 cache so the only difference from the oracle is the serving
    machinery itself."""
    cfg, params, oracle_step = tiny
    prompt = [3, 1, 4, 1, 5, 9, 2, 6]
    server = InferenceServer(cfg, params, max_batch_size=2,
                             max_context=128, block_size=8,
                             cache_dtype=jnp.float32)
    out = server.generate([prompt], max_new_tokens=64)[0]
    ref = naive_generate(oracle_step, prompt, 64)
    assert len(out) == 64
    for t, (a, b) in enumerate(zip(out, ref)):
        assert a == b, (f"diverged at generated token {t}: "
                        f"serving={a} oracle={b}")


def test_mixed_lengths_parity_and_bounded_compiles(tiny):
    """More requests than slots, prompt lengths from 3 to 31: every
    completion matches the oracle, requests retire and admit
    mid-flight, and whatever the lengths one chunk program and one
    decode program compile."""
    cfg, params, oracle_step = tiny
    rng = np.random.RandomState(0)
    prompts = [list(rng.randint(0, VOCAB, size=n))
               for n in (3, 9, 14, 17, 25, 31, 6, 23)]
    server = InferenceServer(cfg, params, max_batch_size=3,
                             max_context=64, block_size=8,
                             cache_dtype=jnp.float32)
    outs = server.generate(prompts, max_new_tokens=12)
    for p, o in zip(prompts, outs):
        assert o == naive_generate(oracle_step, p, 12), p
    pre, dec = server.engine.compile_counts()
    assert dec == 1, f"decode recompiled: {dec} programs"
    assert pre == 1, f"{pre} chunk programs for one chunk width"
    st = server.stats()
    assert st["requests_finished"] == 8
    assert st["queue_depth_peak"] >= 1        # batching was actually
    assert st["batch_occupancy_avg"] > 0      # continuous


def test_preemption_is_bit_stable(tiny):
    """A pool too small for the running set forces preemption; the
    evicted request re-prefills and must still match the oracle."""
    cfg, params, oracle_step = tiny
    prompts = [[3, 1, 4, 1, 5, 9, 2, 6],
               [2, 7, 1, 8, 2, 8, 1, 8],
               [9, 9, 8, 7, 6, 5, 4, 3]]
    server = InferenceServer(cfg, params, max_batch_size=3,
                             max_context=64, block_size=4,
                             num_blocks=10,  # 9 usable = 36 tokens
                             cache_dtype=jnp.float32)
    outs = server.generate(prompts, max_new_tokens=24)
    for p, o in zip(prompts, outs):
        assert o == naive_generate(oracle_step, p, 24), p
    st = server.stats()
    assert st["preemptions"] >= 1             # pressure actually hit
    # everything came back: free outright or held evictable by the
    # prefix cache (still reclaimable — the hold IS the feature)
    assert st["kv_blocks_free"] + st["kv_blocks_evictable"] == 9
    server.scheduler.audit()


def test_eos_terminates_early_and_frees_resources(tiny):
    cfg, params, oracle_step = tiny
    prompt = [5, 4, 3, 2, 1]
    ref = naive_generate(oracle_step, prompt, 32)
    eos = ref[7]                              # will fire at step 7
    stop = ref.index(eos) + 1
    server = InferenceServer(cfg, params, max_batch_size=2,
                             max_context=64, block_size=8,
                             cache_dtype=jnp.float32)
    out = server.generate([prompt], max_new_tokens=32, eos_id=eos)[0]
    assert out == ref[:stop]
    assert server.scheduler.finished[0].finish_reason == "eos"
    # all blocks reclaimable: free list + evictable prefix-cache holds
    assert server.engine.allocator.num_free \
        + server.scheduler.prefix_cache.num_evictable == \
        server.engine.cache_cfg.num_blocks - 1
    server.scheduler.audit()


def test_default_cache_dtype_is_half_and_still_generates(tiny):
    """The amp-policy default (bf16) halves KV HBM; generation stays
    well-formed (bit parity is only promised for fp32 caches)."""
    cfg, params, _ = tiny
    server = InferenceServer(cfg, params, max_batch_size=2,
                             max_context=64, block_size=8)
    assert pool_dtype(server.engine.cache) == jnp.bfloat16
    out = server.generate([[1, 2, 3]], max_new_tokens=8)[0]
    assert len(out) == 8
    assert all(0 <= t < VOCAB for t in out)


def test_scheduler_rejects_oversized_and_empty_prompts(tiny):
    cfg, params, _ = tiny
    server = InferenceServer(cfg, params, max_batch_size=2,
                             max_context=32, block_size=8,
                             cache_dtype=jnp.float32)
    with pytest.raises(ValueError):
        server.submit(list(range(32)), 4)     # no room to generate
    with pytest.raises(ValueError):
        server.submit([], 4)
    # max_new_tokens is capped to fit max_context
    req = server.submit(list(range(30)), 100)
    assert req.max_new_tokens == 2


def test_stats_keys_are_backward_compatible(tiny):
    """The telemetry migration (docs/observability.md) moved every
    meter onto the shared MetricsRegistry; this pins the contract that
    no pre-telemetry ``stats()`` key was renamed or dropped — log
    scrapers and the bench harness key on these literally."""
    cfg, params, _ = tiny
    server = InferenceServer(cfg, params, max_batch_size=2,
                             max_context=64, block_size=8,
                             cache_dtype=jnp.float32)
    server.generate([[1, 2, 3], [4, 5, 6, 7]], max_new_tokens=4)
    st = server.stats()
    pre_telemetry = {
        "tokens_generated", "tokens_per_s", "queue_depth_peak",
        "batch_occupancy_avg", "prefill_compiles", "decode_compiles",
        "requests_finished", "preemptions", "kv_blocks_free",
        "kv_blocks_cached", "kv_blocks_evictable", "requests_failed",
        "requests_failed_total", "prefill_chunks", "chunk_iters_peak",
        # prefix-cache block (default-on server)
        "prefix_hit_requests", "prefix_hit_rate", "prefix_hit_tokens",
        "prefix_miss_tokens", "prefix_cow_blocks",
        "prefix_evicted_blocks",
    }
    missing = pre_telemetry - st.keys()
    assert not missing, f"stats() lost pre-telemetry keys: {missing}"
    # and the new telemetry keys ride alongside
    assert "tokens_per_s_recent" in st
    # overload/lifecycle keys (docs/resilience.md, "Overload policy &
    # lifecycle") extend stats() without touching anything above
    overload = {"pressure", "pressure_peak", "breaker_state",
                "breaker_events", "oom_events", "draining"}
    assert not overload - st.keys(), \
        f"stats() lost overload keys: {overload - st.keys()}"
    assert st["breaker_state"] == "closed"     # healthy run
    assert st["oom_events"] == 0
    # speculative-decoding keys (docs/serving.md) ride alongside in
    # their own block — the bench and dashboards key on these
    spec = {"enabled", "spec_tokens", "drafted_tokens",
            "accepted_tokens", "acceptance_rate", "verify_steps",
            "decode_steps", "decode_tokens", "tokens_per_engine_step",
            "verify_compiles", "drafted_per_step", "accepted_per_step"}
    assert not spec - st["speculation"].keys(), \
        f"stats() lost speculation keys: {spec - st['speculation'].keys()}"
    assert st["speculation"]["enabled"] is True    # default-on server
    # pipelined serve loop keys (docs/serving.md) ride alongside in
    # their own block — the pipeline bench and dashboards key on these
    pipe = {"enabled", "depth", "launches", "retired_behind",
            "pending", "host_stall_ms", "host_plan_ms"}
    assert not pipe - st["pipeline"].keys(), \
        f"stats() lost pipeline keys: {pipe - st['pipeline'].keys()}"
    assert st["pipeline"]["enabled"] is True       # default-on server
    assert st["pipeline"]["pending"] == 0          # idle server
    # ops-plane tier (docs/observability.md, "Ops plane & watchdog"):
    # the programs/watchdog/ops blocks ride alongside — the router,
    # ops_probe, and dashboards key on these
    progs = {"enabled", "by_program", "total_wall_ms",
             "total_compile_ms"}
    assert not progs - st["programs"].keys(), \
        f"stats() lost programs keys: {progs - st['programs'].keys()}"
    assert st["programs"]["enabled"] is True       # default-on server
    assert st["programs"]["by_program"]            # launches tallied
    wd = {"enabled", "stalled", "stalls", "deadline_s"}
    assert not wd - st["watchdog"].keys(), \
        f"stats() lost watchdog keys: {wd - st['watchdog'].keys()}"
    assert st["watchdog"]["enabled"] is False      # off by default
    ops = {"enabled", "port", "requests"}
    assert not ops - st["ops"].keys(), \
        f"stats() lost ops keys: {ops - st['ops'].keys()}"
    assert st["ops"]["enabled"] is False           # off by default
    # tensor-parallel serving block (docs/serving.md,
    # "Tensor-parallel serving"): pinned even unsharded — the tp
    # bench and dashboards key on these
    shard = {"enabled", "tp", "axis", "devices", "mesh",
             "kv_pool_bytes_per_device", "collective_programs"}
    assert not shard - st["sharding"].keys(), \
        f"stats() lost sharding keys: {shard - st['sharding'].keys()}"
    assert st["sharding"]["enabled"] is False      # no mesh passed
    assert st["sharding"]["tp"] == 1
    assert st["sharding"]["collective_programs"] == 0
    # unsharded: the per-device pool IS the logical pool
    assert st["memory"]["pool_bytes_per_device"] == \
        st["memory"]["pool_bytes"]
    # hierarchical KV offload block (docs/serving.md, "Hierarchical
    # KV offload"): pinned even with the tier off — ops_probe
    # --offload and capacity dashboards key on these
    off = {"enabled", "demotes", "demote_failed", "promotes_host",
           "promotes_disk", "spills", "crc_rejects", "disk_torn",
           "capacity_skips", "host_dropped", "host_entries",
           "host_bytes", "host_bytes_cap", "disk_entries",
           "spill_dir", "promote_ms"}
    assert not off - st["offload"].keys(), \
        f"stats() lost offload keys: {off - st['offload'].keys()}"
    assert st["offload"]["enabled"] is False       # off by default
    assert st["offload"]["transport_skips"] == 0
    # KV transport block (docs/serving.md, "KV transport"): pinned
    # even on the default in-process backend — ops_probe --transport
    # and the chaos soak's envelope invariants key on these
    tr = {"backend", "peers", "attempts", "retries", "delivered",
          "rejects", "failures", "deadline_exceeded",
          "breaker_fastfail", "ingested", "dedup_hits", "per_peer"}
    assert not tr - st["transport"].keys(), \
        f"stats() lost transport keys: {tr - st['transport'].keys()}"
    assert st["transport"]["backend"] == "inprocess"
    assert "offload" in st["transport"]["per_peer"]
    assert st["transport"]["per_peer"]["offload"]["breaker"] == "closed"
    # evictable bytes price the cold reclaimable tier of the device
    # pool (blocks_evictable * bytes_per_block) — the offload bench
    # and ops_probe --offload render this
    assert st["memory"]["evictable_bytes"] == \
        st["memory"]["blocks_evictable"] \
        * st["memory"]["bytes_per_block"]
    lat = st["latency"]
    assert set(lat) == {"ttft_ms", "queue_wait_ms", "decode_token_ms",
                        "itl_ms", "step_ms",
                        "queue_wait_by_priority_ms"}
    # both requests ran at the default priority class
    assert set(lat["queue_wait_by_priority_ms"]) == {0}
    assert lat["queue_wait_by_priority_ms"][0]["count"] == 2
    # both requests finished: their timelines fed the histograms
    assert lat["ttft_ms"]["count"] == 2
    assert lat["queue_wait_ms"]["count"] == 2
    assert lat["ttft_ms"]["p50"] <= lat["ttft_ms"]["p99"]
    for req in server.scheduler.finished:
        tl = req.timeline()
        assert tl["submitted_at"] <= tl["admitted_at"] \
            <= tl["first_token_at"] <= tl["finished_at"]


def test_greedy_sample_rejects_ints_and_breaks_ties_low(tiny):
    """The bit-exactness contract speculation relies on: ties break
    toward the LOWEST token id (np.argmax's first-maximum rule), so a
    verify row's argmax resolves identically to a decode row's; and
    non-floating inputs raise instead of silently argmaxing token
    ids."""
    del tiny
    from apex_tpu.serving import greedy_sample

    tied = np.zeros((3, 8), np.float32)
    tied[0, [2, 5]] = 1.0        # tie between 2 and 5 -> 2
    tied[1, [0, 7]] = 3.5        # tie between 0 and 7 -> 0
    tied[2, :] = -1.0            # full tie -> 0
    assert greedy_sample(tied).tolist() == [2, 0, 0]
    # shape-generic: a (V,) row and a (B, K, V) verify block
    assert int(greedy_sample(tied[0])) == 2
    assert greedy_sample(np.stack([tied, tied])).shape == (2, 3)
    for bad in (np.array([[1, 2, 3]], np.int32),
                np.array([1, 2, 3], np.int64)):
        with pytest.raises(TypeError, match="floating"):
            greedy_sample(bad)
    # float16/bfloat16-as-float32 logits stay accepted
    assert greedy_sample(tied.astype(np.float16)).tolist() == [2, 0, 0]


def test_chunk_longer_than_its_program_is_refused(tiny):
    """The chunk program's width is the caller's to name (the server
    passes its ``prefill_chunk``): a chunk longer than it raises
    instead of being cut."""
    cfg, params, _ = tiny
    server = InferenceServer(cfg, params, max_batch_size=2,
                             max_context=100, block_size=8,
                             cache_dtype=jnp.float32)
    assert server.prefill_chunk == 100        # min(256, max_context)
    eng = server.engine
    blocks = eng.allocator.alloc(3)
    with pytest.raises(ValueError, match="exceeds pad_to=16"):
        eng.chunk_prefill(list(range(17)), 0, blocks, pad_to=16)
    assert eng.compile_counts() == (0, 0)
