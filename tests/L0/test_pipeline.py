"""Pipelined serve loop: dispatch-ahead must be a REORDERING of the
synchronous loop, never a different computation.

The load-bearing oracle is bit-exact greedy parity between the
pipelined (``enable_pipeline=True``, the default) and synchronous
loops over 64+ generated tokens — under plain decode, speculation,
forced preemption, forced prefix-cache eviction, mid-stream
``drain()``, launch-time OOM, and finite-flag poisoning of the fused
programs.  Greedy argmax is order-independent, so ANY divergence means
the retire/plan/launch split changed a scheduling decision the
synchronous loop would have made differently — exactly the bug class
this file exists to catch.

The second pillar is the fused on-device sampling contract:
``ops.greedy_argmax`` must match the host-side ``greedy_sample``
bit-exactly for fp32 AND bf16 logits including exact ties (lowest
token id wins) — speculative acceptance compares argmax-to-argmax, so
one differently-resolved tie would silently change accepted drafts.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from apex_tpu import models
from apex_tpu.ops.sampling import finite_rows, greedy_argmax
from apex_tpu.serving import InferenceServer, SamplingParams, greedy_sample

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

    @jax.jit
    def oracle_step(ids, mask):
        return m.apply({"params": params}, ids, attention_mask=mask)

    return cfg, params, oracle_step


def _server(cfg, params, *, pipeline, **kw):
    kw.setdefault("cache_dtype", jnp.float32)
    return InferenceServer(cfg, params, enable_pipeline=pipeline, **kw)


def _audited_generate(server, prompts, n, **kw):
    # these parity oracles assume argmax pacing: pin default-greedy
    # sampling explicitly (docs/serving.md, "Stochastic sampling")
    kw.setdefault("sampling", SamplingParams())
    reqs = [server.submit(p, n, **kw) for p in prompts]
    while server.scheduler.has_work:
        server.step()
        server.scheduler.audit()
    return [list(r.generated) for r in reqs]


def _assert_parity(got, want, what):
    for i, (a, b) in enumerate(zip(got, want)):
        assert a == b, (f"{what}: request {i} diverged: "
                        f"pipelined={a} synchronous={b}")


# -- the fused-sampling contract (on-device argmax == greedy_sample) -------

def test_greedy_argmax_matches_greedy_sample_bit_exactly():
    """fp32 AND bf16, exact ties included: the device argmax must
    resolve every row exactly as ``np.argmax`` would on the host —
    lowest token id wins — or speculative acceptance would accept
    different drafts on the two paths."""
    fast = jax.jit(greedy_argmax)
    for dtype in (jnp.float32, jnp.bfloat16):
        for trial in range(50):
            rng = np.random.RandomState(trial)
            logits = rng.randn(4, 97).astype(np.float32)
            if trial % 2 == 0:
                # force exact ties, including at the row max
                row = trial % 4
                logits[row, rng.choice(97, 7, replace=False)] = \
                    logits[row].max()
            dev = jnp.asarray(logits).astype(dtype)
            # the host reference samples the SAME (possibly rounded)
            # values the device sees
            host = np.asarray(dev).astype(np.float32)
            assert (np.asarray(fast(dev))
                    == greedy_sample(host)).all(), (dtype, trial)
    # documented canonical tie cases (mirrors greedy_sample's test)
    tied = np.zeros((3, 8), np.float32)
    tied[0, [2, 5]] = 1.0
    tied[1, [0, 7]] = 3.5
    tied[2, :] = -1.0
    for dtype in (jnp.float32, jnp.bfloat16):
        assert np.asarray(
            fast(jnp.asarray(tied).astype(dtype))).tolist() == [2, 0, 0]
    # shape-generic like greedy_sample: (V,) and (B, K, V)
    assert int(fast(jnp.asarray(tied[0]))) == 2
    assert np.asarray(fast(jnp.asarray(
        np.stack([tied, tied])))).shape == (2, 3)


def test_finite_rows_matches_host_guard():
    x = np.zeros((4, 8), np.float32)
    x[1, 3] = np.nan
    x[2, 0] = np.inf
    got = np.asarray(jax.jit(finite_rows)(jnp.asarray(x)))
    want = np.all(np.isfinite(x), axis=-1)
    assert (got == want).all()


# -- the parity oracle ------------------------------------------------------

def test_pipelined_matches_synchronous_and_oracle_64_tokens(tiny):
    """The acceptance bar: 64 generated tokens, token-for-token, vs
    BOTH the synchronous loop and the full-recompute oracle."""
    cfg, params, oracle_step = tiny
    prompt = [3, 1, 4, 1, 5, 9, 2, 6]
    got = _server(cfg, params, pipeline=True, max_batch_size=2,
                  max_context=128, block_size=8) \
        .generate([prompt], max_new_tokens=64)[0]
    want = _server(cfg, params, pipeline=False, max_batch_size=2,
                   max_context=128, block_size=8) \
        .generate([prompt], max_new_tokens=64)[0]
    assert len(got) == 64
    _assert_parity([got], [want], "64-token")
    # and against the training-forward oracle (full recompute)
    toks = list(prompt)
    ids = np.zeros((1, 128), np.int32)
    mask = np.zeros((1, 128), np.int32)
    for _ in range(64):
        ln = len(toks)
        ids[0, :ln] = toks
        mask[0, :ln] = 1
        logits = oracle_step(jnp.asarray(ids), jnp.asarray(mask))
        toks.append(int(np.argmax(np.asarray(logits[0, ln - 1]))))
    assert got == toks[len(prompt):]


def test_parity_under_forced_preemption(tiny):
    """A pool too small for the running set forces preemption; the
    pipelined loop must preempt the same victims at the same points
    (the in-flight hold must never change the choice — the window is
    empty whenever the planner runs)."""
    cfg, params, _ = tiny
    prompts = [[3, 1, 4, 1, 5, 9, 2, 6],
               [2, 7, 1, 8, 2, 8, 1, 8],
               [9, 9, 8, 7, 6, 5, 4, 3]]
    kw = dict(max_batch_size=3, max_context=64, block_size=4,
              num_blocks=10)
    srv = _server(cfg, params, pipeline=True, **kw)
    got = _audited_generate(srv, prompts, 24)
    want = _audited_generate(
        _server(cfg, params, pipeline=False, **kw), prompts, 24)
    _assert_parity(got, want, "forced-preemption")
    assert srv.stats()["preemptions"] >= 1     # pressure actually hit


def test_parity_under_forced_prefix_eviction(tiny):
    """Sequential shared-prefix traffic on a pool too small to keep
    every cache hold resident: LRU eviction fires, and the pipelined
    loop must evict identically (eviction happens inside planning,
    where the window is empty)."""
    cfg, params, _ = tiny
    rng = np.random.RandomState(7)
    shared = list(rng.randint(0, VOCAB, size=12))
    prompts = [shared + list(rng.randint(0, VOCAB, size=4))
               for _ in range(4)]
    kw = dict(max_batch_size=2, max_context=64, block_size=4,
              num_blocks=14)
    srv = _server(cfg, params, pipeline=True, **kw)
    got = _audited_generate(srv, prompts, 16)
    want = _audited_generate(
        _server(cfg, params, pipeline=False, **kw), prompts, 16)
    _assert_parity(got, want, "forced-eviction")
    assert srv.stats()["prefix_evicted_blocks"] >= 1


def test_parity_speculation_on_and_off(tiny):
    """Pipelining composes with speculative decoding (verify launches
    dispatch ahead too) and with speculation disabled."""
    cfg, params, _ = tiny
    prompts = [[1, 2, 3, 1, 2, 3, 1, 2],       # repetitive: drafts fire
               [5, 9, 2, 6, 5, 3, 5, 8]]
    for spec in (True, False):
        kw = dict(max_batch_size=2, max_context=128, block_size=8,
                  enable_speculation=spec)
        got = _audited_generate(
            _server(cfg, params, pipeline=True, **kw), prompts, 32)
        want = _audited_generate(
            _server(cfg, params, pipeline=False, **kw), prompts, 32)
        _assert_parity(got, want, f"speculation={spec}")


def test_parity_with_midstream_drain(tiny):
    """drain() begun mid-generation flushes the dispatch-ahead window
    deterministically: in-flight completions are bit-identical to an
    undrained run."""
    cfg, params, _ = tiny
    prompt = [3, 1, 4, 1, 5, 9, 2, 6]
    want = _server(cfg, params, pipeline=False, max_batch_size=2,
                   max_context=128, block_size=8) \
        .generate([prompt], max_new_tokens=24)[0]
    srv = _server(cfg, params, pipeline=True, max_batch_size=2,
                  max_context=128, block_size=8)
    req = srv.submit(prompt, 24)
    for _ in range(6):                  # mid-stream, window pending
        srv.step()
    srv.drain()
    assert req.finished and list(req.generated) == want
    # the drained server's window is flushed and its stats settled
    st = srv.stats()
    assert st["pipeline"]["pending"] == 0
    assert st["draining"] is True


def test_launch_oom_retires_bit_identically_across_window(tiny):
    """A chaos-style MemoryError at the verify LAUNCH (the pipelined
    analog of the verify-OOM skip-and-retry): the iteration is
    skipped, lookahead rolls back, and the retry next iteration is
    bit-identical — while a pending window from the previous
    iteration still retires cleanly."""
    cfg, params, _ = tiny
    prompts = [[3, 1, 4, 1, 5, 9, 2, 6], [2, 7, 1, 8]]
    kw = dict(max_batch_size=2, max_context=128, block_size=8)
    baseline = _audited_generate(
        _server(cfg, params, pipeline=True, **kw), prompts, 16)

    srv = _server(cfg, params, pipeline=True, **kw)
    orig = srv.engine.verify_sampled
    calls = {"n": 0}

    def flaky(tokens, lengths, positions, tables):
        calls["n"] += 1
        if calls["n"] in (2, 3):
            raise MemoryError("injected HBM burst")
        return orig(tokens, lengths, positions, tables)

    srv.engine.verify_sampled = flaky
    got = _audited_generate(srv, prompts, 16)
    _assert_parity(got, baseline, "launch-oom")
    st = srv.stats()
    assert st["oom_events"] == 2
    assert st["requests_failed_total"] == 0


def test_finite_flag_poison_evicts_only_poisoned_request(tiny):
    """The fused-path non-finite guard: flipping one slot's finite
    flag (what a NaN row becomes on device) fails exactly that
    request at retire; the other completes bit-identically."""
    cfg, params, _ = tiny
    prompts = [[3, 1, 4, 1, 5, 9, 2, 6], [2, 7, 1, 8, 2, 8, 1, 8]]
    kw = dict(max_batch_size=2, max_context=64, block_size=8,
              enable_speculation=False)
    baseline = _audited_generate(
        _server(cfg, params, pipeline=True, **kw), prompts, 12)

    srv = _server(cfg, params, pipeline=True, **kw)
    victim = srv.submit(prompts[0], 12)
    other = srv.submit(prompts[1], 12)
    orig = srv.engine.decode_sampled
    calls = {"n": 0}

    def poisoned(tokens, positions, tables):
        ids, fin = orig(tokens, positions, tables)
        calls["n"] += 1
        if calls["n"] == 3:
            fin = fin.at[victim.slot].set(False)
        return ids, fin

    srv.engine.decode_sampled = poisoned
    while srv.scheduler.has_work:
        srv.step()
        srv.scheduler.audit()
    assert victim.finish_reason == "nonfinite"
    # tokens before the poisoned call: the prefill-sampled first token
    # plus decode launches 1 and 2 (launch 3 carries the poison)
    assert len(victim.generated) == 3
    assert victim.generated == baseline[0][:3]
    assert other.finish_reason == "length"
    assert list(other.generated) == baseline[1]


def test_prefill_launch_oom_replays_chunk(tiny):
    """MemoryError out of the fused chunk program: the chunk replays
    next iteration and generation stays bit-stable."""
    cfg, params, _ = tiny
    prompts = [[3, 1, 4, 1], [5, 9, 2, 6, 5, 3]]
    kw = dict(max_batch_size=2, max_context=64, block_size=8)
    baseline = _audited_generate(
        _server(cfg, params, pipeline=True, **kw), prompts, 8)

    srv = _server(cfg, params, pipeline=True, **kw)
    orig = srv.engine.chunk_prefill_sampled
    calls = {"n": 0}

    def flaky(tokens, start, block_table, pad_to=None):
        calls["n"] += 1
        if calls["n"] == 1:
            raise MemoryError("injected HBM burst")
        return orig(tokens, start, block_table, pad_to=pad_to)

    srv.engine.chunk_prefill_sampled = flaky
    got = _audited_generate(srv, prompts, 8)
    _assert_parity(got, baseline, "prefill-launch-oom")
    assert srv.stats()["oom_events"] == 1


# -- scheduling-state invariants -------------------------------------------

def test_inflight_hold_pins_window_and_audit_checks_it(tiny):
    """Between a launch and its retire the scheduler's in-flight hold
    pins the window's requests: audit() passes with the window
    pending, the preemption victim chooser skips held requests, and
    the hold always empties by the next plan phase."""
    cfg, params, _ = tiny
    srv = _server(cfg, params, pipeline=True, max_batch_size=2,
                  max_context=64, block_size=8,
                  enable_speculation=False)
    reqs = [srv.submit([1, 2, 3], 8), srv.submit([4, 5, 6, 7], 8)]
    sched = srv.scheduler
    saw_pending = False
    while sched.has_work:
        srv.step()
        if srv._inflight is not None:
            saw_pending = True
            assert set(sched.inflight) == \
                {r.uid for r in srv._inflight.running}
            # the victim chooser must refuse to evict held requests
            for r in srv._inflight.running:
                v = sched._preempt_victim(exclude=None)
                assert v is None or v.uid not in sched.inflight
        sched.audit()           # passes with the window pending
    assert saw_pending, "window never went pending"
    assert not sched.inflight
    assert all(r.finish_reason == "length" for r in reqs)


def test_lookahead_bounded_while_window_pending(tiny):
    """The pipelined analog of lookahead rollback: a decoding request
    may hold lookahead blocks only for the launched-but-unretired
    verify; by the next plan phase the rejected tail is returned, so
    the bound is next-token-need plus one window's spec budget."""
    cfg, params, _ = tiny
    srv = _server(cfg, params, pipeline=True, max_batch_size=2,
                  block_size=4)
    reqs = [srv.submit([3, 1, 4, 1, 5], 32),
            srv.submit([2, 7, 1, 8], 32)]
    bs = srv.engine.block_size
    spec_slack = -(-(srv.spec_tokens + 1) // bs) + 1
    while srv.scheduler.has_work:
        srv.step()
        srv.scheduler.audit()
        for r in srv.scheduler.running.values():
            if not r.prefilling:
                assert len(r.block_table) <= \
                    r.num_cached // bs + 1 + spec_slack, \
                    (f"request {r.uid} kept {len(r.block_table)} "
                     f"blocks with num_cached={r.num_cached}")
    assert all(r.finish_reason == "length" for r in reqs)
    usable = srv.engine.cache_cfg.num_blocks - 1
    assert srv.engine.allocator.num_free \
        + srv.scheduler.prefix_cache.num_evictable == usable


# -- observability ----------------------------------------------------------

def test_pipeline_stats_and_flight_fields_pinned(tiny):
    """The stats()["pipeline"] block and the flight record's
    per-step pipeline fields — dashboards and the bench key on these
    literally."""
    from apex_tpu.observability import FlightRecorder

    cfg, params, _ = tiny
    rec = FlightRecorder(capacity=256)
    srv = _server(cfg, params, pipeline=True, max_batch_size=2,
                  max_context=64, block_size=8, flight_recorder=rec)
    srv.generate([[1, 2, 3], [4, 5, 6, 7]], max_new_tokens=6)
    st = srv.stats()["pipeline"]
    assert set(st) == {"enabled", "depth", "launches",
                       "retired_behind", "pending", "host_stall_ms",
                       "host_plan_ms"}
    assert st["enabled"] is True and st["depth"] == 1
    assert st["launches"] >= 1
    assert st["retired_behind"] == st["launches"]   # window always drains
    assert st["pending"] == 0                       # idle server
    assert st["host_stall_ms"]["count"] == st["retired_behind"]
    assert st["host_plan_ms"]["count"] >= st["launches"]
    records = list(rec.records())
    assert records, "flight recorder captured nothing"
    for r in records:
        assert set(r["pipeline"]) == {"pending", "retired_tokens"}
    # every launched step was retired exactly one record later: total
    # retired tokens equals total produced decode-phase tokens
    spec = srv.stats()["speculation"]
    assert sum(r["pipeline"]["retired_tokens"] for r in records) == \
        spec["decode_tokens"]


def test_pipelined_compile_counts_match_audit_bounds(tiny):
    """The compile audit holds on the pipelined path: one decode
    program (the sampled twin), one chunk program for the one chunk
    width, one verify width."""
    cfg, params, _ = tiny
    rng = np.random.RandomState(0)
    prompts = [list(rng.randint(0, VOCAB, size=n))
               for n in (3, 9, 14, 17, 25, 31, 6, 23)]
    srv = _server(cfg, params, pipeline=True, max_batch_size=3,
                  max_context=64, block_size=8)
    srv.generate(prompts, max_new_tokens=12)
    pre, dec = srv.engine.compile_counts()
    assert dec == 1, f"decode recompiled: {dec} programs"
    assert pre == 1, f"{pre} chunk programs for one chunk width"
    assert srv.engine.verify_compiles() <= 1
