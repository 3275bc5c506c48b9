"""Speculative decoding: the bit-exact greedy acceptance oracle.

The headline contract is that speculation NEVER changes output: a
server with speculative decoding enabled must generate token-for-token
what the same params generate with it disabled (and what the
full-recompute forward generates) — including under forced preemption,
forced prefix-cache eviction, verify-call OOM bursts, and poisoned
verify logits.  Acceptance keeps only drafts matching the model's own
argmax, so a wrong draft can cost wasted verify width but never a
wrong token; these tests additionally assert speculation actually
ENGAGED (acceptance > 0) so the parity isn't vacuous.

The second pillar is compile discipline: the verify program must trace
exactly once per speculation width however drafts and batch
composition vary (``DecodeEngine.verify_compiles``), and lookahead
blocks must roll back after every verify step (the KV-rollback
half of the block-budgeting contract).
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from apex_tpu import models
from apex_tpu.serving import InferenceServer, NgramDraft, SamplingParams
from apex_tpu.serving.speculation import DraftSource

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


def naive_generate(oracle_step, prompt, n, pad_to=128):
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


def _server(cfg, params, spec=True, **kw):
    kw.setdefault("cache_dtype", jnp.float32)
    kw.setdefault("max_context", 128)
    kw.setdefault("block_size", 8)
    return InferenceServer(cfg, params, enable_speculation=spec, **kw)


def _audited_generate(server, prompts, max_new, eos_id=None):
    # these parity oracles assume argmax pacing: pin default-greedy
    # sampling explicitly (docs/serving.md, "Stochastic sampling")
    reqs = [server.submit(p, max_new, eos_id,
                          sampling=SamplingParams())
            for p in prompts]
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
                            f"token {t}: speculative={x} baseline={y}")


# -- headline parity oracle -----------------------------------------------

def test_spec_parity_64_tokens_vs_off_and_oracle(tiny):
    """The acceptance oracle: >= 64 generated tokens per request,
    speculation on vs off AND vs the full-recompute forward, audited
    every step — with speculation demonstrably engaged and exactly one
    verify program compiled."""
    cfg, params, oracle_step = tiny
    rng = np.random.RandomState(0)
    prompts = [list(rng.randint(0, VOCAB, size=n))
               for n in (10, 17, 5, 23)]
    off = _server(cfg, params, spec=False, max_batch_size=2)
    want = _audited_generate(off, prompts, 64)

    srv = _server(cfg, params, spec=True, max_batch_size=2)
    got = _audited_generate(srv, prompts, 64)
    _assert_parity(got, want, "spec-on-vs-off")
    for p, o in zip(prompts, got):
        assert o == naive_generate(oracle_step, p, 64), p

    sp = srv.stats()["speculation"]
    assert sp["enabled"] is True
    assert sp["accepted_tokens"] > 0, "speculation never engaged"
    assert 0.0 < sp["acceptance_rate"] <= 1.0
    assert sp["verify_steps"] > 0
    # >= 2x decoded tokens per engine step on this (self-repetitive)
    # traffic — the bench floor, holding in-suite too
    assert sp["tokens_per_engine_step"] >= 2.0, sp
    assert sp["verify_compiles"] == 1, \
        f"verify recompiled: {sp['verify_compiles']} programs"
    assert srv.engine.verify_compiles() == 1
    # drafted/accepted histograms saw every verify step
    assert sp["drafted_per_step"]["count"] > 0
    assert sp["accepted_per_step"]["count"] > 0
    # speculation-off server never traced a verify program
    assert off.stats()["speculation"]["verify_compiles"] == 0


def test_spec_parity_under_forced_preemption(tiny):
    """A pool too small for the running set forces preemption while
    speculation is on (lookahead competing for the same blocks);
    resumed requests must stay bit-stable."""
    cfg, params, _ = tiny
    prompts = [[3, 1, 4, 1, 5, 9, 2, 6],
               [2, 7, 1, 8, 2, 8, 1, 8],
               [9, 9, 8, 7, 6, 5, 4, 3]]
    kw = dict(max_batch_size=3, max_context=64, block_size=4,
              num_blocks=10)                    # 9 usable = 36 tokens
    want = _audited_generate(_server(cfg, params, spec=False, **kw),
                             prompts, 24)
    srv = _server(cfg, params, spec=True, **kw)
    got = _audited_generate(srv, prompts, 24)
    _assert_parity(got, want, "spec-preemption")
    st = srv.stats()
    assert st["preemptions"] >= 1              # pressure actually hit
    assert st["speculation"]["accepted_tokens"] > 0
    srv.scheduler.audit()


def test_spec_parity_under_forced_eviction(tiny):
    """Waves whose blocks can only come from LRU eviction of the
    prefix cache, speculation on — eviction (including of lookahead-
    adjacent holds) must not perturb outputs."""
    cfg, params, _ = tiny
    rng = np.random.RandomState(7)
    wave1 = [list(rng.randint(0, VOCAB, size=20)) for _ in range(2)]
    wave2 = [list(rng.randint(0, VOCAB, size=20)) for _ in range(2)]
    kw = dict(max_batch_size=2, max_context=64, block_size=4,
              num_blocks=20, prefill_chunk=8)

    base = _server(cfg, params, spec=False, **kw)
    want = [_audited_generate(base, w, 16)
            for w in (wave1, wave2, wave1)]
    srv = _server(cfg, params, spec=True, **kw)
    got = [_audited_generate(srv, w, 16)
           for w in (wave1, wave2, wave1)]
    for g, w, tag in zip(got, want, ("w1", "w2", "w1-rerun")):
        _assert_parity(g, w, f"spec-eviction-{tag}")
    st = srv.stats()
    assert st["prefix_evicted_blocks"] > 0
    assert st["speculation"]["accepted_tokens"] > 0


def test_spec_parity_with_eos_inside_draft(tiny):
    """EOS accepted mid-draft must terminate exactly where one-token
    decode would."""
    cfg, params, oracle_step = tiny
    prompt = [5, 4, 3, 2, 1]
    ref = naive_generate(oracle_step, prompt, 32)
    eos = ref[20]           # deep enough to be inside the cycle the
    #                         drafts predict, so it arrives in a draft
    stop = ref.index(eos) + 1
    srv = _server(cfg, params, spec=True, max_batch_size=2)
    out = _audited_generate(srv, [prompt], 32, eos_id=eos)[0]
    assert out == ref[:stop]
    assert srv.scheduler.finished[0].finish_reason == "eos"
    srv.scheduler.audit()


# -- fault isolation on the verify path -----------------------------------

def test_verify_oom_is_retried_bit_exactly(tiny):
    """A MemoryError out of the verify call skips the iteration and
    retries bit-identically (drafts are pure functions of history)."""
    cfg, params, _ = tiny
    prompts = [[3, 1, 4, 1, 5, 9, 2, 6], [2, 7, 1, 8]]
    # pipeline off: the fault injects through engine.verify, which the
    # pipelined loop bypasses (its launch-time OOM path has its own
    # test in tests/L0/test_pipeline.py)
    baseline = _server(cfg, params, spec=True, max_batch_size=2,
                       enable_pipeline=False) \
        .generate(prompts, max_new_tokens=16)

    srv = _server(cfg, params, spec=True, max_batch_size=2,
                  enable_pipeline=False)
    orig = srv.engine.verify
    calls = {"n": 0}

    def flaky(tokens, lengths, positions, tables):
        calls["n"] += 1
        if calls["n"] in (2, 3):
            raise MemoryError("injected HBM burst")
        return orig(tokens, lengths, positions, tables)

    srv.engine.verify = flaky
    got = _audited_generate(srv, prompts, 16)
    _assert_parity(got, baseline, "verify-oom")
    st = srv.stats()
    assert st["oom_events"] == 2
    assert st["requests_failed_total"] == 0
    srv.scheduler.audit()


def test_verify_nonfinite_evicts_only_poisoned_request(tiny):
    """Poison one slot's verify logits: that request fails
    'nonfinite' before ANY of its drafted tokens can be accepted; the
    other request completes bit-identically."""
    cfg, params, _ = tiny
    prompts = [[3, 1, 4, 1, 5, 9, 2, 6], [2, 7, 1, 8, 2, 8, 1, 8]]
    # pipeline off: the poison injects through engine.verify, which
    # the pipelined loop bypasses (finite-flag poisoning of the fused
    # path is covered by tests/L0/test_pipeline.py)
    baseline = _server(cfg, params, spec=True, max_batch_size=2,
                       enable_pipeline=False) \
        .generate(prompts, max_new_tokens=16)

    srv = _server(cfg, params, spec=True, max_batch_size=2,
                  enable_pipeline=False)
    victim = srv.submit(prompts[0], 16)
    other = srv.submit(prompts[1], 16)
    orig = srv.engine.verify
    calls = {"n": 0}

    def poisoned(tokens, lengths, positions, tables):
        out = np.array(orig(tokens, lengths, positions, tables))
        calls["n"] += 1
        if calls["n"] == 3:
            out[victim.slot] = np.nan
        return out

    srv.engine.verify = poisoned
    while srv.scheduler.has_work:
        srv.step()
        srv.scheduler.audit()
    assert victim.finish_reason == "nonfinite"
    assert len(victim.generated) < 16
    assert other.finish_reason == "length"
    assert list(other.generated) == baseline[1]
    assert srv.failures.count("requests_failed_nonfinite") == 1


# -- block budgeting / KV rollback ----------------------------------------

def test_lookahead_rolls_back_every_step(tiny):
    """After every iteration, no decoding request holds blocks beyond
    what its next token needs — verify lookahead is borrowed, not
    kept — and at the end everything is reclaimable."""
    cfg, params, _ = tiny
    # pipeline off: the per-step no-lookahead-kept probe is a property
    # of the borrow-within-iteration synchronous loop; the pipelined
    # loop legitimately holds the launched window's lookahead until
    # retire (bounded — pinned by tests/L0/test_pipeline.py)
    srv = _server(cfg, params, spec=True, max_batch_size=2,
                  block_size=4, enable_pipeline=False)
    reqs = [srv.submit([3, 1, 4, 1, 5], 32),
            srv.submit([2, 7, 1, 8], 32)]
    bs = srv.engine.block_size
    while srv.scheduler.has_work:
        srv.step()
        srv.scheduler.audit()
        for r in srv.scheduler.running.values():
            if not r.prefilling:
                # at most the block the next token writes into; a
                # block-aligned num_cached may sit one short until
                # ensure_decode_capacity grows it next iteration
                assert len(r.block_table) <= r.num_cached // bs + 1, \
                    (f"request {r.uid} kept {len(r.block_table)} "
                     f"blocks with num_cached={r.num_cached}")
    assert all(r.finish_reason == "length" for r in reqs)
    usable = srv.engine.cache_cfg.num_blocks - 1
    assert srv.engine.allocator.num_free \
        + srv.scheduler.prefix_cache.num_evictable == usable


def test_draft_budget_never_overshoots_max_new_tokens(tiny):
    """A request one token from its budget must not waste verify
    width — and must stop exactly at max_new_tokens even when drafts
    would run past it."""
    cfg, params, _ = tiny
    srv = _server(cfg, params, spec=True, max_batch_size=2)
    out = _audited_generate(srv, [[1, 2, 1, 2, 1, 2]], 5)[0]
    assert len(out) == 5
    req = srv.scheduler.finished[0]
    assert req.finish_reason == "length"
    # lifetime accounting is consistent
    assert req.spec_accepted <= req.spec_drafted


# -- configuration seams --------------------------------------------------

def test_opt_out_restores_one_token_decode(tiny):
    cfg, params, _ = tiny
    srv = _server(cfg, params, spec=False, max_batch_size=2)
    assert srv.speculating is False
    out = srv.generate([[1, 2, 1, 2, 1, 2]], max_new_tokens=8)[0]
    assert len(out) == 8
    sp = srv.stats()["speculation"]
    assert sp["verify_steps"] == 0
    assert sp["decode_steps"] > 0
    assert sp["tokens_per_engine_step"] <= 1.0


def test_spec_tokens_validation(tiny):
    cfg, params, _ = tiny
    with pytest.raises(ValueError, match="spec_tokens"):
        _server(cfg, params, spec=True, spec_tokens=0)


def test_pluggable_draft_source(tiny):
    """A custom DraftSource (the small-model interface) drives the
    same verify/acceptance machinery; even an adversarially WRONG
    drafter cannot change output — only waste width."""
    cfg, params, _ = tiny

    class WrongDraft(DraftSource):
        def propose(self, tokens, k):
            return [(tokens[-1] + 17) % VOCAB] * k   # confidently wrong

    want = _server(cfg, params, spec=False, max_batch_size=2) \
        .generate([[4, 2, 4, 2]], max_new_tokens=16)
    srv = _server(cfg, params, spec=True, max_batch_size=2,
                  draft_source=WrongDraft())
    got = _audited_generate(srv, [[4, 2, 4, 2]], 16)
    _assert_parity(got, want, "wrong-drafter")
    sp = srv.stats()["speculation"]
    assert sp["drafted_tokens"] > 0
    # wrong guesses are mostly rejected but output never moved
    assert sp["acceptance_rate"] < 1.0

    class OutOfVocabDraft(DraftSource):
        def propose(self, tokens, k):
            return [VOCAB + 100] * k          # must never reach the
            #                                   embedding gather

    srv2 = _server(cfg, params, spec=True, max_batch_size=2,
                   draft_source=OutOfVocabDraft())
    got2 = _audited_generate(srv2, [[4, 2, 4, 2]], 16)
    _assert_parity(got2, want, "oob-drafter")
    assert srv2.stats()["speculation"]["drafted_tokens"] == 0


# -- NgramDraft unit tests ------------------------------------------------

def test_ngram_draft_extrapolates_periodic_history():
    d = NgramDraft(max_ngram=3, min_ngram=1)
    assert d.propose([7, 8, 7, 8, 7, 8], 4) == [7, 8, 7, 8]
    assert d.propose([5, 5, 5], 3) == [5, 5, 5]


def test_ngram_draft_prefers_longest_and_most_recent_match():
    d = NgramDraft(max_ngram=2, min_ngram=1)
    # suffix (1, 2): bigram occurred earlier followed by 9 — the
    # bigram match (9) must beat the more recent unigram match (4)
    assert d.propose([1, 2, 9, 3, 2, 4, 1, 2], 1) == [9]
    # two occurrences of the suffix unigram: the MOST RECENT wins
    assert d.propose([3, 8, 5, 3, 6, 0, 3], 1) == [6]


def test_ngram_draft_no_match_returns_empty():
    d = NgramDraft(max_ngram=3, min_ngram=1)
    assert d.propose([1, 2, 3, 4, 5], 4) == []
    assert d.propose([], 4) == []
    assert d.propose([1], 4) == []
    assert d.propose([1, 1], 0) == []


def test_ngram_draft_history_window_bounds_lookup():
    d = NgramDraft(max_ngram=1, min_ngram=1, history_window=4)
    # the only earlier occurrence of 9 sits outside the window
    assert d.propose([9, 7, 1, 2, 3, 9], 1) == []
    wide = NgramDraft(max_ngram=1, min_ngram=1, history_window=None)
    assert wide.propose([9, 7, 1, 2, 3, 9], 1) == [7]


def test_ngram_draft_validates_params():
    with pytest.raises(ValueError):
        NgramDraft(max_ngram=1, min_ngram=2)
    with pytest.raises(ValueError):
        NgramDraft(min_ngram=0)
    with pytest.raises(ValueError):
        NgramDraft(history_window=1)


# -- stats surface (satellite: pinned keys) --------------------------------

def test_speculation_stats_keys_are_pinned(tiny):
    """The stats()["speculation"] block the bench and dashboards key
    on — additions ride alongside, renames/drops fail here."""
    cfg, params, _ = tiny
    srv = _server(cfg, params, spec=True, max_batch_size=2)
    srv.generate([[1, 2, 1, 2]], max_new_tokens=8)
    sp = srv.stats()["speculation"]
    assert set(sp) >= {
        "enabled", "spec_tokens", "drafted_tokens", "accepted_tokens",
        "acceptance_rate", "verify_steps", "decode_steps",
        "decode_tokens", "tokens_per_engine_step", "verify_compiles",
        "drafted_per_step", "accepted_per_step",
    }
    assert sp["accepted_tokens"] <= sp["drafted_tokens"]
    assert sp["decode_tokens"] <= 8
