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


# -- the indexed drafter against the backward scan -------------------------

def scan_propose(tokens, k, max_ngram=3, min_ngram=1, history_window=512):
    """The plain backward scan the index must reproduce: for n from
    max_ngram down, the token after the most recent earlier occurrence
    of the last n tokens in the window, each guess joining the history."""
    hist = list(tokens)
    if history_window is not None and len(hist) > history_window:
        hist = hist[len(hist) - history_window:]
    out = []
    for _ in range(max(0, k)):
        nxt = None
        n_hist = len(hist)
        for n in range(min(max_ngram, n_hist - 1), min_ngram - 1, -1):
            suffix = tuple(hist[n_hist - n:])
            for i in range(n_hist - n - 1, -1, -1):
                if tuple(hist[i:i + n]) == suffix:
                    nxt = int(hist[i + n])
                    break
            if nxt is not None:
                break
        if nxt is None:
            break
        out.append(nxt)
        hist.append(nxt)
    return out


class ScanDraft(DraftSource):
    """The backward scan as a user's ``propose``-only draft source."""

    def propose(self, tokens, k):
        return scan_propose(tokens, k)


def _history(vocab, kind, length, rng):
    """Random ids, or a short random period with one token in ten
    replaced: the repetitive tails drafts extrapolate."""
    period = list(rng.randint(0, vocab, size=rng.randint(2, 8)))
    out = []
    for i in range(length):
        if kind == "periodic" and rng.rand() >= 0.1:
            out.append(int(period[i % len(period)]))
        else:
            out.append(int(rng.randint(0, vocab)))
    return out


def _grow_and_check(draft, hist, prompt_len, k, rng, **scan_kw):
    """Grow ``generated`` by 1 to k + 1 tokens a call, as verify
    accepts them, and hold the indexed drafts to the scan at every
    call.  Returns the last index and the number of indexes built."""
    prompt, generated = hist[:prompt_len], []
    index, built, pos = None, 0, prompt_len
    while True:
        got, new = draft.propose_indexed(prompt, generated, k, index)
        built += new is not index
        index = new
        want = scan_propose(prompt + generated, k, **scan_kw)
        assert got == want, (len(prompt) + len(generated), got, want)
        if pos >= len(hist):
            return index, built
        step = int(rng.randint(1, k + 2))
        generated.extend(hist[pos:pos + step])
        pos += step


@pytest.mark.parametrize("k", [0, 1, 4])
@pytest.mark.parametrize("max_ngram,min_ngram", [(3, 1), (2, 1), (3, 2)])
@pytest.mark.parametrize("history_window", [None, 2, 4, 512])
@pytest.mark.parametrize("kind", ["periodic", "random"])
@pytest.mark.parametrize("vocab", [3, 50, 65536])
def test_ngram_index_matches_backward_scan(vocab, kind, history_window,
                                           max_ngram, min_ngram, k):
    """The per-request index drafts exactly what the backward scan
    drafts, call after call, across window compactions (a window of
    512 is compacted past 1,024 tokens)."""
    rng = np.random.RandomState(vocab * 7 + len(kind) + (k << 4))
    length = 1100 if history_window == 512 else 300
    hist = _history(vocab, kind, length, rng)
    draft = NgramDraft(max_ngram, min_ngram, history_window)
    _grow_and_check(draft, hist, int(rng.randint(1, 40)), k, rng,
                    max_ngram=max_ngram, min_ngram=min_ngram,
                    history_window=history_window)


def test_ngram_index_rebuilds_on_a_history_it_did_not_index():
    """A history that does not extend the indexed one — another
    request's, or one cut short — is indexed afresh, and the drafts
    follow the history handed in, not the index."""
    d = NgramDraft()
    a = [1, 2, 3, 1, 2, 3, 1, 2]
    got, index = d.propose_indexed(a[:4], a[4:], 4)
    assert got == scan_propose(a, 4) == [3, 1, 2, 3]
    b = [7, 8, 9, 7, 8, 9, 7, 8, 9, 7]
    got, again = d.propose_indexed(b[:4], b[4:], 4, index)
    assert again is not index
    assert got == scan_propose(b, 4) == [8, 9, 7, 8]
    got, cut = d.propose_indexed(b[:4], b[4:6], 4, again)
    assert cut is not again
    assert got == scan_propose(b[:6], 4)
    # an extension keeps the index; another drafter's index is not used
    got, same = d.propose_indexed(b[:4], b[4:8], 4, cut)
    assert same is cut and got == scan_propose(b[:8], 4)
    _, other = NgramDraft(max_ngram=2).propose_indexed(b[:4], b[4:8], 4,
                                                       same)
    assert other is not same


def test_ngram_index_stays_within_twice_the_window():
    """Grown call by call to 10,000 tokens, the index keeps at most
    twice the window, drafts as the scan does all the way, and is
    rebuilt about once a window."""
    rng = np.random.RandomState(3)
    hist = _history(50, "periodic", 10000, rng)
    window = 512
    index, built = _grow_and_check(NgramDraft(history_window=window),
                                   hist, 100, 4, rng)
    assert len(index.toks) <= 2 * window
    assert max(len(m) for m in index.maps if m is not None) <= 2 * window
    assert built <= len(hist) // window + 1, built


@pytest.mark.parametrize("stochastic", [False, True])
def test_server_drafts_match_the_scan(tiny, stochastic):
    """The server with its default indexed drafter and with the scan
    as a user's ``propose``-only source: the same tokens out, and the
    same drafts, verify launches and decode launches on the way."""
    cfg, params, _ = tiny
    rng = np.random.RandomState(11)
    prompts = [list(rng.randint(0, VOCAB, size=n)) for n in (9, 30, 4)]
    prompts.append([5, 6, 7] * 6)

    def serve(source):
        srv = _server(cfg, params, max_batch_size=2, draft_source=source)
        reqs = [srv.submit(p, 40, sampling=(
            SamplingParams(temperature=0.8, top_p=0.95, seed=i + 1)
            if stochastic else SamplingParams()))
            for i, p in enumerate(prompts)]
        while srv.scheduler.has_work:
            srv.step()
        return [list(r.generated) for r in reqs], \
            srv.stats()["speculation"]

    got, sp = serve(NgramDraft())
    want, sp_scan = serve(ScanDraft())
    _assert_parity(got, want, "indexed-vs-scan")
    for key in ("drafted_tokens", "accepted_tokens", "verify_steps",
                "decode_steps", "draft_calls"):
        assert sp[key] == sp_scan[key], (key, sp[key], sp_scan[key])
    assert sp["drafted_tokens"] > 0
    # sampled at 0.8, the tiny model's draws seldom repeat a draft
    assert stochastic or sp["accepted_tokens"] > 0
    # one index a request, none for a source that keeps none
    assert sp["draft_index_rebuilds"] == len(prompts)
    assert sp_scan["draft_index_rebuilds"] == 0


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
        "drafted_per_step", "accepted_per_step", "draft_calls",
        "draft_index_rebuilds",
    }
    assert sp["accepted_tokens"] <= sp["drafted_tokens"]
    assert sp["decode_tokens"] <= 8
    assert 1 == sp["draft_index_rebuilds"] <= sp["draft_calls"]
