"""The generators: the same seed gives the same traffic, every seed the
same work in another order, latencies count from the due moment."""

import collections
import hashlib
import json

import numpy as np
import pytest

from benchmarks.harness import serve, spec, traffic

CHAT = spec.load_cell("gpt2xl-chat-open").traffic
DOCS = spec.load_cell("gpt2xl-doc-backlog").traffic
PACKED = spec.load_cell("gpt2m-train-1chip").traffic
PACKED4 = spec.load_cell("gpt2m-train-ddp4").traffic
BIG = 2 ** 31 + 12345          # the driver's seeds pass 32 signed bits


def shape(plan):
    return sorted((len(p.prompt), p.max_new, p.greedy) for p in plan)


def gaps(plan, a=0, b=None):
    return sorted(np.round(np.diff([0.0] + [p.due for p in plan])[a:b], 9))


@pytest.mark.parametrize("mix", [CHAT, DOCS], ids=["chat", "docs"])
def test_same_seed_same_requests_other_seed_same_work(mix):
    a = traffic.plan_requests(mix, BIG, 200, 50257)
    b = traffic.plan_requests(mix, BIG, 200, 50257)
    c = traffic.plan_requests(mix, 7, 200, 50257)
    assert [(p.prompt, p.max_new, p.due, p.sample_seed) for p in a] \
        == [(p.prompt, p.max_new, p.due, p.sample_seed) for p in b]
    assert shape(a) == shape(c)                       # the same work
    # dealt out in another order: no two seeds replay one schedule
    assert mix["stratum"] > 1
    assert [len(p.prompt) for p in a] != [len(p.prompt) for p in c]
    assert a[0].prompt != c[0].prompt
    if mix["loop"] == "open":
        assert [p.due for p in a] != [p.due for p in c]
    # in another order only within a stratum: every stretch of the run
    # carries the same load whatever the seed, and ends at the same time
    k = mix["stratum"]
    for i in range(0, 200, k):
        assert shape(a[i:i + k]) == shape(c[i:i + k])
        assert gaps(a, i, i + k) == gaps(c, i, i + k)
        assert a[min(i + k, 200) - 1].due == pytest.approx(
            c[min(i + k, 200) - 1].due)


# the accepted mixes' plans as the parent commit of PR 26 made them
# (sha256 of index, due, prompt ids, max_new, greedy and sample seed of
# the first 200 requests): a mix without "shared" plans what it planned.
# The chat mix at the rate it had there: PR 26 set it anew (0.8 of the
# knee of PR 25's programs), which scales the arrivals and nothing else
PARENT_CHAT_RATE = 0.7
PARENT_PLANS = {
    ("chat", 7): "81cb5ceb1d8ea02aefa1080b9d4dffcc794a369dda49026ff24f8c906"
                 "c74a11b",
    ("chat", BIG): "135bf48a5835f86ad943a4132422f70a7658ff093250f90b547b61f"
                   "7222bf88f",
    ("docs", 7): "7b978e5fed63684711703140ab52120661f6d0dabb5de69ccd1649ef9"
                 "984de69",
    ("docs", BIG): "bc0360e3e0494d3f10727db74e6ab4a992d0bcc00470b9f000e6254"
                   "89a2f195d",
}


@pytest.mark.parametrize("which,seed", sorted(PARENT_PLANS))
def test_a_mix_that_shares_nothing_plans_what_it_planned_id_for_id(
        which, seed):
    mix = {"chat": dict(CHAT, rate_per_s=PARENT_CHAT_RATE),
           "docs": DOCS}[which]
    plan = traffic.plan_requests(mix, seed, 200, 50257)
    text = json.dumps([(p.index, round(p.due, 9), p.prompt, p.max_new,
                        p.greedy, p.sample_seed) for p in plan])
    assert hashlib.sha256(text.encode()).hexdigest() \
        == PARENT_PLANS[which, seed]


SHARED = dict(DOCS, prompt={"dist": "uniform", "min": 384, "max": 768},
              shared={"asks": {"dist": "uniform", "min": 3, "max": 5},
                      "suffix": {"dist": "uniform", "min": 8, "max": 48},
                      "apart": 4})


def _documents(plan, head=384):
    """``{document's first ids: [places of its asks]}``."""
    docs = {}
    for p in plan:
        docs.setdefault(tuple(p.prompt[:head]), []).append(p.index)
    return docs


@pytest.mark.parametrize("loop", ["closed", "open"])
def test_shared_requests_ask_one_document_several_times(loop):
    mix = dict(SHARED, loop=loop, rate_per_s=2.0)
    a = traffic.plan_requests(mix, BIG, 240, 50257)
    b = traffic.plan_requests(mix, BIG, 240, 50257)
    c = traffic.plan_requests(mix, 7, 240, 50257)
    assert [(p.prompt, p.max_new, p.due, p.sample_seed, p.greedy)
            for p in a] == [(p.prompt, p.max_new, p.due, p.sample_seed,
                             p.greedy) for p in b]
    assert [p.index for p in a] == list(range(240))
    docs = _documents(a)
    whole = [places for places in docs.values() if places[-1] < 220]
    # 3 to 5 asks of a document, each the document's ids and then a
    # suffix of its own, at most ``apart`` places from the last
    assert {len(v) for v in whole} == {3, 4, 5}
    for head, places in docs.items():
        asks = [a[i].prompt for i in places]
        n = min(len(x) for x in asks) - 48
        assert len({tuple(x[:n]) for x in asks}) == 1
        assert len({tuple(x) for x in asks}) == len(asks)
        assert all(0 < j - i <= 4 for i, j in zip(places, places[1:]))
    for p in a:
        assert 384 + 8 <= len(p.prompt) <= 768 + 48
        assert 16 <= p.max_new <= 64
        assert len(p.prompt) + p.max_new <= 1024
    assert abs(np.mean([p.greedy for p in a]) - 0.125) < 0.01
    # another seed: the same documents and asks, dealt whole within
    # strata, so every stratum's asks fill the same places of the plan
    # (12 documents of 3 to 5 asks: a boundary every 60 places or less)
    assert a[0].prompt != c[0].prompt
    assert [len(p.prompt) for p in a] != [len(p.prompt) for p in c]
    one = lambda p: (len(p.prompt), p.max_new, p.greedy)
    diff, ends = collections.Counter(), [0]
    for i, (x, y) in enumerate(zip(a, c), start=1):
        diff[one(x)] += 1
        diff[one(y)] -= 1
        if not any(diff.values()):
            ends.append(i)
    assert len(ends) >= 5 and ends[-1] >= 180
    assert all(j - i <= 60 for i, j in zip(ends, ends[1:]))
    if loop == "open":
        assert [p.due for p in a] != [p.due for p in c]
        assert a[-1].due == pytest.approx(c[-1].due)
    with pytest.raises(ValueError, match="multiple"):
        traffic.plan_requests(dict(mix, stratum=6), 1, 10, 50257)


@pytest.mark.parametrize("mix,seconds,n", [(CHAT, 45, 272), (DOCS, 45, 460)],
                         ids=["chat", "docs"])
def test_the_plan_outlasts_the_run(mix, seconds, n):
    """Open loop: what the rate sends through the fill, the window and
    the longest drain; closed loop: the mix's own number."""
    assert traffic.planned_count(mix, seconds, serve.DRAIN_LIMIT_S) == n
    plan = traffic.plan_requests(mix, BIG, n, 50257)
    if mix["loop"] == "open":
        assert plan[-1].due > mix["fill_s"] + seconds + 30


def test_chat_mix_is_what_its_file_says():
    plan = traffic.plan_requests(CHAT, 3, 4000, 50257)
    prompts = np.array([len(p.prompt) for p in plan])
    outputs = np.array([p.max_new for p in plan])
    assert prompts.min() >= 16 and prompts.max() <= 640
    assert outputs.min() >= 8 and outputs.max() <= 256
    assert (prompts + outputs).max() <= 1024
    assert 110 < np.median(prompts) < 150 and 55 < np.median(outputs) < 75
    gaps = np.diff([p.due for p in plan])
    assert abs(gaps.mean() * CHAT["rate_per_s"] - 1.0) < 0.08    # Poisson
    assert abs(gaps.std() / gaps.mean() - 1.0) < 0.1
    greedy = np.mean([p.greedy for p in plan])
    assert abs(greedy - CHAT["greedy_share"]) < 0.01
    assert all(0 <= t < 50257 for p in plan[:50] for t in p.prompt)


def test_doc_mix_is_what_its_file_says():
    plan = traffic.plan_requests(DOCS, 3, 1000, 50257)
    prompts = np.array([len(p.prompt) for p in plan])
    outputs = np.array([p.max_new for p in plan])
    assert prompts.min() >= 512 and prompts.max() <= 960
    assert outputs.min() >= 16 and outputs.max() <= 64
    assert all(p.due == 0.0 for p in plan)        # closed loop
    assert abs(np.mean([p.greedy for p in plan]) - 0.125) < 0.01


@pytest.mark.parametrize("mix,batch", [(PACKED, 8), (PACKED4, 32)],
                         ids=["1chip", "ddp4"])
def test_packed_batches_are_full_rows_that_all_differ(mix, batch):
    assert batch == mix["batch_per_chip"] * (4 if mix is PACKED4 else 1)
    feed = traffic.packed_batches(mix, BIG, batch, 1024, 50257)
    again = traffic.packed_batches(mix, BIG, batch, 1024, 50257)
    other = traffic.packed_batches(mix, 5, batch, 1024, 50257)
    seen = []
    for _ in range(3):
        rows = next(feed)
        assert rows.shape == (batch, 1024) and rows.dtype == np.int32
        assert rows.min() >= 0 and rows.max() < 50257
        assert np.array_equal(rows, next(again))
        assert not np.array_equal(rows, next(other))
        seen.extend(map(bytes, rows))
        # documents of about 400 tokens joined by the separator
        n_sep = int((rows == 50256).sum())
        assert 5 * batch // 8 <= n_sep <= 60 * batch // 8
    assert len(set(seen)) == 3 * batch


def _sent(due, sent_at, stamps, reason="length", greedy=True):
    p = traffic.Planned(0, 0.0, [1, 2, 3], len(stamps), greedy, 0)
    return serve.Sent(p, None, due, sent_at, list(stamps), reason)


def test_latency_counts_from_the_due_moment_and_lateness_is_reported():
    run = {"t_open": 100.0, "t_close": 110.0, "sent": [
        # due 101.0, sent 40 ms late, first token 250 ms after it was due
        _sent(101.0, 101.04, [101.25, 101.30, 101.40]),
        _sent(102.0, 102.00, [102.10, 102.12]),
        # rejected at the door: counts as the longest wait, and as failed
        _sent(103.0, 103.00, [], reason="rejected"),
        # due before the window: not one of its requests, but its tokens
        # delivered inside the window count for throughput
        _sent(99.0, 99.0, [99.5, 100.5, 100.6]),
    ]}
    out = serve.end_to_end(run, 10.0)
    assert out["attempted"] == 3 and out["failed"] == 1
    assert out["ttft_p50_ms"] == pytest.approx(250.0)
    assert out["ttft_p95_ms"] == pytest.approx(10_000.0)   # the failed one
    assert out["gen_late_p95_ms"] == pytest.approx(40.0)
    assert out["itl_p95_ms"] == pytest.approx(100.0)
    assert out["itl_p50_ms"] == pytest.approx(50.0)    # of 20, 50, 100
    assert out["serve_tokens_per_s"] == pytest.approx((3 + 2 + 2) / 10.0)


def test_a_request_cut_by_the_close_is_attempted_and_a_starved_one_failed():
    """A closed loop cancels at the close what has no token yet.  Three
    requests ended in the 10 s window on 2 slots, so one full turn of
    the slots is 6.7 s: the request sent 2 s before the close was cut
    short, the one that had waited 9 s was starved."""
    done = [_sent(100.5 + i, 100.5 + i, [101.0 + i, 101.5 + i])
            for i in range(3)]
    cut, starved = _sent(108.0, 108.0, [], "cancelled"), \
        _sent(101.0, 101.0, [], "cancelled")
    cut.cancelled = starved.cancelled = True
    run = {"t_open": 100.0, "t_close": 110.0, "slots": 2,
           "sent": done + [cut, starved]}
    out = serve.end_to_end(run, 10.0)
    assert out["attempted"] == 5 and out["failed"] == 1
    # the starved one waited longest; the one cut short is not timed
    assert out["ttft_p95_ms"] == pytest.approx(10_000.0)
    assert out["ttft_p50_ms"] == pytest.approx(500.0)
    assert out["serve_tokens_per_s"] == pytest.approx(6 / 10.0)


def test_a_traced_runs_latencies_stop_where_the_profiler_opened():
    run = {"t_open": 100.0, "t_close": 110.0, "sent": [
        _sent(101.0, 101.0, [101.2, 101.3]),
        _sent(102.0, 102.0, [102.3, 102.4]),
        # due inside the traced sub-window: sent 5 s late by its stall
        _sent(108.0, 113.0, [113.2, 113.3]),
    ]}
    whole = serve.end_to_end(run, 10.0)
    assert whole["ttft_p95_ms"] == pytest.approx(5200.0)
    assert whole["gen_late_p95_ms"] == pytest.approx(5000.0)
    out = serve.end_to_end(run, 10.0, latencies_until=107.0)
    assert out["attempted"] == 3 and out["failed"] == 0
    assert out["ttft_p95_ms"] == pytest.approx(300.0)
    assert out["gen_late_p95_ms"] == pytest.approx(0.0)


def test_percentile_is_the_sample_value_at_rank():
    xs = list(range(1, 101))
    assert serve.percentile(xs, 95) == 95
    assert serve.percentile(xs, 50) == 50
    assert serve.percentile([5.0], 95) == 5.0
    c = collections.Counter(serve.percentile(np.arange(20), q)
                            for q in (95, 100))
    assert c == {18: 1, 19: 1}
