"""The generators: the same seed gives the same traffic, every seed the
same work in another order, latencies count from the due moment."""

import collections

import numpy as np
import pytest

from benchmarks.harness import serve, spec, traffic

CHAT = spec.load_cell("gpt2xl-chat-open").traffic
DOCS = spec.load_cell("gpt2xl-doc-backlog").traffic
PACKED = spec.load_cell("gpt2m-train-1chip").traffic
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


@pytest.mark.parametrize("mix,seconds,n", [(CHAT, 45, 80), (DOCS, 45, 460)],
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


def test_packed_batches_are_full_rows_that_all_differ():
    feed = traffic.packed_batches(PACKED, BIG, 8, 1024, 50257)
    again = traffic.packed_batches(PACKED, BIG, 8, 1024, 50257)
    other = traffic.packed_batches(PACKED, 5, 8, 1024, 50257)
    seen = []
    for _ in range(3):
        rows = next(feed)
        assert rows.shape == (8, 1024) and rows.dtype == np.int32
        assert rows.min() >= 0 and rows.max() < 50257
        assert np.array_equal(rows, next(again))
        assert not np.array_equal(rows, next(other))
        seen.extend(map(bytes, rows))
        # documents of about 400 tokens joined by the separator
        n_sep = int((rows == 50256).sum())
        assert 5 <= n_sep <= 60
    assert len(set(seen)) == 24


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
