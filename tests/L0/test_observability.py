"""Unified telemetry: metrics registry, histograms, span tracer.

Three oracles, all pure-Python and deterministic:

- **Histogram math** — bucket assignment and quantiles are checked
  against a linear-scan oracle over the same geometric boundary
  ladder; the quantile estimate must land in the same bucket as the
  exact sample quantile (the estimator's construction guarantee).
- **Snapshot/diff monotonicity** — counters and histogram counts only
  grow between snapshots; a monotonic series that went backwards (a
  ``reset()`` between readings, or reversed arguments) must never
  yield a negative delta — ``snapshot_diff`` clamps to the new value
  and flags the series ``"reset": True``.
- **Chrome trace validity** — exported JSON must be loadable, every
  event carries ``ph``/``ts``/``pid``/``tid``, B/E events pair up
  per thread, and with a fake clock the whole export is byte-stable.

Plus the two contracts the serving hot path depends on: the disabled
tracer allocates nothing per event (one shared no-op span singleton),
and the ``utils`` meters behave identically standalone vs as registry
views (the PR-1..3 ``stats()`` surface must not move).
"""

import io
import json
import math
import os
import random
import sys
import tracemalloc

import pytest

import re

# tools/ops_probe.py owns the Prometheus line-grammar checker shared
# by the in-process conformance test here and the live-endpoint test
# in test_opsplane.py
sys.path.insert(0, os.path.join(os.path.dirname(os.path.dirname(
    os.path.dirname(os.path.abspath(__file__)))), "tools"))

from apex_tpu.observability import (
    NULL_TRACER,
    HistogramMeter,
    MetricsRegistry,
    SpanTracer,
    escape_label_value,
    series_key,
    snapshot_diff,
)
from apex_tpu.utils.meters import CounterMeter, GaugeMeter, RateMeter


class FakeClock:
    """Deterministic seconds source: starts at 0, each call returns
    the current time then advances by ``tick`` (0 = manual only)."""

    def __init__(self, tick=0.0):
        self.now = 0.0
        self.tick = tick

    def __call__(self):
        t = self.now
        self.now += self.tick
        return t

    def advance(self, dt):
        self.now += dt


# -- histogram math vs oracle ---------------------------------------------


def oracle_bucket(bounds, v):
    for i, b in enumerate(bounds):
        if v <= b:
            return i
    return len(bounds) - 1


def test_histogram_bucket_assignment_matches_oracle():
    h = HistogramMeter(low=1e-6, high=60.0, growth=2.0)
    # below low, every exact boundary, midpoints, above high
    probes = [0.0, 1e-9, 1e-6]
    for b in h.bounds:
        probes += [b, b * 0.999, b * 1.001]
    probes += [59.0, 60.0, 61.0, 1e6]
    for v in probes:
        assert h.bucket_index(v) == oracle_bucket(h.bounds, v), v
    # the ladder is geometric low * growth**i, capped above high
    assert h.bounds[0] == 1e-6
    assert h.bounds[-1] >= 60.0
    for a, b in zip(h.bounds, h.bounds[1:]):
        assert b == pytest.approx(a * 2.0)


def test_histogram_quantiles_match_sample_oracle():
    rng = random.Random(0)
    vals = [rng.uniform(1e-5, 5.0) for _ in range(500)]
    vals += [rng.expovariate(10.0) + 1e-6 for _ in range(500)]
    h = HistogramMeter(low=1e-6, high=60.0, growth=2.0)
    for v in vals:
        h.record(v)
    s = sorted(vals)
    for q in (0.01, 0.1, 0.25, 0.5, 0.9, 0.95, 0.99):
        true = s[max(1, math.ceil(q * len(s))) - 1]
        est = h.quantile(q)
        # estimator guarantee: same bucket as the exact sample quantile
        assert h.bucket_index(est) == h.bucket_index(true), q
    # edges clamp to the exact observed extremes
    assert h.quantile(0.0) == min(vals)
    assert h.quantile(1.0) == max(vals)
    assert h.p50 == h.quantile(0.5)
    assert h.p90 == h.quantile(0.9)
    assert h.p99 == h.quantile(0.99)
    assert h.count == len(vals)
    assert h.sum == pytest.approx(sum(vals))
    assert h.mean == pytest.approx(sum(vals) / len(vals))


def test_histogram_single_value_and_empty():
    h = HistogramMeter()
    assert h.quantile(0.5) == 0.0                # empty: defined, zero
    assert h.describe() == {"type": "histogram", "count": 0, "sum": 0.0}
    h.record(0.125)
    for q in (0.0, 0.5, 1.0):
        assert h.quantile(q) == 0.125            # clamped to min==max


def test_histogram_time_uses_injected_clock():
    clk = FakeClock()
    h = HistogramMeter(clock=clk)
    with h.time():
        clk.advance(0.25)
    assert h.count == 1 and h.min == 0.25 and h.max == 0.25


def test_histogram_rejects_bad_ladder():
    with pytest.raises(ValueError):
        HistogramMeter(low=0.0, high=1.0)
    with pytest.raises(ValueError):
        HistogramMeter(low=1.0, high=0.5)
    with pytest.raises(ValueError):
        HistogramMeter(growth=1.0)


# -- registry: snapshot / diff / exposition --------------------------------


def test_registry_snapshot_diff_monotonic():
    reg = MetricsRegistry(clock=FakeClock())
    c = reg.counter("requests", outcome="ok")
    g = reg.gauge("depth")
    h = reg.histogram("lat_s")
    c.incr(3)
    g.update(5)
    h.record(0.1)
    s1 = reg.snapshot()
    c.incr(2)
    g.update(1)
    h.record(0.2)
    s2 = reg.snapshot()
    d = snapshot_diff(s1, s2)
    assert d[series_key("requests", (("outcome", "ok"),))]["delta"] == 2
    assert "reset" not in d[series_key("requests",
                                       (("outcome", "ok"),))]
    assert d["depth"]["value"] == 1.0            # gauges: newer value
    assert d["lat_s"]["count_delta"] == 1
    assert d["lat_s"]["sum_delta"] == pytest.approx(0.2)
    # reversed argument order looks like a global reset: every
    # monotonic series clamps to its "new" value and is flagged,
    # never a negative delta
    dr = snapshot_diff(s2, s1)
    key = series_key("requests", (("outcome", "ok"),))
    assert dr[key] == {"type": "counter", "delta": 3, "reset": True}
    assert dr["lat_s"]["reset"] is True
    assert dr["lat_s"]["count_delta"] == 1       # clamped, not -1
    # a series absent from old diffs against zero
    d0 = snapshot_diff({}, s2)
    assert d0[series_key("requests", (("outcome", "ok"),))]["delta"] == 5


def test_snapshot_diff_clamps_and_flags_resets():
    """The reset_meters()-between-snapshots case (the satellite fix):
    a counter/gauge/histogram reset between two in-order snapshots
    must produce a clamped, flagged delta — the increment since the
    reset — instead of a negative delta or an exception."""
    reg = MetricsRegistry()
    g = reg.gauge("depth")
    h = reg.histogram("lat_s")
    for v in (0.1, 0.2, 0.3):
        h.record(v)
    g.update(7)
    g.update(5)                         # count=2: a reset is visible
    s1 = reg.snapshot()
    h.reset()
    g.reset()
    h.record(0.4)                       # one post-reset sample
    g.update(2)
    s2 = reg.snapshot()
    d = snapshot_diff(s1, s2)
    assert d["lat_s"] == {"type": "histogram", "count_delta": 1,
                          "sum_delta": pytest.approx(0.4),
                          "reset": True}
    assert d["depth"]["value"] == 2.0
    assert d["depth"]["reset"] is True  # sample count went backwards
    # no reset -> no flag
    s3 = reg.snapshot()
    assert "reset" not in snapshot_diff(s2, s3)["lat_s"]
    assert "reset" not in snapshot_diff(s2, s3)["depth"]


def test_registry_get_or_create_and_kind_conflict():
    reg = MetricsRegistry()
    assert reg.counter("x") is reg.counter("x")
    assert reg.counter("x", a="1") is not reg.counter("x", a="2")
    # labels are identity regardless of kwarg order
    assert reg.counter("x", a="1", b="2") is reg.counter("x", b="2", a="1")
    with pytest.raises(ValueError):
        reg.gauge("x")                           # name is already a counter
    with pytest.raises(ValueError):
        reg.counter("x").incr(-1)                # counters are monotonic


def test_prometheus_text_exposition():
    reg = MetricsRegistry()
    reg.counter("reqs", code="200").incr(7)
    reg.gauge("depth").update(3)
    h = reg.histogram("lat_s", low=0.001, high=1.0, growth=10.0)
    for v in (0.0005, 0.005, 0.05, 0.5, 5.0):
        h.record(v)
    text = reg.prometheus_text()
    lines = text.strip().split("\n")
    assert "# TYPE reqs counter" in lines
    assert 'reqs{code="200"} 7' in lines
    assert "# TYPE depth gauge" in lines
    assert "depth 3.0" in lines
    assert "# TYPE lat_s histogram" in lines
    # cumulative buckets end at +Inf == count, and _sum/_count close out
    buckets = [ln for ln in lines if ln.startswith("lat_s_bucket")]
    counts = [int(ln.rsplit(" ", 1)[1]) for ln in buckets]
    assert counts == sorted(counts), "bucket counts must be cumulative"
    assert buckets[-1] == 'lat_s_bucket{le="+Inf"} 5'
    assert "lat_s_count 5" in lines
    assert any(ln.startswith("lat_s_sum ") for ln in lines)


def test_prometheus_label_escaping():
    """Label values carrying backslashes, quotes, or newlines must be
    escaped per the text-format spec — unescaped they corrupt every
    line after them in a scrape."""
    assert escape_label_value('a"b') == r'a\"b'
    assert escape_label_value("a\\b") == r"a\\b"
    assert escape_label_value("a\nb") == r"a\nb"
    reg = MetricsRegistry()
    reg.counter("errors", path='C:\\tmp\\"x"\nboom').incr(2)
    text = reg.prometheus_text()
    line = [ln for ln in text.splitlines()
            if ln.startswith("errors{")][0]
    assert "\n" not in line             # splitlines proves no raw \n
    assert line == (
        'errors{path="C:\\\\tmp\\\\\\"x\\"\\nboom"} 2')


def test_prometheus_format_conformance_line_by_line():
    """The exposition-hardening oracle: parse the output line by line
    — exactly one # HELP and one # TYPE per family (HELP first),
    every sample line matches the metric-line grammar, histogram
    bucket counts are cumulative-monotonic ending at +Inf == count,
    and set_help text is carried through."""
    reg = MetricsRegistry()
    reg.set_help("reqs", "requests by code")
    reg.counter("reqs", code="200").incr(7)
    reg.counter("reqs", code="500").incr(1)
    reg.gauge("depth").update(3)
    h = reg.histogram("lat_s", low=0.001, high=1.0, growth=10.0)
    for v in (0.0005, 0.005, 0.05, 0.5, 5.0):
        h.record(v)
    lines = reg.prometheus_text().splitlines()
    assert lines, "empty exposition"
    sample_re = re.compile(
        r'^[a-zA-Z_:][a-zA-Z0-9_:]*'
        r'(\{[a-zA-Z_][a-zA-Z0-9_]*="(?:[^"\\]|\\.)*"'
        r'(,[a-zA-Z_][a-zA-Z0-9_]*="(?:[^"\\]|\\.)*")*\})?'
        r' -?[0-9.e+-]+(inf|nan)?$')
    help_seen, type_seen = {}, {}
    current_family = None
    for ln in lines:
        if ln.startswith("# HELP "):
            fam = ln.split()[2]
            assert fam not in help_seen, f"duplicate HELP for {fam}"
            help_seen[fam] = ln
            current_family = fam
        elif ln.startswith("# TYPE "):
            fam = ln.split()[2]
            assert fam not in type_seen, f"duplicate TYPE for {fam}"
            assert fam == current_family, "TYPE must follow its HELP"
            type_seen[fam] = ln.split()[3]
        else:
            assert sample_re.match(ln), f"unparseable line: {ln!r}"
            name = ln.split("{")[0].split(" ")[0]
            # sample lines belong to the current (declared) family
            assert name.startswith(current_family), \
                f"{ln!r} outside its {current_family!r} family block"
    assert set(help_seen) == set(type_seen) == \
        {"reqs", "depth", "lat_s"}
    assert help_seen["reqs"] == "# HELP reqs requests by code"
    assert type_seen == {"reqs": "counter", "depth": "gauge",
                         "lat_s": "histogram"}
    # histogram buckets: cumulative-monotonic, closing at +Inf == count
    buckets = [ln for ln in lines if ln.startswith("lat_s_bucket")]
    counts = [int(ln.rsplit(" ", 1)[1]) for ln in buckets]
    assert counts == sorted(counts), "bucket counts must be cumulative"
    assert buckets[-1].startswith('lat_s_bucket{le="+Inf"}')
    assert counts[-1] == 5
    # the probe's shared checker judges the same text the same way —
    # tests/L0/test_opsplane.py applies it to the LIVE /metrics
    # endpoint, so the two must agree on what conformant means
    import ops_probe
    assert ops_probe.check_prometheus_text(
        reg.prometheus_text()) == []
    broken = reg.prometheus_text() + "not a metric line !!\n"
    assert ops_probe.check_prometheus_text(broken)


def test_histogram_label_set_isolation():
    """Same metric name, different label items: buckets, counts, and
    quantiles stay independent through snapshot, snapshot_diff, and
    the Prometheus exposition — one route's latency burst must not
    bleed into another's distribution."""
    reg = MetricsRegistry()
    a = reg.histogram("lat_s", route="a")
    b = reg.histogram("lat_s", route="b")
    assert a is not b
    assert reg.histogram("lat_s", route="a") is a   # stable identity
    for _ in range(10):
        a.record(0.001)                 # fast route
    b.record(10.0)                      # one slow sample
    assert a.count == 10 and b.count == 1
    assert a.p99 < 0.01 and b.p50 == 10.0
    assert a.bucket_counts != b.bucket_counts
    s1 = reg.snapshot()
    ka = series_key("lat_s", (("route", "a"),))
    kb = series_key("lat_s", (("route", "b"),))
    assert s1[ka]["count"] == 10 and s1[kb]["count"] == 1
    a.record(0.002)
    d = snapshot_diff(s1, reg.snapshot())
    assert d[ka]["count_delta"] == 1 and d[kb]["count_delta"] == 0
    text = reg.prometheus_text()
    inf_a = [ln for ln in text.splitlines()
             if ln.startswith("lat_s_bucket")
             and 'route="a"' in ln and 'le="+Inf"' in ln]
    inf_b = [ln for ln in text.splitlines()
             if ln.startswith("lat_s_bucket")
             and 'route="b"' in ln and 'le="+Inf"' in ln]
    assert inf_a[0].endswith(" 11") and inf_b[0].endswith(" 1")
    assert "lat_s_count" in text
    counts = [ln for ln in text.splitlines()
              if ln.startswith("lat_s_count")]
    assert len(counts) == 2             # one _count per label set


def test_emit_jsonl_deterministic_with_fake_clock():
    clk = FakeClock(tick=1.0)
    reg = MetricsRegistry(clock=clk)
    reg.counter("c").incr()
    buf = io.StringIO()
    reg.emit_jsonl(buf, extra={"step": 7})
    reg.emit_jsonl(buf)
    recs = [json.loads(ln) for ln in buf.getvalue().splitlines()]
    assert [r["ts"] for r in recs] == [0.0, 1.0]
    assert recs[0]["step"] == 7
    assert recs[0]["metrics"]["c"] == {"type": "counter", "value": 1}


# -- meters as registry views ---------------------------------------------


def test_counter_meter_view_matches_standalone():
    reg = MetricsRegistry()
    view = CounterMeter(registry=reg, name="failures", label="reason")
    solo = CounterMeter()
    for cm in (view, solo):
        cm.incr("timeout", 2)
        cm.incr("capacity")
        with pytest.raises(ValueError):
            cm.incr("timeout", -1)
    # the historical API, key for key
    assert view.count("timeout") == solo.count("timeout") == 2
    assert view["capacity"] == solo["capacity"] == 1
    assert view.count("never") == solo.count("never") == 0
    assert view.total == solo.total == 3
    assert view.as_dict() == solo.as_dict() == {
        "capacity": 1, "timeout": 2}
    assert view.ratio("timeout", "timeout", "capacity") == \
        solo.ratio("timeout", "timeout", "capacity") == pytest.approx(2 / 3)
    # the registry sees the view's cells as labeled series
    snap = reg.snapshot()
    assert snap['failures{reason="timeout"}']["value"] == 2
    assert snap['failures{reason="capacity"}']["value"] == 1


def test_gauge_meter_view_matches_standalone():
    reg = MetricsRegistry()
    view = GaugeMeter(registry=reg, name="queue_depth")
    solo = GaugeMeter()
    for gm in (view, solo):
        gm.update(4)
        gm.update(2)
    for gm in (view, solo):
        assert (gm.val, gm.peak, gm.avg, gm.count) == (2.0, 4.0, 3.0, 2)
    assert reg.snapshot()["queue_depth"]["peak"] == 4.0
    view.reset()
    assert (view.val, view.peak, view.count) == (0.0, 0.0, 0)
    with pytest.raises(ValueError):
        GaugeMeter(registry=reg)                 # registry needs name=


def test_rate_meter_windowed_rate():
    clk = FakeClock()
    rm = RateMeter(clock=clk, max_window=60.0)
    clk.advance(1.0)
    rm.update(5)
    clk.advance(10.0)
    rm.update(10)
    clk.advance(1.0)                             # now t=12
    # trailing 2s holds only the n=10 burst
    assert rm.rate_over(2.0) == pytest.approx(10 / 2.0)
    # a window longer than the meter's life converges to the lifetime
    # rate (denominator = actual elapsed, not the window)
    assert rm.rate_over(59.0) == pytest.approx(15 / 12.0)
    assert rm.rate == pytest.approx(15 / 12.0)
    with pytest.raises(ValueError):
        rm.rate_over(0.0)
    with pytest.raises(ValueError):
        RateMeter(max_window=0.0)


def test_rate_meter_prunes_but_keeps_lifetime_total():
    clk = FakeClock()
    rm = RateMeter(clock=clk, max_window=5.0)
    rm.update(100)                               # t=0, will age out
    clk.advance(10.0)
    rm.update(1)                                 # t=10
    assert rm.total == 101                       # lifetime survives pruning
    assert len(rm._events) == 1                  # memory ∝ window
    assert rm.rate_over(5.0) == pytest.approx(1 / 5.0)


def test_rate_meter_degenerate_windows_answer_zero():
    """Edge contract: an empty window and a single sample at zero
    elapsed time both answer 0.0 — never a ZeroDivisionError, never a
    ~1e9 'rate' from a 1e-9 denominator (the first scrape on an
    injected clock hits exactly this)."""
    clk = FakeClock()
    rm = RateMeter(clock=clk, max_window=60.0)
    # empty deque: no events at all
    assert rm.rate_over(10.0) == 0.0
    # single sample in the same clock instant as the read
    rm.update(5)
    assert rm.rate_over(10.0) == 0.0
    # once time actually passes, the sample counts normally
    clk.advance(2.0)
    assert rm.rate_over(10.0) == pytest.approx(5 / 2.0)
    # a window whose events all aged out is empty again
    rm2 = RateMeter(clock=clk, max_window=5.0)
    rm2.update(7)
    clk.advance(100.0)
    assert rm2.rate_over(5.0) == 0.0
    # reset() restores the empty-window answer
    rm.reset()
    assert rm.rate_over(10.0) == 0.0


# -- tracer: chrome export, determinism, disabled path ---------------------


def _matched_pairs(events):
    """Per-(pid, tid) B/E matching; returns [(b_event, e_event)] and
    asserts no E-without-B and nothing left open."""
    stacks, pairs = {}, []
    for ev in events:
        key = (ev["pid"], ev["tid"])
        if ev["ph"] == "B":
            stacks.setdefault(key, []).append(ev)
        elif ev["ph"] == "E":
            assert stacks.get(key), f"E without B on {key}"
            pairs.append((stacks[key].pop(), ev))
    assert not any(st for st in stacks.values()), "unclosed spans"
    return pairs


def test_chrome_trace_export_validates(tmp_path):
    clk = FakeClock(tick=1.0)                    # 1s per clock read
    tr = SpanTracer(clock=clk, pid=42)
    with tr.span("step", n=1):
        with tr.span("decode", batch=3):
            tr.instant("compile", program="decode")
    path = tr.export_chrome(str(tmp_path / "trace.json"))
    data = json.load(open(path))
    events = data["traceEvents"]
    assert len(events) == 5                      # 2 B + 2 E + 1 instant
    for ev in events:
        assert ev["ph"] in ("B", "E", "i")
        assert isinstance(ev["ts"], (int, float)) and ev["ts"] >= 0
        assert ev["pid"] == 42 and "tid" in ev
    pairs = _matched_pairs(events)
    assert sorted(b["name"] for b, _ in pairs) == ["decode", "step"]
    for b, e in pairs:
        assert e["ts"] >= b["ts"]
    # nesting is recorded as span/parent ids in args
    by_name = {ev.get("name"): ev for ev in events if ev["ph"] != "E"}
    outer = by_name["step"]["args"]["span_id"]
    assert by_name["decode"]["args"]["parent_id"] == outer
    assert by_name["compile"]["args"]["parent_id"] == \
        by_name["decode"]["args"]["span_id"]
    assert by_name["compile"]["s"] == "t"
    assert by_name["decode"]["args"]["batch"] == 3
    # fake clock: ts are exact microsecond multiples of the 1s ticks
    assert [ev["ts"] for ev in events] == [
        1e6 * i for i in range(1, 6)]


def test_trace_is_deterministic_under_fake_clock(tmp_path):
    def run():
        tr = SpanTracer(clock=FakeClock(tick=0.5), pid=1)
        with tr.span("a"):
            tr.instant("m", k="v")
        with tr.span("b"):
            pass
        return tr.chrome_events()

    one, two = run(), run()
    # tid differs only if threads do; same thread -> byte-identical
    assert json.dumps(one, sort_keys=True) == json.dumps(two,
                                                         sort_keys=True)


def test_ring_buffer_bounds_memory_and_counts_drops():
    tr = SpanTracer(capacity=8, clock=FakeClock(tick=0.001))
    for i in range(20):
        tr.instant("e", i=i)
    assert len(tr.events) == 8
    assert tr.dropped == 12
    tr.clear()
    assert tr.events == () and tr.dropped == 0
    with pytest.raises(ValueError):
        SpanTracer(capacity=1)


def test_disabled_tracer_allocates_nothing_per_event():
    # the no-op span is one process-wide singleton, not per call
    s1 = NULL_TRACER.span("decode", batch=4)
    s2 = NULL_TRACER.span("admit")
    assert s1 is s2
    with s1:
        pass
    assert NULL_TRACER.enabled is False
    assert NULL_TRACER.events == () and NULL_TRACER.chrome_events() == []
    # and the hot loop holds no per-event memory: peak growth over 10k
    # disabled events stays under one small transient object
    NULL_TRACER.instant("warm")                  # warm any lazy state
    tracemalloc.start()
    base = tracemalloc.get_traced_memory()[0]
    for _ in range(10_000):
        with NULL_TRACER.span("decode"):
            NULL_TRACER.instant("tok")
    cur, peak = tracemalloc.get_traced_memory()
    tracemalloc.stop()
    assert cur - base < 2048, "disabled tracer retained memory"
    assert peak - base < 8192, "disabled tracer allocated per event"


def test_sentry_and_scaler_telemetry(tmp_path):
    """The training step loop end-to-end: each sentry step runs under
    a train_step span and feeds the train_step_s histogram; overflow
    steps emit overflow_skip instants; with registry= the loss-scale
    trajectory lands in the amp_loss_scale gauge, and
    LossScaler.observe records the same state for sentry-less loops."""
    import jax.numpy as jnp

    from apex_tpu.amp.scaler import LossScaler
    from apex_tpu.resilience import TrainingSentry
    from apex_tpu.utils.checkpoint import CheckpointManager

    scaler = LossScaler("dynamic", init_scale=8.0, min_loss_scale=1.0)

    def step_fn(state, x):
        overflow = ~jnp.all(jnp.isfinite(x))
        p = jnp.where(overflow, state["p"], state["p"] + x)
        return {"p": p,
                "scaler": scaler.update(state["scaler"], overflow)}

    tr = SpanTracer(clock=FakeClock(tick=0.001))
    reg = MetricsRegistry()
    mgr = CheckpointManager(str(tmp_path / "c"), registry=reg, tracer=tr)
    sentry = TrainingSentry(step_fn, mgr, checkpoint_every=2,
                            nonfinite_threshold=3, registry=reg,
                            tracer=tr)
    state = {"p": jnp.zeros(()), "scaler": scaler.init()}
    for i in range(3):
        state = sentry.step(i, state, jnp.asarray(1.0))
    state = sentry.step(3, state, jnp.asarray(jnp.inf))   # overflow
    snap = reg.snapshot()
    assert snap["train_step_s"]["count"] == 4
    assert snap["amp_loss_scale"]["value"] == 4.0   # 8.0 halved by skip
    names = [ev[1] for ev in tr.events]
    assert names.count("train_step") >= 4
    assert "overflow_skip" in names
    assert "checkpoint_save" in names               # nested inside step
    # the sentry-less hook records the same trajectory
    reg2 = MetricsRegistry()
    scaler.observe(state["scaler"], reg2)
    s2 = reg2.snapshot()
    assert s2["amp_loss_scale"]["value"] == 4.0
    assert "amp_unskipped_steps" in s2


def test_checkpoint_spans_recorded(tmp_path):
    """The training-side instrumentation end-to-end: a save/restore
    cycle emits checkpoint_save / checkpoint_restore spans and the
    checkpoint_publish instant, and feeds the registry histograms."""
    import numpy as np

    from apex_tpu.utils.checkpoint import CheckpointManager

    tr = SpanTracer(clock=FakeClock(tick=0.001))
    reg = MetricsRegistry()
    mgr = CheckpointManager(str(tmp_path / "ckpt"), registry=reg,
                            tracer=tr)
    state = {"w": np.arange(8, dtype=np.float32)}
    mgr.save(0, state)
    out = mgr.restore(0, target=state)
    assert np.array_equal(out["w"], state["w"])
    names = [ev[1] for ev in tr.events]
    assert "checkpoint_save" in names
    assert "checkpoint_publish" in names
    assert "checkpoint_restore" in names
    snap = reg.snapshot()
    assert snap["checkpoint_save_s"]["count"] == 1
    assert snap["checkpoint_restore_s"]["count"] == 1
    assert snap['checkpoint{event="checkpoints_written"}']["value"] == 1


# -- the process default follows the profiler ------------------------------


@pytest.fixture
def default_tracer():
    """A fresh process default (no ``APEX_TPU_TRACE``), put back after."""
    from apex_tpu.observability import tracing
    prev = tracing.set_tracer(None)
    saved = os.environ.pop(tracing.TRACE_ENV, None)
    try:
        yield tracing.get_tracer()
    finally:
        tracing.set_tracer(prev)
        if saved is not None:
            os.environ[tracing.TRACE_ENV] = saved


class _Profiler:
    """A ``jax.profiler`` session on the CPU backend, Python tracer off
    as the benchmark has it; ``data()`` reads the ``.xplane.pb``."""

    def __init__(self, tmp_path):
        self.dir = str(tmp_path / "xprof")

    def __enter__(self):
        import jax
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        jax.profiler.start_trace(self.dir, profiler_options=opts)
        return self

    def __exit__(self, *exc):
        import jax
        jax.profiler.stop_trace()

    def host_events(self):
        import glob

        import jax
        pb, = glob.glob(os.path.join(self.dir, "plugins", "profile", "*",
                                     "*.xplane.pb"))
        data = jax.profiler.ProfileData.from_file(pb)
        return [(e.name, dict(e.stats)) for p in data.planes
                if p.name == "/host:CPU" for l in p.lines for e in l.events]


def _tiny_server(**kw):
    import jax
    import jax.numpy as jnp

    from apex_tpu import models
    from apex_tpu.serving import InferenceServer
    cfg = models.GPTConfig(
        vocab_size=61, hidden_size=32, num_hidden_layers=2,
        num_attention_heads=2, intermediate_size=64,
        max_position_embeddings=128, hidden_dropout_prob=0.0,
        attention_probs_dropout_prob=0.0)
    params = models.GPTLMHeadModel(cfg).init(
        jax.random.PRNGKey(1), jnp.ones((1, 8), jnp.int32))["params"]
    return InferenceServer(cfg, params, max_batch_size=4, max_context=128,
                           cache_dtype=jnp.float32, **kw)


def _wave(server, new=10):
    """Three distinct prompts and a periodic one (its n-gram drafts
    bring the verify program in); returns the generated tokens."""
    rng = random.Random(5)
    prompts = [[rng.randrange(61) for _ in range(20)] for _ in range(3)]
    reqs = [server.submit(p, new) for p in prompts + [[1, 2, 3, 4] * 6]]
    while server.has_work:
        server.step()
    return [list(r.generated) for r in reqs]


def test_default_tracer_is_off_and_allocates_nothing_without_a_session(
        default_tracer):
    tr = default_tracer
    assert tr is not NULL_TRACER and isinstance(tr, SpanTracer)
    assert tr.enabled is False
    # off, a span is the shared no-op; begin/end stay paired
    assert tr.span("step", iter=1) is NULL_TRACER.span("x")
    assert tr.begin("step") == 0
    tr.end()
    tr.instant("warm")
    tracemalloc.start()
    base = tracemalloc.get_traced_memory()[0]
    for _ in range(10_000):
        with tr.span("launch", program="decode") as s:
            s.set(uid=3)
            tr.instant("tok")
    cur, peak = tracemalloc.get_traced_memory()
    tracemalloc.stop()
    assert cur - base < 2048, "the default tracer retained memory"
    assert peak - base < 8192, "the default tracer allocated per event"
    assert tr.events == () and tr.spans() == [] and tr.dropped == 0


def test_default_server_step_records_nothing_without_a_session(
        default_tracer):
    server = _tiny_server()
    assert server.tracer is default_tracer
    assert all(_wave(server))
    assert default_tracer.events == ()
    assert server.stats()["trace_dropped_events"] == 0
    server.close()


def test_profiler_session_arms_the_ring_and_children_tile_each_step(
        default_tracer, tmp_path):
    from apex_tpu.observability.tracing import PROFILER_PREFIX
    tr = default_tracer
    server = _tiny_server()
    _wave(server)                       # compile outside the session
    assert tr.events == ()
    with _Profiler(tmp_path) as prof:
        assert tr.enabled is True
        _wave(server)
    assert tr.enabled is False
    n_events = len(tr.events)
    _wave(server)                       # the session is over: nothing more
    assert len(tr.events) == n_events
    server.close()

    spans = tr.spans()
    by_id = {s.span_id: s for s in spans}
    steps = [s for s in spans if s.name == "step"]
    assert len(steps) >= 4
    assert [s.args["iter"] for s in steps] == sorted(
        s.args["iter"] for s in steps)
    seen, worst = set(), []
    for st in steps:
        kids = sorted((s for s in spans if s.parent_id == st.span_id),
                      key=lambda s: s.start)
        seen |= {k.name for k in kids}
        assert {k.name for k in kids} <= {
            "retire", "apply", "plan", "chunk_prefill", "prefill", "draft",
            "inputs", "launch", "account"}
        edges = [st.start] + [t for k in kids for t in (k.start, k.end)] \
            + [st.end]
        gaps = [b - a for a, b in zip(edges[::2], edges[1::2])]
        assert min(gaps) >= 0, "children overlap"
        worst.append(max(gaps) / (st.end - st.start))
    # no uncovered stretch over 5% of a step (the median step: a busy
    # machine may take the thread away between two spans of one)
    assert sorted(worst)[len(worst) // 2] <= 0.05, worst
    assert {"retire", "apply", "plan", "chunk_prefill", "draft", "inputs",
            "launch", "account"} <= seen
    # per-request spans share ``uid`` with the request_* instants
    uids = {ev[6]["uid"] for ev in tr.events if ev[1] == "request_enqueue"}
    assert {s.args["uid"] for s in spans if s.name == "submit"} == uids
    assert {s.args["uid"] for s in spans
            if s.name == "chunk_prefill"} == uids
    for s in spans:
        if s.name in ("admit", "cow_copy"):
            assert by_id[s.parent_id].name == "plan"
        if s.name == "prefill_read":
            assert by_id[s.parent_id].name == "chunk_prefill"
        if s.name in ("retire", "apply"):
            assert by_id[s.parent_id].name in ("step", "submit")

    # the same names, under the prefix, on the profiler's host plane
    host = prof.host_events()
    names = [n for n, _ in host if n.startswith(PROFILER_PREFIX)]
    for name in {s.name for s in spans}:
        assert names.count(PROFILER_PREFIX + name) == sum(
            1 for s in spans if s.name == name), name
    launch = next(st for n, st in host if n == PROFILER_PREFIX + "launch")
    assert launch["program"] in ("decode", "verify") and launch["batch"] > 0
    submit = next(st for n, st in host if n == PROFILER_PREFIX + "submit")
    assert submit["uid"] in uids


def test_session_that_starts_or_stops_inside_a_span_leaves_the_stack_balanced(
        default_tracer, tmp_path):
    import jax
    tr = default_tracer
    with tr.span("outer"):              # decided off at entry
        with _Profiler(tmp_path / "a"):
            with tr.span("inner", k=1):
                tr.instant("mark")
    assert tr._stack() == []
    assert [(s.name, s.parent_id) for s in tr.spans()] == [("inner", 0)]
    tr.clear()

    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    jax.profiler.start_trace(str(tmp_path / "b"), profiler_options=opts)
    try:
        with tr.span("outer"):          # decided on at entry
            with tr.span("kept"):
                pass
            jax.profiler.stop_trace()
            with tr.span("dropped"):
                tr.instant("dropped_too")
    finally:
        if tr.enabled:
            jax.profiler.stop_trace()
    assert tr._stack() == []
    assert [s.name for s in tr.spans()] == ["outer", "kept"]
    assert [ev[0] for ev in tr.events] == ["B", "B", "E", "E"]
    # begin/end pair up the same way
    assert tr.begin("off") == 0
    jax.profiler.start_trace(str(tmp_path / "c"), profiler_options=opts)
    try:
        sid = tr.begin("on")
        assert sid > 0
    finally:
        jax.profiler.stop_trace()
    tr.end()
    tr.end()
    assert tr._stack() == []
    assert [s.name for s in tr.spans()] == ["outer", "kept", "on"]


def test_greedy_output_is_the_same_with_the_tracer_on_and_off(tmp_path):
    off = _tiny_server(tracer=NULL_TRACER)
    want = _wave(off, new=24)
    off.close()
    on_tr = SpanTracer()
    on = _tiny_server(tracer=on_tr)
    with _Profiler(tmp_path):
        got = _wave(on, new=24)
    on.close()
    assert got == want
    assert any(s.name == "step" for s in on_tr.spans())
    # an explicit tracer records with or without a session
    assert on_tr.enabled is True


def test_spans_are_handed_out_on_the_clocks_own_seconds():
    clk = FakeClock(tick=1.0)
    clk.now = 100.0
    tr = SpanTracer(clock=clk)          # t0 = 100
    with tr.span("step", iter=7) as s:              # B at 101
        with tr.span("launch", program="decode"):   # B 102, E 103
            pass
        s.set(uid=9)
    open_sid = tr.begin("still_open")   # never closed: not handed out
    got = tr.spans()
    assert [(x.name, x.start, x.end) for x in got] == [
        ("step", 101.0, 104.0), ("launch", 102.0, 103.0)]
    assert got[0].args == {"iter": 7, "uid": 9}
    assert got[1].parent_id == got[0].span_id and got[0].parent_id == 0
    assert open_sid not in {x.span_id for x in got}
    assert NULL_TRACER.spans() == []
