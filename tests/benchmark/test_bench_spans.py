"""``harness/spans.py``: the program's spans on the trace's clock.  The
arithmetic on hand-made intervals, then the nine metrics over a trace
recorded on the chip with a ring made to match its ``bench_step``
annotations, through ``last_line`` and ``check_line``."""

import dataclasses
import json
import os
import sys
import types

import numpy as np
import pytest

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))

import test_bench_trace  # noqa: E402
from apex_tpu import observability  # noqa: E402
from benchmarks.harness import line, readers, spans, spec  # noqa: E402
from benchmarks.harness.spans import Span  # noqa: E402
from test_bench_trace import hand_made  # noqa: E402
from test_bench_trace import line as events  # noqa: E402

NEW = {"gpt2xl-chat-open": [
    "step_idle_ms.chat", "apply_ms.chat", "plan_ms.chat", "draft_ms.chat",
    "launch_host_ms.chat", "idle_unattributed_pct.chat"],
    "gpt2xl-doc-backlog": [
    "step_idle_ms.backlog", "chunk_dispatch_ms.backlog",
    "idle_unattributed_pct.backlog"]}


def tree(*rows):
    """``(name, start, end, parent index or None)`` rows as spans."""
    return [Span(n, a, b, i + 1, 0 if p is None else p + 1, {})
            for i, (n, a, b, p) in enumerate(rows)]


# -- the arithmetic -------------------------------------------------------------

def test_shift_moves_both_edges():
    got = spans.shifted(tree(("step", 1.0, 2.5, None)), 100.0)
    assert (got[0].start, got[0].end) == (101.0, 102.5)
    assert got[0].name == "step" and got[0].span_id == 1


def test_idle_is_what_the_union_of_operations_leaves_of_the_window():
    s, e = spans.idle_intervals([1.0, 1.5, 6.0, 9.0], [3.0, 2.0, 7.0, 12.0],
                                0.0, 10.0)
    assert list(zip(s, e)) == [(0.0, 1.0), (3.0, 6.0), (7.0, 9.0)]
    s, e = spans.idle_intervals([], [], 2.0, 5.0)
    assert list(zip(s, e)) == [(2.0, 5.0)]
    inside = spans.idle_within(np.array([0.0, 3.0, 7.0]),
                               np.array([1.0, 6.0, 9.0]),
                               [0.5, 4.0, 9.5], [3.5, 8.0, 20.0])
    assert inside == pytest.approx([0.5 + 0.5, 2.0 + 1.0, 0.0])


def test_idle_goes_to_the_innermost_span_that_covers_it():
    sp = tree(("step", 0.0, 10.0, None), ("retire", 0.0, 2.0, 0),
              ("plan", 2.0, 5.0, 0), ("admit", 3.0, 4.0, 2),
              ("launch", 6.0, 7.0, 0), ("submit", 11.0, 13.0, None),
              ("apply", 11.5, 12.0, 5))
    # idle: [1, 3.5] crosses retire, plan and admit; [5, 6.5] lies under
    # step itself and launch; [10.5, 12] outside any span, then submit,
    # then its apply
    by = spans.attribute(np.array([1.0, 5.0, 10.5]),
                         np.array([3.5, 6.5, 12.0]), sp)
    assert by == pytest.approx({
        "retire": 1.0, "plan": 1.0, "admit": 0.5, "step": 1.0,
        "launch": 0.5, "submit": 0.5, "apply": 0.5,
        spans.NO_SPAN: 0.5})
    assert sum(by.values()) == pytest.approx(2.5 + 1.5 + 1.5)


def test_self_time_is_the_duration_less_what_children_cover():
    sp = tree(("chunk_prefill", 0.0, 10.0, None),
              ("prefill_read", 6.0, 9.0, 0), ("x", 8.0, 9.5, 0))
    kids = spans.children_of(sp)
    assert spans.self_time(sp[0], kids) == pytest.approx(10.0 - 3.5)
    assert spans.self_time(sp[1], kids) == pytest.approx(3.0)


def test_alignment_is_the_widest_distance_and_fires_on_a_count():
    steps = tree(("step", 1.0002, 1.9999, None), ("step", 3.0, 4.0005, None))
    err = spans.alignment_error(steps, np.array([3.0, 1.0]),
                                np.array([4.0, 2.0]))
    assert err == pytest.approx(0.0005)
    with pytest.raises(ValueError, match="1 'step' spans.*2 'bench_step'"):
        spans.alignment_error(steps[:1], np.array([3.0, 1.0]),
                              np.array([4.0, 2.0]))
    with pytest.raises(ValueError, match="no 'step' span"):
        spans.alignment_error([], np.zeros(0), np.zeros(0))


# -- a run: hand-made ---------------------------------------------------------

class Ring:
    """Stands in for the process tracer."""

    def __init__(self, spans_):
        self._spans = spans_

    def spans(self):
        return self._spans


def ctx_for(trace, opened_at, cell_name="gpt2xl-chat-open"):
    return {"trace": trace, "cell": spec.load_cell(cell_name),
            "run": {"window": types.SimpleNamespace(opened_at=opened_at)}}


@pytest.fixture
def ring(monkeypatch):
    def install(spans_):
        monkeypatch.setattr(observability, "get_tracer",
                            lambda: Ring(spans_))
    return install


def hand_made_run(skew=0.0):
    """Two steps on the host's clock (50 s behind the trace's): the
    device is idle for the first step's apply and plan, and between
    the steps."""
    ops = events(("%a = x", 0.0, 1.0), ("%b = x", 2.0, 5.5),
                 ("%c = x", 6.5, 10.0))
    t = hand_made(ops, events(("jit__decode(1)", 2.0, 5.5)))
    t.host = events(("bench_window", 0.0, 10.0), ("bench_step", 0.5, 5.0),
                    ("bench_submit", 5.2, 5.4), ("bench_step", 6.0, 9.0))
    host = tree(("step", 0.5, 5.0, None), ("retire", 0.5, 1.2, 0),
                ("apply", 1.2, 1.6, 0), ("plan", 1.6, 1.9, 0),
                ("draft", 1.9, 1.95, 0), ("inputs", 1.95, 1.98, 0),
                ("launch", 1.98, 2.1, 0), ("account", 2.1, 5.0, 0),
                ("step", 6.0, 9.0, None), ("retire", 6.0, 6.6, 8),
                ("apply", 6.6, 8.6, 8), ("plan", 8.6, 9.0, 8))
    return t, spans.shifted(host, -50.0 + skew), -50.0


def test_metrics_on_a_hand_made_run(ring, capsys):
    t, host_spans, opened_at = hand_made_run()
    ring(host_spans)
    ctx = ctx_for(t, opened_at)
    a = spans.analysis(ctx)
    assert a.align_s == pytest.approx(0.0, abs=1e-9)
    assert a.idle_s == pytest.approx(t.window_s - t.busy_s) == 2.0
    assert a.by_phase == pytest.approx({
        "retire": 0.2 + 0.5, "apply": 0.4, "plan": 0.3, "draft": 0.05,
        "inputs": 0.03, "launch": 0.02, "account": 0.0,
        spans.NO_SPAN: 0.5})          # the children tile both steps
    # one of the two steps launched work; all idle but 0.5 s is inside
    assert spans.step_idle_ms(a) == pytest.approx(1500.0)
    assert spans.apply_ms(a) == pytest.approx(1e3 * np.median([0.4, 2.0]))
    assert spans.plan_ms(a) == pytest.approx(1e3 * np.median([0.3, 0.4]))
    assert spans.draft_ms(a) == pytest.approx(50.0)
    assert spans.launch_host_ms(a) == pytest.approx(150.0)
    assert spans.chunk_dispatch_ms(a) == 0.0      # none occurred
    assert spans.idle_unattributed_pct(a) == pytest.approx(25.0)
    # the table is printed once, however many metrics read the run
    assert spans.analysis(ctx) is a
    err = capsys.readouterr().err
    assert err.count("alignment error") == 1 and "retire" in err


def test_spans_off_the_benchmarks_annotations_stop_the_run(ring):
    t, host_spans, opened_at = hand_made_run(skew=0.002)
    ring(host_spans)
    with pytest.raises(SystemExit, match="ms off the benchmark's"):
        spans.analysis(ctx_for(t, opened_at))
    ring([s for s in spans.shifted(host_spans, -0.002) if s.name != "step"])
    with pytest.raises(SystemExit, match="0 'step' spans"):
        spans.analysis(ctx_for(t, opened_at))


def test_a_program_without_spans_leaves_the_new_metrics_out(monkeypatch):
    """The parent commit under this benchmark: its tracer has no
    ``spans``, the readers return nothing, and the line that
    ``check_line`` sees lists none of them."""
    monkeypatch.setattr(observability, "get_tracer", lambda: object())
    t, _, opened_at = hand_made_run()
    for name, new in NEW.items():
        ctx = ctx_for(t, opened_at, name)
        cell = ctx["cell"]
        old = [m["name"] for m in cell.per_layer if m["name"] not in new]
        values = {m: 12.5 for m in old}
        for m in list(cell.per_layer):
            if m["name"] in new:
                assert cell.reader(m["name"])(ctx) is None
        assert [m["name"] for m in cell.per_layer] == old
        obj = line.last_line(
            cell, True, correct=True, attempted=3, failed=0, values=values,
            device={"platform": "tpu", "kind": "TPU v5 lite", "count": 1,
                    "memory_peak_bytes": 1, "busy_s": 1.0, "window_s": 2.0},
            compared={})
        line.check_line(obj, cell, True)


# -- every new metric has its file and its entry ------------------------------

@pytest.mark.parametrize("cell_name,metric", [
    (c, m) for c, ms in NEW.items() for m in ms])
def test_new_metric_has_its_file_and_its_entry(cell_name, metric):
    with open(os.path.join(spec.ROOT, "BENCHMARK.json")) as f:
        entry, = [m for m in json.load(f)["per_layer"]
                  if m["name"] == metric]
    assert entry["workloads"] == [cell_name]
    assert entry["source"] == "program_span" and entry["better"] == "lower"
    assert entry["unit"] == ("%" if "pct" in metric else "ms")
    cell = spec.load_cell(cell_name)
    assert metric in [m["name"] for m in cell.per_layer]
    read = cell.reader(metric)
    assert callable(read)
    # the file names its arithmetic in spans.py
    assert hasattr(spans, metric.rsplit(".", 1)[0])


# -- a run: a trace from the chip and a ring made to match -------------------

def ring_for(t, opened_at):
    """A ring as the server would have left it: one ``step`` span just
    inside each ``bench_step`` of the fixture, children tiling it, on
    the host's clock."""
    bench = t.host.pick(lambda n: n == "bench_step").inside(t.lo, t.hi)
    shift = opened_at - t.lo
    out, sid = [], 0
    names = ("retire", "apply", "plan", "chunk_prefill", "draft", "inputs",
             "launch", "account")
    for a, b in zip(bench.start, bench.end):
        a, b = a + 5e-6 + shift, b - 5e-6 + shift
        sid += 1
        step = sid
        out.append(Span("step", a, b, step, 0, {"iter": step}))
        cuts = np.linspace(a, b, len(names) + 1)
        for name, c0, c1 in zip(names, cuts, cuts[1:]):
            sid += 1
            out.append(Span(name, c0, c1, sid, step, {}))
    return out


@pytest.mark.parametrize("tag,cell_name", [
    ("tiny-chat-1chip", "gpt2xl-chat-open"),
    ("tiny-backlog-1chip", "gpt2xl-doc-backlog")])
def test_a_traced_line_from_a_recorded_trace_and_a_ring_passes(
        ring, tag, cell_name):
    t, want = test_bench_trace.load(tag)
    opened_at = 1234.5
    ring(ring_for(t, opened_at))
    ctx = ctx_for(t, opened_at, cell_name)
    cell = ctx["cell"]
    values = {m["name"]: 12.5 for m in cell.per_layer
              if m["name"] not in NEW[cell_name]}
    device = {"platform": "tpu", "kind": "TPU v5 lite", "count": 1,
              "memory_peak_bytes": 9_000_000_000}
    only_new = dataclasses.replace(cell, per_layer=[
        m for m in cell.per_layer if m["name"] in NEW[cell_name]])
    breakdown = readers.read_all(only_new, ctx, values, device)
    a = ctx["program_spans"]
    assert a.align_s < 2e-5 and len(a.steps) > 3
    # every idle second has its phase, and they add up to the line's
    assert sum(a.by_phase.values()) == pytest.approx(
        device["window_s"] - device["busy_s"], rel=1e-6)
    assert want["busy_s"] == pytest.approx(device["busy_s"])
    # and what lies under the program's spans is what lies under the
    # benchmark's own annotations of the same calls
    bench = t.host.pick(lambda n: n == "bench_step").inside(t.lo, t.hi)
    assert sum(v for k, v in a.by_phase.items() if k != spans.NO_SPAN) \
        == pytest.approx(float(np.sum(spans.idle_within(
            *a.idle, bench.start, bench.end))), abs=1e-5 * len(a.steps))
    assert "bench_step" in dict(breakdown["idle_gaps"])
    obj = line.last_line(cell, True, correct=True, attempted=9, failed=0,
                         values=values, device=device, compared={},
                         breakdown=breakdown)
    assert set(NEW[cell_name]) <= set(obj["metrics"])
    text = line.check_line(obj, cell, True)
    got = json.loads(text)["metrics"]
    # the made ring tiles every step and has no ``submit``: what is
    # unattributed is what the benchmark spent between its steps
    assert got[NEW[cell_name][-1]]["value"] == pytest.approx(
        100.0 * a.by_phase[spans.NO_SPAN] / a.idle_s)
    assert got[NEW[cell_name][0]]["value"] > 0           # step_idle_ms
