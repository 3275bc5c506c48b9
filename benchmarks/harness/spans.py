"""The program's own spans on the device's clock: which phase of
``server.step()`` held the host while the device stood idle.

The server's process tracer (``apex_tpu.observability.get_tracer()``,
which a default server shares) records while the profiler's session is
open, in ``time.perf_counter`` seconds.  Both clocks saw the traced
sub-window open: ``SubWindow.opened_at`` on the host's clock, the start
of the ``bench_window`` annotation (``Trace.lo``) on the trace's.  Their
distance shifts every span onto the trace's clock, where the device's
idle intervals (what ``union(trace.ops())`` leaves of the window) are
put down to the innermost span that covers them.

The shift is checked, not trusted: every ``step`` span must coincide,
start and end, with one of the benchmark's own ``bench_step``
annotations within ``ALIGN_S``, else the run stops: a silent zero would
read as a fast host.

The span names, as ``docs/observability.md`` lists them: ``step`` holds
``retire``, ``apply``, ``plan`` (with ``admit``, ``cow_copy``),
``chunk_prefill`` or ``prefill`` (with ``prefill_read``), ``draft``,
``inputs``, ``launch``, ``account``; ``submit`` holds ``retire`` and
``apply``.
"""

import collections
import sys

import numpy as np

from benchmarks.harness.trace import union

ALIGN_S = 1e-3
STEP, SUBMIT, BENCH_STEP = "step", "submit", "bench_step"
LAUNCHES = ("launch", "chunk_prefill", "prefill")
NO_SPAN = "(no span)"

Span = collections.namedtuple("Span", "name start end span_id parent_id args")


# -- arithmetic on intervals --------------------------------------------------

def shifted(spans, by):
    return [s._replace(start=s.start + by, end=s.end + by) for s in spans]


def idle_intervals(ops_start, ops_end, lo, hi):
    """What the union of the operation intervals leaves of [lo, hi]."""
    s, e = union(np.asarray(ops_start, float), np.asarray(ops_end, float))
    gap_s = np.concatenate([[lo], np.minimum(e, hi)])
    gap_e = np.concatenate([np.maximum(s, lo), [hi]])
    keep = gap_e > gap_s
    return gap_s[keep], gap_e[keep]


def innermost(spans):
    """The timeline cut at every span's edges: ``(start, end, span)``
    pieces in order, each under the innermost span that covers it
    (spans of one thread nest)."""
    # at one moment, closings sort before openings
    edges = sorted((t, opens, i) for i, s in enumerate(spans)
                   if s.end > s.start
                   for t, opens in ((s.start, 1), (s.end, 0)))
    out, stack, at = [], [], None
    for t, opens, i in edges:
        if stack and t > at:
            out.append((at, t, spans[stack[-1]]))
        at = t
        if opens:
            stack.append(i)
        elif i in stack:
            stack.remove(i)
    return out


def idle_within(idle_s, idle_e, a, b):
    """Idle seconds inside each [a[i], b[i]]."""
    if not len(idle_s):
        return np.zeros(len(a))
    before = np.concatenate([[0.0], np.cumsum(idle_e - idle_s)])

    def until(t):
        j = np.searchsorted(idle_s, t, side="right") - 1
        inside = np.clip(t - idle_s[np.maximum(j, 0)], 0.0,
                         (idle_e - idle_s)[np.maximum(j, 0)])
        return np.where(j >= 0, before[np.maximum(j, 0)] + inside, 0.0)

    return until(np.asarray(b, float)) - until(np.asarray(a, float))


def attribute(idle_s, idle_e, spans):
    """Idle seconds by the name of the innermost span that covers
    them; what no span covers goes under ``NO_SPAN``."""
    pieces = innermost(spans)
    got = idle_within(idle_s, idle_e, [p[0] for p in pieces],
                      [p[1] for p in pieces])
    by = {}
    for (_, _, span), sec in zip(pieces, got):
        by[span.name] = by.get(span.name, 0.0) + float(sec)
    by[NO_SPAN] = max(0.0, float(np.sum(idle_e - idle_s))
                      - sum(by.values()))
    return by


def children_of(spans):
    kids = {}
    for s in spans:
        kids.setdefault(s.parent_id, []).append(s)
    return kids


def self_time(span, kids):
    """The span's duration less what its children cover."""
    mine = kids.get(span.span_id, [])
    s, e = union(np.array([k.start for k in mine]),
                 np.array([k.end for k in mine]))
    return (span.end - span.start) - float(np.sum(
        np.minimum(e, span.end) - np.maximum(s, span.start)))


def alignment_error(steps, bench_start, bench_end):
    """The widest distance between a ``step`` span's edge and its
    ``bench_step`` annotation's, pairing them in order; raises where
    their numbers differ."""
    if len(steps) != len(bench_start):
        raise ValueError(f"{len(steps)} {STEP!r} spans of the program "
                         f"against {len(bench_start)} {BENCH_STEP!r} "
                         "annotations of the benchmark")
    if not steps:
        raise ValueError(f"no {STEP!r} span in the traced sub-window")
    order = np.argsort(bench_start)
    a = np.array([[s.start, s.end] for s in steps])
    b = np.stack([np.asarray(bench_start)[order],
                  np.asarray(bench_end)[order]], axis=1)
    return float(np.max(np.abs(a - b)))


# -- one traced run -------------------------------------------------------------

class Analysis:
    """The spans of one traced sub-window on the trace's clock, the
    device's idle seconds by phase, and what the metrics read."""

    def __init__(self, spans, trace):
        lo, hi = trace.lo, trace.hi
        self.spans = spans = [s for s in spans
                              if s.start >= lo and s.end <= hi]
        self.kids = children_of(spans)
        self.steps = [s for s in spans if s.name == STEP]
        bench = trace.host.pick(lambda n: n == BENCH_STEP).inside(lo, hi)
        self.align_s = alignment_error(self.steps, bench.start, bench.end)
        if self.align_s > ALIGN_S:
            raise ValueError(
                f"the program's {STEP!r} spans lie {self.align_s * 1e3:.3f}"
                f" ms off the benchmark's {BENCH_STEP!r} annotations "
                f"(limit {ALIGN_S * 1e3:g} ms): the two clocks do not "
                "line up")
        ops = trace.ops()
        self.idle = idle_intervals(ops.start, ops.end, lo, hi)
        self.idle_s = float(np.sum(self.idle[1] - self.idle[0]))
        self.by_phase = attribute(*self.idle, spans)

    def under(self, step, names):
        return [k for k in self.kids.get(step.span_id, [])
                if k.name in names]

    def per_step_ms(self, names, self_only=False):
        """Median, over the steps in which a span named so occurred, of
        the time the step spent under such spans; 0.0 where none did."""
        per = []
        for st in self.steps:
            got = self.under(st, names)
            if got:
                per.append(sum(self_time(k, self.kids) if self_only
                               else k.end - k.start for k in got))
        return 1e3 * float(np.median(per)) if per else 0.0

    def table(self):
        rows = {}
        for s in self.spans:
            r = rows.setdefault(s.name, [0, 0.0, 0.0])
            r[0] += 1
            r[1] += self_time(s, self.kids)
        for name, sec in self.by_phase.items():
            rows.setdefault(name, [0, 0.0, 0.0])[2] = sec
        lines = [f"spans: {len(self.steps)} steps, device idle "
                 f"{self.idle_s:.6f} s, alignment error "
                 f"{self.align_s * 1e6:.1f} us",
                 f"spans: {'name':<14} {'count':>6} {'self_s':>10} "
                 f"{'idle_s':>10}"]
        for name, (n, self_s, idle) in sorted(rows.items(),
                                              key=lambda kv: -kv[1][2]):
            lines.append(f"spans: {name:<14} {n:>6} {self_s:>10.6f} "
                         f"{idle:>10.6f}")
        return "\n".join(lines)


def analysis(ctx):
    """The run's ``Analysis``, made once and kept in ``ctx``; None
    where the program has no span API to read (a checkout from before
    it had one)."""
    if "program_spans" not in ctx:
        from apex_tpu.observability import get_tracer
        read = getattr(get_tracer(), "spans", None)
        if read is None:
            print("spans: this program's tracer hands out no spans; the "
                  "metrics that read them are left out", file=sys.stderr)
            ctx["program_spans"] = None
        else:
            trace, window = ctx["trace"], ctx["run"]["window"]
            try:
                got = Analysis(shifted([Span(*s) for s in read()],
                                       trace.lo - window.opened_at), trace)
            except ValueError as e:
                raise SystemExit(f"{ctx['cell'].name}: {e}")
            print(got.table(), file=sys.stderr, flush=True)
            ctx["program_spans"] = got
    return ctx["program_spans"]


def reader(metric, compute):
    """A metric file's ``read``.  ``check_line`` refuses a line that
    lacks a metric its cell lists, so where the program has no spans
    to read the metric also leaves the cell's list for this line."""
    def read(ctx):
        got = analysis(ctx)
        if got is None:
            cell = ctx["cell"]
            cell.per_layer = [m for m in cell.per_layer
                              if m["name"] != metric]
            return None
        return compute(got)
    return read


# -- what the metrics read ----------------------------------------------------

def step_idle_ms(a):
    """Device idle time inside ``step`` spans over the number of those
    that launched work."""
    launched = sum(1 for st in a.steps if a.under(st, LAUNCHES))
    idle = float(np.sum(idle_within(*a.idle, [s.start for s in a.steps],
                                    [s.end for s in a.steps])))
    return 1e3 * idle / launched if launched else 0.0


def apply_ms(a):
    return a.per_step_ms(("apply",), self_only=True)


def plan_ms(a):
    return a.per_step_ms(("plan",))


def draft_ms(a):
    return a.per_step_ms(("draft",))


def launch_host_ms(a):
    """``inputs`` plus ``launch`` of one decode or verify launch."""
    per = [sum(k.end - k.start for k in a.under(st, ("inputs", "launch")))
           for st in a.steps if a.under(st, ("launch",))]
    return 1e3 * float(np.median(per)) if per else 0.0


def chunk_dispatch_ms(a):
    """Host time of one ``chunk_prefill`` span: its duration less the
    ``prefill_read`` in which a final chunk waits for the device."""
    d = [self_time(s, a.kids) for s in a.spans if s.name == "chunk_prefill"]
    return 1e3 * float(np.median(d)) if d else 0.0


def idle_unattributed_pct(a):
    """The share of the device's idle time under ``step`` or ``submit``
    themselves, or under no span at all."""
    loose = sum(a.by_phase.get(k, 0.0) for k in (STEP, SUBMIT, NO_SPAN))
    return 100.0 * loose / a.idle_s if a.idle_s > 0 else 0.0
