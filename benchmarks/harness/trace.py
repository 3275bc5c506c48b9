"""From the profiler's ``.xplane.pb`` to the numbers the metrics read.

What a v5e trace holds (looked at by hand, PR 23): one plane per chip,
``/device:TPU:<n>``, whose line ``XLA Modules`` has one event for every
launch of a jitted program (``jit_step(<fingerprint>)``) and whose line
``XLA Ops`` has one event for every HLO operation, named by its HLO text
(``%_adam_kernel.3 = ... custom-call(...)``; a Pallas kernel carries the
name it gave ``pallas_call``).  Modules, steps and operations overlap
each other, so busy time is taken from the operations line of ONE
device, as the union of its intervals clipped to the window: never a sum
over lines or over devices.  The host's plane carries the benchmark's
own ``TraceAnnotation``s on the same clock; the one named ``WINDOW``
bounds the traced sub-window.
"""

import dataclasses
import re

import numpy as np

WINDOW = "bench_window"
_COLLECTIVE = re.compile(
    r"^%(all-reduce|all-gather|reduce-scatter|collective-permute|"
    r"all-to-all)")


class SubWindow:
    """The profiler over a bounded sub-window of a run: ``start`` opens
    a trace in a fresh directory under ``TMPDIR`` (outside what is
    copied back) with the ``WINDOW`` annotation, ``stop`` closes both,
    ``reduce`` reads the trace and deletes the directory.  The Python
    tracer is off: it slows the host and its events are not read."""

    def __init__(self):
        self.dir = self._ann = self.opened_at = None

    @property
    def started(self):
        return self.dir is not None

    @property
    def open(self):
        return self._ann is not None

    def start(self):
        import tempfile
        import time

        import jax
        self.dir = tempfile.mkdtemp(prefix="bench_trace_")
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        jax.profiler.start_trace(self.dir, profiler_options=opts)
        self._ann = jax.profiler.TraceAnnotation(WINDOW)
        self._ann.__enter__()
        self.opened_at = time.perf_counter()

    def stop(self):
        import jax
        self._ann.__exit__(None, None, None)
        self._ann = None
        jax.profiler.stop_trace()

    def reduce(self):
        import shutil
        try:
            return Trace.from_dir(self.dir)
        finally:
            shutil.rmtree(self.dir, ignore_errors=True)


@dataclasses.dataclass
class Line:
    names: list
    start: np.ndarray       # seconds on the trace's clock
    end: np.ndarray

    @classmethod
    def of(cls, line):
        names, start, dur = [], [], []
        for e in line.events:
            names.append(e.name)
            start.append(e.start_ns)
            dur.append(e.duration_ns)
        start = np.asarray(start, np.float64) * 1e-9
        return cls(names, start, start + np.asarray(dur, np.float64) * 1e-9)

    def pick(self, keep):
        idx = [i for i, n in enumerate(self.names) if keep(n)]
        return Line([self.names[i] for i in idx], self.start[idx],
                    self.end[idx])

    def clipped(self, lo, hi):
        """Intervals cut to [lo, hi]; those wholly outside go."""
        s, e = np.maximum(self.start, lo), np.minimum(self.end, hi)
        keep = np.nonzero(e > s)[0]
        return Line([self.names[i] for i in keep], s[keep], e[keep])

    def inside(self, lo, hi):
        """Events that lie wholly within [lo, hi]."""
        keep = np.nonzero((self.start >= lo) & (self.end <= hi))[0]
        return Line([self.names[i] for i in keep], self.start[keep],
                    self.end[keep])

    @property
    def durations(self):
        return self.end - self.start


def union(start, end):
    """Merged intervals ``(starts, ends)`` of possibly overlapping ones."""
    if len(start) == 0:
        return np.zeros(0), np.zeros(0)
    order = np.argsort(start, kind="stable")
    s, e = start[order], np.maximum.accumulate(end[order])
    new = np.concatenate([[True], s[1:] > e[:-1]])
    first = np.nonzero(new)[0]
    last = np.concatenate([first[1:] - 1, [len(s) - 1]])
    return s[first], e[last]


def covered(start, end):
    s, e = union(start, end)
    return float(np.sum(e - s))


def subtract(start, end, cut_start, cut_end):
    """Total length of the intervals left after cutting out the union
    of ``cut``: how long the first ran with none of the second."""
    s, e = union(start, end)
    both_s = np.concatenate([s, cut_start])
    both_e = np.concatenate([e, cut_end])
    return covered(both_s, both_e) - covered(cut_start, cut_end)


_OP = re.compile(r"^(%[^ ]+) = \(?(\w+\[[^\]]*\])[^ ]*.*? ([a-z][\w\-]*)\(")


def op_label(name):
    """``%fusion.12 bf16[8,1024,4096] fusion`` from an operation's HLO
    text: its result, the (first) shape it makes, its opcode."""
    m = _OP.match(name)
    return " ".join(m.groups()) if m else name.split(" = ", 1)[0][:80]


def program_of(name):
    """``jit_step`` from ``jit_step(1553...)``."""
    return name.split("(", 1)[0]


class Trace:
    """One traced sub-window, reduced lazily."""

    def __init__(self, planes, lo, hi, host):
        self.planes = planes        # {device name: {"ops", "modules"}}
        self.lo, self.hi = lo, hi
        self.host = host            # the benchmark's annotations
        busy = {d: self.busy_on(d) for d in planes}
        self.device = max(busy, key=busy.get)       # the busiest chip
        self.busy_s = busy[self.device]
        self.window_s = hi - lo

    @classmethod
    def from_file(cls, path):
        import jax
        return cls.from_profile(jax.profiler.ProfileData.from_file(path))

    @classmethod
    def from_dir(cls, trace_dir):
        """The one trace that ``jax.profiler.start_trace(trace_dir)``
        left behind."""
        import glob
        import os
        found = glob.glob(os.path.join(trace_dir, "plugins", "profile",
                                       "*", "*.xplane.pb"))
        if len(found) != 1:
            raise ValueError(f"{len(found)} traces under {trace_dir}")
        return cls.from_file(found[0])

    @classmethod
    def from_profile(cls, profile):
        planes, host = {}, None
        for plane in profile.planes:
            if plane.name.startswith("/device:TPU:"):
                lines = {l.name: l for l in plane.lines}
                if "XLA Ops" not in lines or "XLA Modules" not in lines:
                    continue
                planes[plane.name] = {"ops": Line.of(lines["XLA Ops"]),
                                      "modules": Line.of(lines["XLA Modules"])}
            elif plane.name == "/host:CPU":
                for line in plane.lines:
                    got = Line.of(line).pick(lambda n: n.startswith("bench_"))
                    if WINDOW in got.names:
                        host = got
        if not planes:
            raise ValueError("the trace has no /device:TPU: plane with "
                             "operations: nothing ran on a chip, or the "
                             "trace overflowed")
        if host is None:
            raise ValueError(f"the trace has no {WINDOW!r} annotation")
        i = host.names.index(WINDOW)
        return cls(planes, float(host.start[i]), float(host.end[i]), host)

    def ops(self, device=None):
        return self.planes[device or self.device]["ops"].clipped(
            self.lo, self.hi)

    def busy_on(self, device):
        """The union of one chip's operation intervals in the window."""
        ops = self.ops(device)
        return covered(ops.start, ops.end)

    def kernel_seconds(self, kernel, lo=None, hi=None):
        """Device time of the Pallas kernel ``kernel`` on the busiest
        chip, clipped to [lo, hi] (the window unless given), and how
        many of its events that holds."""
        pat = re.compile(r"^%" + re.escape(kernel) + r"(\.\d+)? = ")
        ops = self.planes[self.device]["ops"].clipped(
            self.lo if lo is None else lo, self.hi if hi is None else hi)
        got = ops.pick(lambda n: pat.match(n) is not None)
        return float(np.sum(got.durations)), len(got.names)

    def whole_launches(self, prefix):
        """The launches of programs named ``prefix``... that lie wholly
        inside the window: ``(count, first start, last end)``.  An
        event shorter than half the median launch is no whole launch
        but a stub the profiler leaves of one (1.5 ms beside ten steps
        of 283 in one traced training run of two, ``PERF.md`` section
        6, PR 26), and is not counted."""
        mods = self.planes[self.device]["modules"].inside(
            self.lo, self.hi).pick(
                lambda n: program_of(n).startswith(prefix))
        if not mods.names:
            return 0, None, None
        whole = mods.durations >= 0.5 * np.median(mods.durations)
        return (int(whole.sum()), float(mods.start[whole].min()),
                float(mods.end[whole].max()))

    def program_durations(self, prefix):
        """Device durations of the launches of programs whose name
        starts with ``prefix`` that lie wholly inside the window."""
        mods = self.planes[self.device]["modules"].inside(self.lo, self.hi)
        return mods.pick(
            lambda n: program_of(n).startswith(prefix)).durations

    def programs(self):
        mods = self.planes[self.device]["modules"].inside(self.lo, self.hi)
        out = {}
        for n, d in zip(mods.names, mods.durations):
            out.setdefault(program_of(n), []).append(float(d))
        return out

    def collective_exposed_s(self, lo=None, hi=None):
        """Time of collective operations on the busiest chip during
        which no other operation runs there."""
        ops = self.planes[self.device]["ops"].clipped(
            self.lo if lo is None else lo, self.hi if hi is None else hi)
        coll = ops.pick(lambda n: _COLLECTIVE.match(n) is not None)
        rest = ops.pick(lambda n: _COLLECTIVE.match(n) is None)
        if not coll.names:
            return None
        return subtract(coll.start, coll.end, *union(rest.start, rest.end))

    def breakdown(self, top=10):
        ops = self.ops()
        by_op = {}
        for n, d in zip(ops.names, ops.durations):
            key = op_label(n)
            by_op[key] = by_op.get(key, 0.0) + float(d)
        s, e = union(ops.start, ops.end)
        gap_s = np.concatenate([[self.lo], e])
        gap_e = np.concatenate([s, [self.hi]])
        spans = self.host.pick(lambda n: n != WINDOW)
        by_gap = {}
        for a, b in zip(gap_s, gap_e):
            if b <= a:
                continue
            mid = 0.5 * (a + b)
            hit = np.nonzero((spans.start <= mid) & (spans.end >= mid))[0]
            # the innermost annotation the host was inside
            label = (spans.names[hit[np.argmax(spans.start[hit])]]
                     if len(hit) else "bench_unannotated")
            by_gap[label] = by_gap.get(label, 0.0) + float(b - a)

        def rows(d):
            return [[k, v] for k, v in sorted(d.items(),
                                              key=lambda kv: -kv[1])[:top]]

        return {"device_ops": rows(by_op), "idle_gaps": rows(by_gap)}
