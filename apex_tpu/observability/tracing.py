"""In-process span tracer with Chrome trace-event export.

Answers "where did this request / this step spend its time" — the
question xprof annotations (``utils/profiling.py``) can't, because
they only label ops *inside* compiled programs.  This tracer lives on
the host side of the step loop: scheduler phases (admit / prefix-match
/ chunk-prefill / decode / evict / preempt), engine compile events,
checkpoint save/restore/publish, and the amp step all record spans
here, and the export is Chrome trace-event JSON that loads directly in
Perfetto (https://ui.perfetto.dev) or ``chrome://tracing``.

Design points:

- **Zero overhead when off.**  The process default records only
  while a ``jax.profiler`` session is active: off, ``span()`` is one
  attribute read, one ``TraceAnnotation.is_enabled()`` and the shared
  no-op context-manager singleton, and ``instant()`` does nothing —
  nothing is allocated or recorded per event, and hot paths can
  additionally guard on ``tracer.enabled``.  ``APEX_TPU_TRACE=
  /path/trace.json`` (exported at process exit) or
  :func:`enable_tracing` / :func:`set_tracer` turn recording on for
  good; :data:`NULL_TRACER` stays for whoever passes it.
- **On the device's clock.**  While a profiler session is active every
  span also enters a ``jax.profiler.TraceAnnotation`` named
  ``apex:<span>`` (:data:`PROFILER_PREFIX`) with the span's arguments,
  so xprof / Perfetto show the program's phases on the host line above
  the device's operations, on the profiler's own clock.  Whether a span
  records and annotates is decided once, at its entry, and remembered:
  a session that starts or stops inside a span leaves the per-thread
  stack balanced.  jax is resolved on first use; this module imports
  without it.
- **Bounded memory.**  Events land in a ring buffer
  (``deque(maxlen=capacity)``); a long-running server keeps the most
  recent window and reports how many events rolled off
  (:attr:`SpanTracer.dropped`).
- **Monotonic, injectable clock.**  Timestamps come from
  ``time.perf_counter`` relative to tracer construction (exported in
  microseconds, the Chrome ``ts`` unit); tests inject a fake clock
  for deterministic output.  :meth:`SpanTracer.spans` hands out the
  closed spans in the clock's own seconds for readers that line them
  up with another timeline.
- **Span / parent ids.**  Spans nest per thread (a thread-local
  stack); every B/instant event carries ``span_id`` and, when nested,
  ``parent_id`` in its ``args``, so request flows reconstruct even
  outside the viewer.

See ``docs/observability.md`` for the instrumented span names and a
Perfetto walkthrough.
"""

from __future__ import annotations

import atexit
import itertools
import json
import os
import threading
import time
from collections import deque, namedtuple
from typing import Optional

TRACE_ENV = "APEX_TPU_TRACE"
# every span's name on the profiler's host line: ``apex:step``,
# ``apex:retire``...  (``bench_`` is the benchmark's own)
PROFILER_PREFIX = "apex:"

Span = namedtuple("Span", "name start end span_id parent_id args")


_annotation = None


def _profiler():
    """``jax.profiler.TraceAnnotation``, resolved on first use: this
    module is read by tools that start no backend."""
    global _annotation
    if _annotation is None:
        from jax.profiler import TraceAnnotation
        _annotation = TraceAnnotation
    return _annotation


class _NullSpan:
    """The shared do-nothing context manager ``NullTracer.span``
    returns — one instance per process, never one per call."""

    __slots__ = ()

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        return False

    def set(self, **args):
        pass


_NULL_SPAN = _NullSpan()
# what ``begin`` leaves on the stack for a span it did not record, so
# that the ``end`` that pairs with it pops that and nothing else
_OFF = (0, None, None)


class NullTracer:
    """The disabled tracer: every operation is a no-op and allocates
    nothing per event (``span()`` hands back the one module-level
    :class:`_NullSpan`)."""

    enabled = False
    events = ()
    dropped = 0

    def span(self, name, **args):
        return _NULL_SPAN

    def begin(self, name, **args):
        return 0

    def end(self):
        pass

    def instant(self, name, **args):
        pass

    def clear(self):
        pass

    def chrome_events(self):
        return []

    def export_chrome(self, path):
        return None

    def spans(self):
        return []


NULL_TRACER = NullTracer()


class SpanTracer:
    """Recording tracer: bounded ring buffer of span/instant events.
    While a ``jax.profiler`` session is active every span also enters
    a ``TraceAnnotation`` named ``PROFILER_PREFIX + name``.

    Args:
      capacity: ring-buffer bound (events past it evict the oldest;
        :attr:`dropped` counts them).
      clock: monotonic seconds source (injectable for determinism).
      pid: the ``pid`` stamped on exported events (defaults to the
        real process id).
    """

    # records whenever asked; the process default clears this and
    # records only while a profiler session is active
    _always = True

    def __init__(self, capacity: int = 1 << 16,
                 clock=time.perf_counter, pid: Optional[int] = None):
        if capacity < 2:
            raise ValueError(f"capacity must be >= 2, got {capacity}")
        self.capacity = int(capacity)
        self._clock = clock
        self._t0 = clock()
        self._events = deque(maxlen=self.capacity)
        self._appended = 0
        self._ids = itertools.count(1)
        self._local = threading.local()
        self.pid = os.getpid() if pid is None else int(pid)

    @property
    def enabled(self) -> bool:
        return self._always or _profiler().is_enabled()

    # -- recording --------------------------------------------------------

    def _ts_us(self) -> float:
        return (self._clock() - self._t0) * 1e6

    def _stack(self):
        st = getattr(self._local, "stack", None)
        if st is None:
            st = self._local.stack = []
        return st

    def _push(self, ev) -> None:
        self._appended += 1
        self._events.append(ev)

    def _open(self, name: str, args: dict):
        """Record a span's begin and, under a profiler session, enter
        its annotation; returns the stack frame :meth:`end` pops."""
        sid = next(self._ids)
        st = self._stack()
        parent = st[-1][0] if st else 0
        self._push(("B", name, self._ts_us(), threading.get_ident(),
                    sid, parent, args))
        ann = None
        prof = _profiler()
        if prof.is_enabled():
            ann = prof(PROFILER_PREFIX + name, **args)
            ann.__enter__()
        frame = (sid, name, ann)
        st.append(frame)
        return frame

    def begin(self, name: str, **args) -> int:
        """Open a span; returns its id (0 for one not recorded).
        Prefer :meth:`span` — begin/end must pair up per thread or the
        B/E nesting breaks."""
        if not self.enabled:
            self._stack().append(_OFF)
            return 0
        return self._open(name, args)[0]

    def end(self) -> None:
        """Close the current thread's innermost open span: what its
        :meth:`begin` decided holds, whatever the profiler did since."""
        st = self._stack()
        frame = st.pop() if st else (0, None, None)
        if frame is _OFF:
            return
        sid, name, ann = frame
        if ann is not None:
            ann.__exit__(None, None, None)
        self._push(("E", name, self._ts_us(), threading.get_ident(),
                    sid, 0, None))

    def span(self, name: str, **args):
        """``with tracer.span("launch", batch=4): ...``"""
        if not self.enabled:
            return _NULL_SPAN
        return _span_ctx(self, name, args)

    def instant(self, name: str, **args) -> None:
        """A zero-duration marker (Chrome ``ph="i"``) — compile
        events, preemptions, request lifecycle edges."""
        if not self.enabled:
            return
        st = self._stack()
        parent = st[-1][0] if st else 0
        self._push(("i", name, self._ts_us(), threading.get_ident(),
                    next(self._ids), parent, args or None))

    def clear(self) -> None:
        self._events.clear()
        self._appended = 0

    # -- introspection / export -------------------------------------------

    @property
    def events(self):
        return tuple(self._events)

    @property
    def dropped(self) -> int:
        """Events evicted by the ring buffer since construction or
        :meth:`clear`."""
        return self._appended - len(self._events)

    def spans(self):
        """The closed spans still in the ring, by start: ``Span(name,
        start, end, span_id, parent_id, args)`` with ``start``/``end``
        in the clock's own seconds (``time.perf_counter`` unless
        injected), ``parent_id`` 0 at the top."""
        begun, out = {}, []
        for ph, name, ts, _tid, sid, parent, args in tuple(self._events):
            if ph == "B":
                begun[sid] = (name, ts, parent, args)
            elif ph == "E" and sid in begun:
                name, ts0, parent, args = begun.pop(sid)
                out.append(Span(name, self._t0 + ts0 * 1e-6,
                                self._t0 + ts * 1e-6, sid, parent,
                                args or {}))
        out.sort(key=lambda s: (s.start, s.span_id))
        return out

    def chrome_events(self):
        """The buffer as Chrome trace-event dicts: ``ph`` B/E/i,
        ``ts`` in microseconds, ``pid``/``tid``, span/parent ids in
        ``args``."""
        out = []
        for ph, name, ts, tid, sid, parent, args in self._events:
            ev = {"ph": ph, "ts": round(ts, 3), "pid": self.pid,
                  "tid": tid}
            if name is not None:
                ev["name"] = name
            if ph != "E":
                a = {"span_id": sid}
                if parent:
                    a["parent_id"] = parent
                if args:
                    a.update(args)
                ev["args"] = a
            if ph == "i":
                ev["s"] = "t"       # thread-scoped instant
            out.append(ev)
        return out

    def export_chrome(self, path: str) -> str:
        """Write the buffer as a Chrome/Perfetto-loadable JSON trace;
        returns ``path``."""
        data = {
            "traceEvents": self.chrome_events(),
            "displayTimeUnit": "ms",
            "otherData": {"producer": "apex_tpu.observability",
                          "dropped_events": self.dropped},
        }
        with open(path, "w") as f:
            json.dump(data, f)
            f.write("\n")
        return path


class _span_ctx:
    """Reentrant-per-call span context manager (one tiny object per
    *recorded* span; the off path never reaches here)."""

    __slots__ = ("_tracer", "_name", "_args", "_ann")

    def __init__(self, tracer, name, args):
        self._tracer = tracer
        self._name = name
        self._args = args

    def __enter__(self):
        self._ann = self._tracer._open(self._name, self._args)[2]
        return self

    def __exit__(self, *exc):
        self._tracer.end()
        return False

    def set(self, **args):
        """Add arguments learned inside the span (a request's uid once
        it exists): they land on the ring's begin event and on the
        profiler's annotation."""
        self._args.update(args)     # the dict the begin event holds
        if self._ann is not None:
            self._ann.set_metadata(**args)


# -- process default -------------------------------------------------------

_tracer = None


def _export_at_exit(tracer: SpanTracer, path: str) -> None:
    try:
        tracer.export_chrome(path)
    except OSError:
        pass                        # never fail interpreter shutdown


class _ProfilerFollower(SpanTracer):
    """The process default: records (and annotates) only while a
    ``jax.profiler`` session is active — "on" is "someone is
    profiling"."""

    _always = False


def get_tracer():
    """The process tracer.  First call resolves it: a recording
    :class:`SpanTracer` exporting to ``$APEX_TPU_TRACE`` at exit when
    that env var names a path, else one that records only while a
    ``jax.profiler`` session is active."""
    global _tracer
    if _tracer is None:
        path = os.environ.get(TRACE_ENV)
        if path:
            _tracer = SpanTracer()
            atexit.register(_export_at_exit, _tracer, path)
        else:
            _tracer = _ProfilerFollower()
    return _tracer


def set_tracer(tracer):
    """Install ``tracer`` as the process tracer; returns the previous
    one (which may be None if never resolved) so tests can restore
    it."""
    global _tracer
    prev, _tracer = _tracer, tracer
    return prev


def enable_tracing(path: Optional[str] = None, *,
                   capacity: int = 1 << 16,
                   clock=time.perf_counter) -> SpanTracer:
    """Install and return a recording process tracer; with ``path``,
    also export there at interpreter exit (the programmatic twin of
    ``APEX_TPU_TRACE``)."""
    tracer = SpanTracer(capacity=capacity, clock=clock)
    set_tracer(tracer)
    if path:
        atexit.register(_export_at_exit, tracer, path)
    return tracer
