"""Per-compiled-program accounting for the serving engine.

The latency histograms (PR 4) split a step into *phases* (prefill /
chunk_prefill / decode / verify spans) and the pipeline block (PR 8)
into a host/device share — but none of them answer the question an
engine owner actually asks when a step gets slow: **which compiled
program is the time going to**, per shape variant?  A server runs a
small, closed set of XLA programs (one per
chunk width, one decode, one per verify width, their fused-sampling
twins, and the COW block copy); this module tallies each of them.

- :class:`ProgramAccounting` — per-program-key cells of call count,
  host wall time, compile count, and compile time.  The key is the
  program name plus its shape variant (``prefill[64]``,
  ``chunk_prefill_sampled[32]``, ``decode``, ``verify[5]``,
  ``copy_blocks``), so a recompile storm or a mis-bucketed workload
  shows up as extra keys, not just extra time.  With a ``registry=``
  every cell also feeds labeled registry counters
  (``serving_program_calls{program=...}`` / ``_wall_s`` /
  ``_compiles`` / ``_compile_s``), so one Prometheus scrape carries
  the table.
- :data:`NULL_PROGRAM_ACCOUNTING` — the disabled instance
  (``enabled = False``); ``DecodeEngine`` guards its marks on
  ``programs.enabled or tracer.enabled`` so the disabled path skips
  even the clock reads.

Wall-time semantics: the tally measures the HOST-side cost of each
launch — argument staging plus the jit call.  For synchronously
executed programs (donated calls on CPU, materialized logits paths)
that includes device time; for the async-dispatched sampled twins the
device-bound share surfaces separately as the pipelined loop's retire
wait (``stats()["pipeline"]["host_stall_ms"]``).  A call whose jit
cache grew is a *compile call*: its whole wall time is attributed to
``compile_s`` (trace + lower + compile dominate it), and the
steady-state per-call figure excludes it — which is exactly why the
compile split exists: one slow first call must not poison the
steady-state average the table is read for.

Accounting never feeds back into scheduling and draws no randomness,
so a soak runs byte-identical with it on or off (the chaos axis runs
with it on).  Surfaced as the pinned ``stats()["programs"]`` table
and rendered over the wire by ``tools/ops_probe.py --programs``
(``docs/observability.md``, "Ops plane & watchdog").
"""

from __future__ import annotations

import time
from typing import Any, Dict, Optional


class NullProgramAccounting:
    """The disabled accounting: marks are no-ops and the engine skips
    clock reads entirely (``programs.enabled`` guard)."""

    enabled = False

    def begin(self) -> float:
        return 0.0

    def note(self, program: str, t0: float, compiled: bool) -> None:
        pass

    def table(self) -> Dict[str, Dict[str, Any]]:
        return {}


NULL_PROGRAM_ACCOUNTING = NullProgramAccounting()


class _Cell:
    """One program key's tallies (plus its registry counter views)."""

    __slots__ = ("calls", "wall_s", "compiles", "compile_s",
                 "_c_calls", "_c_wall", "_c_compiles", "_c_compile_s")

    def __init__(self, registry, program: str):
        self.calls = 0
        self.wall_s = 0.0
        self.compiles = 0
        self.compile_s = 0.0
        if registry is not None:
            self._c_calls = registry.counter(
                "serving_program_calls", program=program)
            self._c_wall = registry.counter(
                "serving_program_wall_s", program=program)
            self._c_compiles = registry.counter(
                "serving_program_compiles", program=program)
            self._c_compile_s = registry.counter(
                "serving_program_compile_s", program=program)
        else:
            self._c_calls = self._c_wall = None
            self._c_compiles = self._c_compile_s = None

    def note(self, wall: float, compiled: bool) -> None:
        self.calls += 1
        self.wall_s += wall
        if compiled:
            self.compiles += 1
            self.compile_s += wall
        if self._c_calls is not None:
            self._c_calls.incr()
            self._c_wall.incr(wall)
            if compiled:
                self._c_compiles.incr()
                self._c_compile_s.incr(wall)


class ProgramAccounting:
    """Call-count + wall-time + compile tallies per compiled program.

    Args:
      registry: optional :class:`MetricsRegistry`; each program key
        then feeds four labeled counters so scrapes carry the table.
      clock: injectable monotonic-seconds source (deterministic
        tests).
    """

    enabled = True

    def __init__(self, registry=None, clock=time.perf_counter):
        self._registry = registry
        self._clock = clock
        self._cells: Dict[str, _Cell] = {}

    def begin(self) -> float:
        """Pre-launch clock mark; pair with :meth:`note`."""
        return self._clock()

    def note(self, program: str, t0: float, compiled: bool) -> None:
        """Account one launch of ``program`` started at ``t0``;
        ``compiled`` attributes the call's wall time to compilation."""
        wall = self._clock() - t0
        cell = self._cells.get(program)
        if cell is None:
            cell = self._cells[program] = _Cell(self._registry, program)
        cell.note(wall, compiled)

    def table(self) -> Dict[str, Dict[str, Any]]:
        """``{program_key: row}`` sorted by key — the
        ``stats()["programs"]["by_program"]`` table.  ``steady_ms``
        is the per-call average EXCLUDING compile calls (0.0 until a
        program has run post-compile)."""
        out: Dict[str, Dict[str, Any]] = {}
        for key in sorted(self._cells):
            c = self._cells[key]
            steady_calls = c.calls - c.compiles
            steady_s = c.wall_s - c.compile_s
            out[key] = {
                "calls": c.calls,
                "compiles": c.compiles,
                "wall_ms": round(c.wall_s * 1e3, 3),
                "compile_ms": round(c.compile_s * 1e3, 3),
                "steady_ms": round(steady_s / steady_calls * 1e3, 4)
                if steady_calls > 0 else 0.0,
            }
        return out
