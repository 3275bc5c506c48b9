"""Device time by block of the program: the operations of the traced
sub-window put down to the device scope (``apex_tpu.observability.
DEVICE_SCOPES``) they were made under.

The trace names each operation of a chip by its HLO text
(``%fusion.12 = bf16[8,1024]{...} fusion(...)``, no metadata) and each
launch by its program (``jit_step(<id>)``).  The program's optimized HLO
holds what the trace leaves out: each instruction's
``metadata={op_name=... stack_frame_id=...}``, the scope path the
program gave it (``jit(step)/transpose(jvp(GPTLMHeadModel))/block_0/
mlp/dot_general``) and the frame of the line that made it.  The process
still holds the compiled programs of the run when its readers run, so
the paths are read from the HLO of the live executables whose module
names launched in the window, and an operation is found there by its
program and its ``op_label`` (name, first shape, opcode).

An operation's block is the innermost name of the vocabulary on its
path; flax module names, ``jit(...)`` and a transformation's wrapper
(``transpose(jvp(attention))`` is ``attention``) are not names of it,
so the backward pass lands in its forward operation's block.  A
block's seconds are the exclusive time of the busiest chip's
operations in the window that lie in it (each moment goes to the
latest started of the operations running then, so a ``while`` counts
only the time its body does not cover), and its share is that over the
time of all of them, which is the chip's busy time.  ``unscoped`` is
what lies in no block, operations with no metadata included.

On a checkout whose program has no vocabulary the metrics are left out
of the line, as ``spans.reader`` leaves its own; so they are where the
window's programs are no longer alive (a serving run frees its server
before the readers run).
"""

import re
import sys
import time

import numpy as np

from benchmarks.harness.trace import op_label, program_of

UNSCOPED = "unscoped"
TOP_UNSCOPED = 5

# -- paths from the optimized HLO ---------------------------------------------

_INSTRUCTION = re.compile(r"^\s*(?:ROOT )?(%.*)$")
_OP_NAME = re.compile(r'metadata=\{op_name="((?:[^"\\]|\\.)*)"([^}]*)\}')
_FRAME = re.compile(r"stack_frame_id=(\d+)")
_SOURCE = re.compile(r'source_file="([^"]*)" source_line=(\d+)')


def _table(text, name):
    """``{id: the rest of the line}`` of one of the module's debug
    tables (``FileNames``, ``FileLocations``, ``StackFrames``)."""
    head = f"\n{name}\n"
    if head not in text:
        return {}
    body = text.split(head, 1)[1].split("\n\n", 1)[0]
    return dict(re.findall(r"^(\d+) (.*)$", body, re.M))


def frame_sources(text):
    """``{stack frame id: "file:line"}`` of the frames of the HLO module
    ``text``: the innermost frame an instruction names is the line of
    the program that made it."""
    files = {k: v.strip('"') for k, v in _table(text, "FileNames").items()}
    locs = {}
    for k, v in _table(text, "FileLocations").items():
        f, line = (re.search(r"file_name_id=(\d+)", v),
                   re.search(r"\bline=(\d+)", v))
        if f and line:
            locs[k] = f"{files.get(f.group(1), '?')}:{line.group(1)}"
    out = {}
    for k, v in _table(text, "StackFrames").items():
        loc = re.search(r"file_location_id=(\d+)", v)
        if loc and loc.group(1) in locs:
            out[k] = locs[loc.group(1)]
    return out


def program_paths(text):
    """``{op_label: (path, source)}`` of every instruction of the HLO
    module ``text`` that carries a scope path; ``source`` is None where
    the metadata names no line."""
    sources = frame_sources(text)
    out = {}
    for raw in text.splitlines():
        m = _INSTRUCTION.match(raw)
        if m is None or "metadata={op_name=" not in raw:
            continue
        meta = _OP_NAME.search(raw)
        if meta is None:
            continue
        rest, source = meta.group(2), None
        frame, where = _FRAME.search(rest), _SOURCE.search(rest)
        if frame:
            source = sources.get(frame.group(1))
        elif where:
            source = f"{where.group(1)}:{where.group(2)}"
        out.setdefault(op_label(m.group(1)), (meta.group(1), source))
    return out


def live_paths(programs):
    """``{(program, op_label): (path, source)}`` from the optimized HLO
    of the executables this process holds whose module is named in
    ``programs``.  Two executables of one name (one program at two
    shapes) differ in their labels' shapes; where a label is in both,
    the first kept is the one met first."""
    import jax
    out = {}
    for exe in jax.extend.backend.get_backend().live_executables():
        for module in exe.hlo_modules():
            if module.name not in programs:
                continue
            for label, got in program_paths(module.to_string()).items():
                out.setdefault((module.name, label), got)
    return out


def programs_of(trace, ops):
    """The program of each of ``ops``: the launch on the busiest chip's
    ``XLA Modules`` line that holds the operation's start, or None."""
    mods = trace.planes[trace.device]["modules"]
    order = np.argsort(mods.start, kind="stable")
    start, end = mods.start[order], mods.end[order]
    names = [program_of(mods.names[i]) for i in order]
    at = np.searchsorted(start, ops.start, side="right") - 1
    return [names[i] if i >= 0 and s < end[i] else None
            for i, s in zip(at.tolist(), ops.start.tolist())]


# -- from a path to a block ---------------------------------------------------

_WRAPPED = re.compile(r"^[\w.\-]+\((.*)\)$")


def _unwrapped(part):
    """``attention`` from ``transpose(jvp(attention))``."""
    while True:
        m = _WRAPPED.match(part)
        if m is None:
            return part
        part = m.group(1)


def block_of(path, vocabulary):
    """The innermost name of ``vocabulary`` on the scope path ``path``,
    or None.  The last part of a path is the operation itself and is no
    scope."""
    if not path:
        return None
    for part in reversed(path.split("/")[:-1]):
        name = _unwrapped(part)
        if name in vocabulary:
            return name
    return None


def exclusive(start, end):
    """Each interval's share of the union: every moment goes to the
    latest started of the intervals running then, so one operation that
    holds others (a ``while`` and its body) counts only its own time,
    and the shares add up to the union's length."""
    start = np.asarray(start, float)
    end = np.asarray(end, float)
    own = [0.0] * len(start)
    real = np.nonzero(end > start)[0]
    times = np.concatenate([start[real], end[real]]).tolist()
    opens = [1] * len(real) + [0] * len(real)
    which = np.concatenate([real, real]).tolist()
    # at one moment, closings before openings
    order = np.lexsort((opens, times)).tolist()
    stack, at = [], None
    for k in order:
        t, i = times[k], which[k]
        if stack and t > at:
            own[stack[-1]] += t - at
        at = t
        if opens[k]:
            stack.append(i)
        elif stack[-1] == i:
            stack.pop()
        else:
            stack.remove(i)
    return np.asarray(own)


class Blocks:
    """One traced sub-window's device time by block."""

    def __init__(self, trace, vocabulary, paths):
        ops = trace.ops()
        own = exclusive(ops.start, ops.end)
        self.seconds = dict.fromkeys(vocabulary, 0.0)
        self.seconds[UNSCOPED] = 0.0
        by_op, loose, self.largest = {}, {}, {}
        for key, sec in zip(zip(programs_of(trace, ops), ops.names),
                            own.tolist()):
            by_op[key] = by_op.get(key, 0.0) + sec
        for (program, name), sec in by_op.items():
            label = op_label(name)
            path, source = paths.get((program, label), (None, None))
            block = block_of(path, vocabulary)
            if block is None:
                block = UNSCOPED
                key = (label, source or "-")
                loose[key] = loose.get(key, 0.0) + sec
            self.seconds[block] += sec
            if sec > self.largest.get(block, ("", 0.0))[1]:
                self.largest[block] = (label, sec)
        self.total_s = sum(self.seconds.values())
        self.loose = sorted(loose.items(), key=lambda kv: -kv[1])

    def pct(self, block):
        return (100.0 * self.seconds[block] / self.total_s
                if self.total_s > 0 else 0.0)

    def table(self):
        lines = [f"blocks: device time {self.total_s:.6f} s by device scope",
                 f"blocks: {'block':<14} {'seconds':>10} {'share_pct':>10}"
                 "  largest operation"]
        for name, sec in sorted(self.seconds.items(), key=lambda kv: -kv[1]):
            label, op_s = self.largest.get(name, ("-", 0.0))
            lines.append(f"blocks: {name:<14} {sec:>10.6f} "
                         f"{self.pct(name):>10.3f}  {label} {op_s:.6f} s")
        for (label, source), sec in self.loose[:TOP_UNSCOPED]:
            lines.append(f"blocks: unscoped op {sec:.6f} s {label} "
                         f"({source})")
        return "\n".join(lines)


def vocabulary():
    """The program's ``DEVICE_SCOPES``, or None where it has none."""
    import apex_tpu.observability as obs
    return getattr(obs, "DEVICE_SCOPES", None)


def analysis(ctx):
    """The run's ``Blocks``, made once and kept in ``ctx``; None where
    the program has no vocabulary or none of the window's programs is
    alive to read."""
    if "device_blocks" not in ctx:
        vocab, trace = vocabulary(), ctx["trace"]
        ctx["device_blocks"] = None
        if vocab is None:
            print("blocks: this program names no device scopes; the "
                  "metrics that read them are left out", file=sys.stderr)
            return None
        t0 = time.perf_counter()
        programs = {program_of(n) for n in
                    trace.planes[trace.device]["modules"].names}
        paths = live_paths(programs)
        if not paths:
            print(f"blocks: none of the window's programs "
                  f"{sorted(programs)} is alive with operation metadata; "
                  "the metrics that read it are left out", file=sys.stderr)
            return None
        got = Blocks(trace, vocab, paths)
        print(got.table(), file=sys.stderr)
        print(f"blocks: {len(paths)} paths of {len(programs)} programs "
              f"read and put to the window's operations in "
              f"{time.perf_counter() - t0:.2f}s", file=sys.stderr,
              flush=True)
        ctx["device_blocks"] = got
    return ctx["device_blocks"]


def reader(metric, block):
    """A metric file's ``read``: the share of the device's time in
    ``block`` (or ``UNSCOPED``).  Where there is nothing to read, the
    metric also leaves the cell's list for this line, as
    ``spans.reader`` does."""
    def read(ctx):
        got = analysis(ctx)
        if got is None:
            cell = ctx["cell"]
            cell.per_layer = [m for m in cell.per_layer
                              if m["name"] != metric]
            return None
        return got.pct(block)
    return read
