"""``harness/blocks.py``: the device's time by block of the program.
The paths read from a module's optimized HLO, on hand-written text and
on programs compiled here; the rule that gives an operation's block on
hand-made paths; the exclusive time of nested operations; an
operation's program; and the readers with and without a vocabulary in
the program, on hand-made traces and on the traces recorded on the chip
(``record_fixture.py``)."""

import json
import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))

import test_bench_trace  # noqa: E402
from apex_tpu import observability  # noqa: E402
from apex_tpu.observability import device_scope  # noqa: E402
from benchmarks.harness import blocks, line, readers, spec  # noqa: E402
from benchmarks.harness.trace import covered, op_label  # noqa: E402
from test_bench_trace import FIXTURES, hand_made  # noqa: E402
from test_bench_trace import line as events  # noqa: E402

VOCAB = observability.DEVICE_SCOPES
NEW = ["head_device_pct.train", "optimizer_device_pct.train",
       "unscoped_device_pct.train"]
CELLS = ["gpt2m-train-1chip", "gpt2m-train-ddp4"]

# the shape of a module's text as ``HloModule.to_string`` gives it
HLO = """HloModule jit_step, is_scheduled=true

FileNames
1 "apex_tpu/models/gpt.py"
2 "optax/losses/_classification.py"

FunctionNames
1 "__call__"

FileLocations
1 {file_name_id=1 function_name_id=1 line=354 end_line=354 column=4}
2 {file_name_id=2 function_name_id=1 line=401 end_line=401 column=8}

StackFrames
1 {file_location_id=1 parent_frame_id=1}
2 {file_location_id=2 parent_frame_id=1}


%region_0.1 (a: f32[], b: f32[]) -> f32[] {
  %a = f32[] parameter(0), metadata={op_name="reduce_sum"}
  ROOT %add.3 = f32[] add(%a, %b), metadata={op_name="jit(step)/jvp(head)/reduce_sum" stack_frame_id=1}
}

ENTRY %main.9 (p: f32[8,5]) -> f32[] {
  %p = f32[8,5]{1,0} parameter(0), metadata={op_name="params[0]"}
  %log.0 = f32[8,5]{1,0:T(8,128)} log(%p), metadata={op_name="jit(step)/jvp(head)/log" stack_frame_id=2}
  %fusion.12 = (bf16[8]{0}, f32[8]{0}) fusion(%p), kind=kLoop, calls=%region_0.1, metadata={op_name="jit(step)/optimizer/mul" source_file="apex_tpu/optimizers/fused_adam.py" source_line=77}
  %copy.1 = f32[8,5]{0,1} copy(%log.0)
  ROOT %reduce.2 = f32[] reduce(%log.0), dimensions={0,1}, to_apply=%region_0.1, metadata={op_name="jit(step)/jvp(head)/reduce_sum" stack_frame_id=9}
}
"""


def without_metadata(text):
    """The ENTRY instructions of a module's text as the trace names
    them: the HLO text with no ``metadata``."""
    body = text.split("ENTRY ", 1)[1].split("\n", 1)[1]
    out = []
    for raw in body.splitlines():
        raw = raw.strip()
        if raw.startswith("ROOT "):
            raw = raw[len("ROOT "):]
        if raw.startswith("%"):
            out.append(raw.split(", metadata={", 1)[0])
    return out


# -- paths from the optimized HLO ---------------------------------------------

def test_frames_name_their_file_and_line():
    assert blocks.frame_sources(HLO) == {
        "1": "apex_tpu/models/gpt.py:354",
        "2": "optax/losses/_classification.py:401"}
    assert blocks.frame_sources("HloModule m\n\nENTRY %e () -> f32[] {\n}") \
        == {}


def test_program_paths_key_each_instruction_by_its_label():
    got = blocks.program_paths(HLO)
    assert got["%log.0 f32[8,5] log"] == (
        "jit(step)/jvp(head)/log", "optax/losses/_classification.py:401")
    # a ROOT, and a computation that is not the entry
    assert got["%add.3 f32[] add"] == ("jit(step)/jvp(head)/reduce_sum",
                                       "apex_tpu/models/gpt.py:354")
    # a tuple's first shape, and a line named in the metadata itself
    assert got["%fusion.12 bf16[8] fusion"] == (
        "jit(step)/optimizer/mul", "apex_tpu/optimizers/fused_adam.py:77")
    # a frame the tables do not hold names no line
    assert got["%reduce.2 f32[] reduce"] == (
        "jit(step)/jvp(head)/reduce_sum", None)
    # no metadata, no entry; a parameter's name is no path but is kept
    assert "%copy.1 f32[8,5] copy" not in got
    assert got["%p f32[8,5] parameter"] == ("params[0]", None)
    # the trace's name of an operation has the same label
    for name in without_metadata(HLO):
        if not name.startswith("%copy.1 "):
            assert op_label(name) in got, name


def _scoped(x):
    with device_scope("mlp"):
        y = jnp.tanh(x) * 2.0
    with device_scope("head"):
        return jnp.sum(y * y)


def _compiled(fn, *args):
    jitted = jax.jit(fn)
    jitted(*args).block_until_ready()
    return jitted, jitted.lower(*args).compile().as_text()


def test_live_paths_read_the_programs_this_process_holds():
    x = jnp.arange(8.0)
    keep, text = _compiled(_scoped, x)
    got = blocks.live_paths({"jit__scoped"})
    assert got, "the compiled program is alive"
    assert {p for p, _ in got} == {"jit__scoped"}
    found = {blocks.block_of(path, VOCAB) for path, _ in got.values()}
    assert {"mlp", "head"} <= found
    sources = {src for path, src in got.values()
               if blocks.block_of(path, VOCAB)}
    assert all(src and "test_bench_blocks.py:" in src for src in sources)
    # every operation of the entry that carries metadata is found by the
    # label of the name the trace gives it
    entry = text.split("ENTRY ", 1)[1]
    named = [raw for raw in entry.splitlines()[1:]
             if "metadata={op_name=" in raw]
    assert named
    for raw in named:
        name, = without_metadata("ENTRY %e\n" + raw)
        assert ("jit__scoped", op_label(name)) in got, name
    # a program not asked for is not read
    assert blocks.live_paths({"jit_no_such_program"}) == {}
    del keep


# -- an operation's block -----------------------------------------------------

CHUNK_AND = "jit(_chunk_stoch_impl)/GPTLMHeadModel/wte/jit(_take)/and:"


@pytest.mark.parametrize("path,block", [
    ("jit(_decode_sampled_impl)/GPTLMHeadModel/block_3/attention/"
     "kv_write/scatter:", "kv_write"),                      # nested
    ("jit(step)/transpose(jvp(GPTLMHeadModel))/block_0/mlp/mlp_in/"
     "dot_general", "mlp"),                                 # backward
    ("jit(f)/transpose(jvp(attention))/mul", "attention"),
    ("jit(f)/transpose(jvp(head))/vmap(jvp(head))/vmap()/checkpoint/"
     "rematted_computation/mlp/cos", "mlp"),
    ("jit(step)/optimizer/pallas_call", "optimizer"),
    ("jit(f)/Model/norm/head/mul", "head"),                  # innermost
    ("jit(_decode_impl)/sample/jit(_thresholds)/while", "sample"),
    (CHUNK_AND, None),                                      # none
    ("jit(f)/head", None),                  # the last part is the op
    ("", None), (None, None)])
def test_an_operations_block_is_the_innermost_name_of_the_vocabulary(
        path, block):
    assert blocks.block_of(path, VOCAB) == block


@pytest.mark.parametrize("start,end,want", [
    # the first holds three
    ([0.0, 1.0, 2.0, 5.0, 7.0], [6.0, 2.0, 4.0, 6.0, 8.0],
     [2.0, 1.0, 2.0, 1.0, 1.0]),
    # the third starts inside the second and outlasts it
    ([0.0, 1.0, 4.0], [10.0, 5.0, 7.0], [4.0, 3.0, 3.0]),
    # two alike, and one of no length
    ([0.0, 0.0, 3.0], [2.0, 2.0, 3.0], [0.0, 2.0, 0.0])])
def test_exclusive_time_counts_each_moment_once(start, end, want):
    start, end = np.array(start), np.array(end)
    own = blocks.exclusive(start, end)
    assert list(own) == pytest.approx(want)
    assert own.sum() == pytest.approx(covered(start, end))


def test_an_operation_belongs_to_the_launch_that_holds_its_start():
    ops = events(("%a = x", 0.5, 1.0), ("%b = x", 3.5, 4.0),
                 ("%c = x", 2.5, 2.6), ("%d = x", 6.0, 7.0))
    mods = events(("jit_g(2)", 3.0, 5.0), ("jit_step(1)", 0.0, 2.0),
                  ("jit_step(1)", 5.5, 9.0))
    t = hand_made(ops, mods)
    assert blocks.programs_of(t, t.ops()) == ["jit_step", "jit_g", None,
                                              "jit_step"]


def test_shares_on_a_hand_made_trace():
    ops = events(("%w = f32[2] while()", 0.0, 4.0),
                 ("%a = f32[2] sort()", 0.5, 1.5),
                 ("%b = f32[2] dot()", 2.0, 3.0),
                 ("%c = f32[2] and()", 5.0, 6.0),
                 ("%b = f32[2] dot()", 7.0, 7.5),     # another program's
                 ("%d = f32[2] add()", 9.0, 12.0))    # ends after the window
    t = hand_made(ops, events(("jit_step(1)", 0.0, 6.5),
                              ("jit_other(2)", 6.8, 7.8)))
    paths = {("jit_step", "%w f32[2] while"): ("jit(f)/sample/while:",
                                                "s.py:1"),
             ("jit_step", "%a f32[2] sort"): ("jit(f)/sample/jit(g)/sort:",
                                               "s.py:2"),
             ("jit_step", "%b f32[2] dot"): ("jit(f)/M/block_0/mlp/dot:",
                                              "m.py:3"),
             ("jit_step", "%c f32[2] and"): ("jit(f)/M/wte/and:", "m.py:4"),
             ("jit_other", "%b f32[2] dot"): ("jit(o)/head/dot:", "h.py:5")}
    got = blocks.Blocks(t, VOCAB, paths)
    assert got.total_s == pytest.approx(t.busy_s) == 6.5
    assert got.seconds["sample"] == pytest.approx(3.0)
    assert got.seconds["mlp"] == pytest.approx(1.0)
    assert got.seconds["head"] == pytest.approx(0.5)
    assert got.seconds[blocks.UNSCOPED] == pytest.approx(2.0)   # %c, %d
    assert got.pct("sample") == pytest.approx(300 / 6.5)
    assert sum(got.pct(b) for b in got.seconds) == pytest.approx(100.0)
    # what lies in no block, by operation and the line that made it
    assert dict(got.loose) == pytest.approx({
        ("%c f32[2] and", "m.py:4"): 1.0, ("%d f32[2] add", "-"): 1.0})
    # and each block's largest operation, by its own time: the loop's
    # 4 s less the 2 its body covers
    assert got.largest["sample"] == ("%w f32[2] while", pytest.approx(2.0))
    assert "sample" in got.table().splitlines()[2]


@pytest.mark.parametrize("tag", FIXTURES)
def test_the_shares_of_a_recorded_trace_add_up(tag):
    """With no path for any of its operations a trace is all
    ``unscoped``, and the exclusive time is its busy time: the
    fixtures' operations do not nest."""
    t, want = test_bench_trace.load(tag)
    got = blocks.Blocks(t, VOCAB, {})
    assert got.total_s == pytest.approx(want["busy_s"], rel=1e-6)
    assert got.pct(blocks.UNSCOPED) == pytest.approx(100.0)
    assert got.table().count("blocks: unscoped op ") == min(
        blocks.TOP_UNSCOPED, len(got.loose))


# -- the readers --------------------------------------------------------------

def ctx_for(trace, cell_name):
    return {"trace": trace, "cell": spec.load_cell(cell_name)}


@pytest.mark.parametrize("cell_name,metric", [
    (c, m) for c in CELLS for m in NEW])
def test_new_metric_has_its_file_and_its_entry(cell_name, metric):
    with open(os.path.join(spec.ROOT, "BENCHMARK.json")) as f:
        entry, = [m for m in json.load(f)["per_layer"]
                  if m["name"] == metric]
    assert entry["workloads"] == CELLS
    assert entry["source"] == "program_span" and entry["better"] == "lower"
    assert entry["unit"] == "%" and entry["layer"] == "whole step"
    cell = spec.load_cell(cell_name)
    assert entry["moves"] == "train_tokens_per_s"
    assert entry["moves"] in [m["name"] for m in cell.end_to_end]
    assert metric in [m["name"] for m in cell.per_layer]
    assert callable(cell.reader(metric))


def _recorded_with_paths(monkeypatch, tag="tiny-train-1chip"):
    """The recorded training trace, with the paths its program would
    give: the largest of its operations under ``head`` and the next
    under ``optimizer``."""
    t, want = test_bench_trace.load(tag)
    ops = t.ops()
    by_op = {}
    for n, d in zip(ops.names, ops.durations):
        by_op[n] = by_op.get(n, 0.0) + float(d)
    first, second = sorted(by_op, key=by_op.get, reverse=True)[:2]
    paths = {("jit_step", op_label(first)): ("jit(step)/head/dot:", None),
             ("jit_step", op_label(second)): ("jit(step)/optimizer/x:",
                                              None)}
    asked = []

    def live(programs):
        asked.append(programs)
        return paths

    monkeypatch.setattr(blocks, "live_paths", live)
    return t, want, asked


def test_the_readers_on_a_recorded_trace(monkeypatch, capsys):
    t, _, asked = _recorded_with_paths(monkeypatch)
    ctx = ctx_for(t, "gpt2m-train-1chip")
    cell = ctx["cell"]
    got = {m: cell.reader(m)(ctx) for m in NEW}
    assert all(isinstance(v, float) and v > 0 for v in got.values())
    assert sum(got.values()) == pytest.approx(100.0)
    # the programs are read once, for the programs the trace launched
    assert asked == [{"jit_step"}]
    err = capsys.readouterr().err
    assert err.count("by device scope") == 1
    assert ctx["device_blocks"] is blocks.analysis(ctx)


def test_the_readers_on_a_program_compiled_here(capsys):
    """The whole path on the CPU: a program with device scopes, run
    and alive, a trace that names its operations as the chip's does."""
    x = jnp.arange(8.0)
    keep, text = _compiled(_scoped, x)
    names = without_metadata(text)
    ops = events(*[(n, float(i), i + 0.5) for i, n in enumerate(names)])
    t = hand_made(ops, events(("jit__scoped(7)", 0.0, float(len(names)))))
    ctx = ctx_for(t, "gpt2m-train-1chip")
    got = {m: ctx["cell"].reader(m)(ctx) for m in NEW}
    analysed = ctx["device_blocks"]
    assert analysed.seconds["head"] > 0
    assert analysed.seconds["mlp"] + analysed.seconds["head"] \
        + analysed.seconds[blocks.UNSCOPED] == pytest.approx(t.busy_s)
    assert got["optimizer_device_pct.train"] == 0.0
    assert got["head_device_pct.train"] == pytest.approx(
        analysed.pct("head"))
    assert "by device scope" in capsys.readouterr().err
    del keep


def test_a_program_without_the_vocabulary_leaves_the_new_metrics_out(
        monkeypatch, capsys):
    """The parent commit under this benchmark: its observability has no
    ``DEVICE_SCOPES``, the readers return nothing, and the line that
    ``check_line`` sees lists none of them."""
    monkeypatch.delattr(observability, "DEVICE_SCOPES")
    t, _ = test_bench_trace.load("tiny-train-1chip")
    for name in CELLS:
        ctx = ctx_for(t, name)
        cell = ctx["cell"]
        old = [m["name"] for m in cell.per_layer if m["name"] not in NEW]
        for m in list(cell.per_layer):
            if m["name"] in NEW:
                assert cell.reader(m["name"])(ctx) is None
        assert [m["name"] for m in cell.per_layer] == old
        obj = line.last_line(
            cell, True, correct=True, attempted=3, failed=0,
            values={m: 12.5 for m in old},
            device={"platform": "tpu", "kind": "TPU v5 lite",
                    "count": cell.chips, "memory_peak_bytes": 1,
                    "busy_s": 1.0, "window_s": 2.0},
            compared={})
        line.check_line(obj, cell, True)
    assert "names no device scopes" in capsys.readouterr().err


def test_programs_no_longer_alive_leave_the_new_metrics_out(capsys):
    ops = events(("%a = f32[2] add()", 0.0, 1.0))
    t = hand_made(ops, events(("jit_freed_long_ago(3)", 0.0, 2.0)))
    ctx = ctx_for(t, "gpt2m-train-1chip")
    assert ctx["cell"].reader("head_device_pct.train")(ctx) is None
    assert "head_device_pct.train" not in [
        m["name"] for m in ctx["cell"].per_layer]
    assert "is alive with operation metadata" in capsys.readouterr().err


def test_read_all_with_the_new_metrics_passes_check_line(monkeypatch):
    t, want, _ = _recorded_with_paths(monkeypatch)
    ctx = ctx_for(t, "gpt2m-train-1chip")
    cell = ctx["cell"]
    values = {m["name"]: 12.5 for m in cell.per_layer
              if m["name"] not in NEW}
    device = {"platform": "tpu", "kind": "TPU v5 lite", "count": 1,
              "memory_peak_bytes": 9_000_000_000}
    only_new = spec.Cell(**{**cell.__dict__, "per_layer": [
        m for m in cell.per_layer if m["name"] in NEW]})
    breakdown = readers.read_all(only_new, ctx, values, device)
    assert device["busy_s"] == pytest.approx(want["busy_s"])
    # the breakdown is what it was: operations and idle gaps
    assert set(breakdown) == {"device_ops", "idle_gaps"}
    obj = line.last_line(cell, True, correct=True, attempted=9, failed=0,
                         values=values, device=device, compared={},
                         breakdown=breakdown)
    got = json.loads(line.check_line(obj, cell, True))["metrics"]
    assert set(NEW) <= set(got)
