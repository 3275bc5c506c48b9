"""The reducer: interval arithmetic on hand-made cases, and the traces
recorded on the chip (``record_fixture.py``, PR 23) against the numbers
written beside them and against what any trace must satisfy."""

import glob
import gzip
import json
import os

import numpy as np
import pytest

from benchmarks.harness import trace
from benchmarks.harness.trace import Line, Trace

DATA = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data")
FIXTURES = sorted(os.path.basename(p)[:-len(".xplane.pb.gz")]
                  for p in glob.glob(os.path.join(DATA, "*.xplane.pb.gz")))


def line(*events):
    return Line([e[0] for e in events],
                np.array([e[1] for e in events], float),
                np.array([e[2] for e in events], float))


def hand_made(ops, modules, lo=0.0, hi=10.0, second=None):
    planes = {"/device:TPU:0": {"ops": ops, "modules": modules}}
    if second is not None:
        planes["/device:TPU:1"] = {"ops": second, "modules": modules}
    host = line((trace.WINDOW, lo, hi), ("bench_step", 0.0, 4.0),
                ("bench_feed", 4.0, 6.0))
    return Trace(planes, lo, hi, host)


def test_union_covers_overlap_once():
    s = np.array([0.0, 1.0, 5.0, 5.5, 9.0])
    e = np.array([2.0, 3.0, 6.0, 5.8, 9.5])
    assert trace.covered(s, e) == pytest.approx(3.0 + 1.0 + 0.5)
    assert trace.covered(np.zeros(0), np.zeros(0)) == 0.0
    # what is left of [0, 10] once [2, 3] and [4, 12] are cut out
    assert trace.subtract(np.array([0.0]), np.array([10.0]),
                          np.array([2.0, 4.0]), np.array([3.0, 12.0])) \
        == pytest.approx(3.0)


def test_busy_is_a_clipped_union_on_one_device_never_a_sum():
    ops = line(("%a = x", -1.0, 1.0),       # starts before the window
               ("%while.1 = x", 2.0, 6.0),  # holds the next two
               ("%b = x", 2.5, 3.0), ("%c = x", 3.0, 5.0),
               ("%d = x", 9.0, 12.0))       # ends after the window
    quiet = line(("%a = x", 1.0, 2.0))
    t = hand_made(ops, line(("jit_step(1)", 2.0, 6.0)), second=quiet)
    assert t.device == "/device:TPU:0"          # the busiest, not the sum
    assert t.busy_s == pytest.approx(1.0 + 4.0 + 1.0)
    assert t.window_s == 10.0 and 0 < t.busy_s <= t.window_s
    gaps = dict(t.breakdown()["idle_gaps"])
    # [1, 2] under bench_step, [6, 9] starts under nothing we annotated
    assert gaps["bench_step"] == pytest.approx(1.0)
    assert gaps["bench_unannotated"] == pytest.approx(3.0)


def test_kernels_programs_and_collectives():
    ops = line(("%_adam_kernel.3 = f32[8] custom-call()", 0.0, 1.0),
               ("%_decode_kernel = bf16[8] custom-call()", 1.0, 2.0),
               ("%_decode_kernel_q8.1 = bf16[8] custom-call()", 2.0, 3.0),
               ("%all-reduce.7 = f32[4] all-reduce()", 3.0, 6.0),
               ("%fusion.2 = f32[4] fusion()", 5.0, 7.0))
    mods = line(("jit_step(77)", 0.0, 4.0), ("jit_step(77)", 4.0, 9.0),
                ("jit__decode_stoch_impl(5)", 9.0, 9.5),
                ("jit_step(77)", 9.6, 10.4))         # not wholly inside
    t = hand_made(ops, mods)
    assert t.kernel_seconds("_adam_kernel") == (1.0, 1)
    assert t.kernel_seconds("_decode_kernel") == (1.0, 1)    # not the q8
    assert t.kernel_seconds("_fwd_kernel") == (0.0, 0)
    assert t.kernel_seconds("_adam_kernel", 0.5, 9.0) == (0.5, 1)
    assert t.whole_launches("jit_step") == (2, 0.0, 9.0)
    assert t.whole_launches("jit_nothing") == (0, None, None)
    assert list(t.program_durations("jit__decode")) == [0.5]
    assert t.collective_exposed_s() == pytest.approx(2.0)    # [3, 5]
    assert hand_made(line(("%f = x", 0.0, 1.0)),
                     mods).collective_exposed_s() is None
    top = t.breakdown()["device_ops"]
    assert top[0] == ["%all-reduce.7 f32[4] all-reduce", 3.0]
    assert len(top) <= 10
    assert trace.op_label(
        "%_ln_fwd_kernel.5 = (f32[512,768]{1,0:T(8,128)S(1)}, f32[512,128]"
        "{1,0}) custom-call(bf16[512,768]{1,0:T(8,128)(2,1)S(1)} %pad.2), "
        "custom_call_target=\"tpu_custom_call\"") \
        == "%_ln_fwd_kernel.5 f32[512,768] custom-call"
    assert trace.op_label("%copy-start.10") == "%copy-start.10"


def test_the_stub_of_a_launch_in_flight_is_no_whole_launch():
    """Ten steps of 283 ms and, before them, the 1.5 ms stub the
    profiler leaves of the step that was running when it started: ten
    whole launches, or every share that divides by their count reads a
    tenth too high (``step_mfu.train`` 36.7 for 33.4)."""
    step = 0.283
    mods = line(("jit_step(7)", 0.0100, 0.0115), *[
        ("jit_step(7)", 0.0115 + i * step, 0.0115 + (i + 1) * step)
        for i in range(10)])
    t = hand_made(line(("%f = x", 0.0, 3.0)), mods, hi=3.06)
    n, lo, hi = t.whole_launches("jit_step")
    assert n == 10 and lo == pytest.approx(0.0115)
    assert n / (hi - lo) == pytest.approx(1 / step)
    assert len(t.program_durations("jit_step")) == 11    # the median's


def test_a_trace_with_no_device_plane_or_no_window_is_an_error():
    class Plane:
        def __init__(self, name, lines):
            self.name, self.lines = name, lines

    class Profile:
        planes = [Plane("/host:CPU", [])]

    with pytest.raises(ValueError, match="no /device:TPU"):
        Trace.from_profile(Profile())


def load(tag):
    import jax
    with gzip.open(os.path.join(DATA, tag + ".xplane.pb.gz"), "rb") as f:
        profile = jax.profiler.ProfileData.from_serialized_xspace(f.read())
    with open(os.path.join(DATA, tag + ".json")) as f:
        return Trace.from_profile(profile), json.load(f)


def test_the_chip_fixtures_are_there():
    assert len(FIXTURES) >= 4, FIXTURES
    assert any("4chip" in tag for tag in FIXTURES), FIXTURES


@pytest.mark.parametrize("tag", FIXTURES)
def test_recorded_trace_gives_the_numbers_written_beside_it(tag):
    t, want = load(tag)
    assert t.device == want["device"] and sorted(t.planes) == want["devices"]
    assert t.busy_s == pytest.approx(want["busy_s"], rel=1e-9)
    assert t.window_s == pytest.approx(want["window_s"], rel=1e-9)
    got = {k: [len(v), sum(v)] for k, v in t.programs().items()}
    assert got.keys() == want["programs"].keys()
    for k, (n, total) in want["programs"].items():
        assert got[k][0] == n and got[k][1] == pytest.approx(total, rel=1e-9)
    for k, (seconds, n) in want["kernels"].items():
        s, c = t.kernel_seconds(k)
        assert c == n and s == pytest.approx(seconds, rel=1e-9, abs=1e-12)
    # what of the collectives no other operation hid, on the busiest
    # chip: None where the trace holds no collective (one chip)
    exposed = t.collective_exposed_s()
    if want["collective_exposed_s"] is None:
        assert exposed is None and len(t.planes) == 1
    else:
        assert exposed == pytest.approx(want["collective_exposed_s"],
                                        rel=1e-9)
        assert 0 < exposed < t.busy_s
        assert t.busy_s == max(t.busy_on(d) for d in t.planes)


@pytest.mark.parametrize("tag", FIXTURES)
def test_recorded_trace_holds_what_any_trace_must(tag):
    t, want = load(tag)
    assert 0 < t.busy_s <= t.window_s
    # busy is one device's union: no more than its operations' sum, and
    # every device's own busy time fits the window
    ops = t.ops()
    assert t.busy_s <= float(np.sum(ops.durations)) + 1e-9
    for d in t.planes:
        assert t.busy_on(d) <= t.window_s + 1e-9
    # programs wholly inside the window cannot outlast it
    assert sum(sum(v) for v in t.programs().values()) <= t.window_s * 1.001
    b = t.breakdown()
    assert 1 <= len(b["device_ops"]) <= 10 and len(b["idle_gaps"]) <= 10
    idle = sum(s for _, s in b["idle_gaps"])
    assert idle <= t.window_s - t.busy_s + 1e-6
    if "train" in tag:
        n, lo, hi = t.whole_launches("jit_step")
        assert n >= 3 and t.kernel_seconds("_adam_kernel", lo, hi)[1] == n
    if "4chip" in tag:
        assert len(t.planes) == 4 and t.collective_exposed_s() is not None
