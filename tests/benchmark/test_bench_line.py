"""``check_line`` takes the line the driver reads and refuses each way a
line can be wrong; ``last_line`` builds only what the cell lists."""

import copy
import json
import math

import pytest

from benchmarks.harness import line, spec

CELLS = ["gpt2m-train-1chip", "gpt2xl-chat-open", "gpt2xl-doc-backlog"]


def good(cell, traced):
    wanted = cell.per_layer if traced else cell.end_to_end
    values = {m["name"]: 12.5 for m in wanted}
    device = {"platform": "tpu", "kind": "TPU v5 lite", "count": cell.chips,
              "memory_peak_bytes": 9_000_000_000}
    if traced:
        device.update(busy_s=2.5, window_s=3.0)
    return line.last_line(
        cell, traced, correct=True, attempted=40, failed=0, values=values,
        device=device, compared={"x_gap": {"value": 0.01, "limit": 0.05}},
        breakdown={"device_ops": [["%fusion.1", 1.0]],
                   "idle_gaps": [["bench_step", 0.2]]} if traced else None)


@pytest.mark.parametrize("traced", [False, True])
@pytest.mark.parametrize("name", CELLS)
def test_good_line_passes_and_round_trips(name, traced):
    cell = spec.load_cell(name)
    obj = good(cell, traced)
    text = line.check_line(obj, cell, traced)
    back = json.loads(text)
    assert list(back)[-1] == "compared"
    assert set(back) >= {"correct", "attempted", "failed", "metrics",
                         "device"}
    assert ("breakdown" in back) == traced
    assert "\n" not in text


def _drop_metric(o):
    o["metrics"].pop(next(iter(o["metrics"])))


def _nan(o):
    o["metrics"][next(iter(o["metrics"]))]["value"] = math.nan


def _none(o):
    o["metrics"][next(iter(o["metrics"]))]["value"] = None


def _unit(o):
    o["metrics"][next(iter(o["metrics"]))]["unit"] = "furlongs"


BREAKS = {
    "missing metric": (_drop_metric, None),
    "NaN": (_nan, None),
    "None": (_none, None),
    "wrong unit": (_unit, None),
    "extra key": (lambda o: o.update(notes="x"), None),
    "extra metric": (lambda o: o["metrics"].update(
        zzz={"value": 1.0, "unit": "s"}), None),
    "missing key": (lambda o: o.pop("failed"), None),
    "correct not a bool": (lambda o: o.update(correct="yes"), None),
    "more failed than attempted": (lambda o: o.update(failed=41), None),
    "wrong chip count": (lambda o: o["device"].update(count=3), None),
    "no memory peak": (lambda o: o["device"].update(memory_peak_bytes=0),
                       None),
    "busy_s 0": (lambda o: o["device"].update(busy_s=0.0), True),
    "busy_s above window_s": (lambda o: o["device"].update(busy_s=3.2),
                              True),
    "no window_s": (lambda o: o["device"].pop("window_s"), True),
    "breakdown too long": (lambda o: o["breakdown"].update(
        device_ops=[["x", 1.0]] * 11), True),
    "share over 100": (lambda o: o["metrics"].__setitem__(
        "step_mfu.train", {"value": 104.0, "unit": "%"}), True),
    "compared without limit": (lambda o: o["compared"].update(
        y={"value": 1.0}), None),
}


@pytest.mark.parametrize("why", sorted(BREAKS))
def test_wrong_line_is_refused(why):
    breaker, only_traced = BREAKS[why]
    cell = spec.load_cell("gpt2m-train-1chip")
    for traced in ([True] if only_traced else [False, True]):
        obj = copy.deepcopy(good(cell, traced))
        breaker(obj)
        with pytest.raises(line.LineError):
            line.check_line(obj, cell, traced)


def test_a_reader_that_found_nothing_leaves_its_metric_out_and_is_refused():
    cell = spec.load_cell("gpt2m-train-1chip")
    values = {m["name"]: 1.0 for m in cell.per_layer[1:]}
    obj = line.last_line(cell, True, correct=True, attempted=1, failed=0,
                         values=values, device={
                             "platform": "tpu", "kind": "TPU v5 lite",
                             "count": 1, "memory_peak_bytes": 1,
                             "busy_s": 1.0, "window_s": 2.0}, compared={})
    assert cell.per_layer[0]["name"] not in obj["metrics"]
    with pytest.raises(line.LineError, match="missing"):
        line.check_line(obj, cell, True)
