"""The one place the result line is built and checked.

``last_line`` builds the object for every cell in both modes;
``check_line`` holds it to the contract before it is printed, so that a
wrong line is never printed as if it were right.
"""

import json
import math

LINE_KEYS = ("correct", "attempted", "failed", "metrics", "device")
DEVICE_KEYS = ("platform", "kind", "count", "memory_peak_bytes")
TRACED_DEVICE_KEYS = ("busy_s", "window_s")


class LineError(ValueError):
    """The result line breaks the contract."""


def _number(x):
    return (isinstance(x, (int, float)) and not isinstance(x, bool)
            and math.isfinite(x))


def last_line(cell, traced, *, correct, attempted, failed, values, device,
              compared, breakdown=None):
    """``values``: every number the run has, by metric name; the line
    takes the ones this cell lists for this mode.  A per-layer reader
    that found nothing to read is absent from ``values`` and so from
    the line."""
    wanted = cell.per_layer if traced else cell.end_to_end
    metrics = {m["name"]: {"value": values[m["name"]], "unit": m["unit"]}
               for m in wanted if m["name"] in values}
    line = {"correct": bool(correct), "attempted": int(attempted),
            "failed": int(failed), "metrics": metrics,
            "device": dict(device)}
    if traced and breakdown is not None:
        line["breakdown"] = breakdown
    line["compared"] = compared      # last: each number beside its limit
    return line


def check_line(line, cell, traced):
    """Raise ``LineError`` unless ``line`` is what the driver reads."""
    allowed = set(LINE_KEYS) | {"compared"} | ({"breakdown"} if traced
                                               else set())
    for key in LINE_KEYS:
        if key not in line:
            raise LineError(f"the line lacks {key!r}")
    extra = set(line) - allowed
    if extra:
        raise LineError(f"keys the contract does not know: {sorted(extra)}")
    if not isinstance(line["correct"], bool):
        raise LineError("correct is not true or false")
    for key in ("attempted", "failed"):
        if not isinstance(line[key], int) or isinstance(line[key], bool) \
                or line[key] < 0:
            raise LineError(f"{key} is not a count: {line[key]!r}")
    if line["failed"] > line["attempted"]:
        raise LineError("more failed than attempted")

    wanted = cell.per_layer if traced else cell.end_to_end
    names = {m["name"]: m["unit"] for m in wanted}
    for name, unit in names.items():
        if name not in line["metrics"]:
            raise LineError(f"the cell's metric {name!r} is missing")
        got = line["metrics"][name]
        if set(got) != {"value", "unit"}:
            raise LineError(f"{name}: keys {sorted(got)}, not value and unit")
        if not _number(got["value"]):
            raise LineError(f"{name}: {got['value']!r} is not a finite number")
        if got["unit"] != unit:
            raise LineError(f"{name}: unit {got['unit']!r}, not {unit!r}")
        # the contract marks a share of a roofline or of the peak by its
        # name (`<kernel>_roofline`, `mfu` as a part), and an entry of
        # BENCHMARK.json may carry no further key to mark it by
        share = unit == "%" and ("roofline" in name or "mfu" in name)
        if share and not 0 < got["value"] <= 100:
            raise LineError(f"{name}: a share of {got['value']}% means the "
                            "operations, the bytes or the time are miscounted")
    unknown = set(line["metrics"]) - set(names)
    if unknown:
        raise LineError(f"metrics this cell does not list: {sorted(unknown)}")

    device = line["device"]
    need = DEVICE_KEYS + (TRACED_DEVICE_KEYS if traced else ())
    for key in need:
        if key not in device:
            raise LineError(f"device lacks {key!r}")
    if set(device) - set(DEVICE_KEYS + TRACED_DEVICE_KEYS):
        raise LineError(f"device has unknown keys: {sorted(device)}")
    if device["count"] != cell.chips:
        raise LineError(f"device count {device['count']}, cell asks for "
                        f"{cell.chips}")
    if not _number(device["memory_peak_bytes"]) \
            or device["memory_peak_bytes"] <= 0:
        raise LineError("memory_peak_bytes is not above 0")
    if traced:
        busy, window = device["busy_s"], device["window_s"]
        if not (_number(busy) and _number(window)):
            raise LineError("busy_s or window_s is not a number")
        if not 0 < busy <= window:
            raise LineError(f"busy_s {busy} is not within (0, window_s "
                            f"{window}]")
        for key, rows in line.get("breakdown", {}).items():
            if key not in ("device_ops", "idle_gaps") or len(rows) > 10 \
                    or not all(len(r) == 2 and isinstance(r[0], str)
                               and _number(r[1]) for r in rows):
                raise LineError(f"breakdown[{key!r}] is malformed")
    for name, pair in line.get("compared", {}).items():
        if set(pair) != {"value", "limit"} or not all(
                _number(v) for v in pair.values()):
            raise LineError(f"compared[{name!r}] is not a value and a limit")
    return json.dumps(line)
