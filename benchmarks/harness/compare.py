"""What decides ``correct``: the timed path against the plain reference.

Every number compared is printed beside its limit.  The limits live in
the configuration's file (``limits``, by the runner's kind or by a
traffic mix's name: ``limits_for``), each set from two readings taken
on the chip: the largest that sound runs of the program gave, and the
smallest that the control (the reference in the next lower precision)
or a planted fault gave.  ``PERF.md`` records both for each.
"""

import math

import numpy as np


def limits_for(cell, kind):
    """The limits a cell's numbers are held to: those its configuration
    states for the cell's traffic mix by its name, where the readings at
    that mix's sizes called for limits of its own, else those of the
    runner's ``kind`` (``"train"``, ``"serve"``)."""
    limits = cell.config["limits"]
    return limits.get(cell.traffic_name, limits[kind])


# stands for "no number at all" in a line that may hold no NaN
NO_NUMBER = 1e30


def _finite(x):
    x = float(x)
    return x if math.isfinite(x) else NO_NUMBER


def leaf_gaps(got, want):
    """Per leaf, the gap between two norms: the distance between the
    program's norm and the reference's, measured against the
    reference's norm of that leaf or of the median leaf, whichever is
    larger (some gradients are all but zero)."""
    paths = sorted(want)
    ref = np.asarray([float(want[p]) for p in paths])
    mine = np.asarray([float(got[p]) for p in paths])
    floor = float(np.median(ref))
    return dict(zip(paths, np.abs(mine - ref) / np.maximum(ref, floor)))


def norm_gaps(got, want, moved=None):
    """The worst leaf's gap and the median leaf's, over the leaves in
    ``moved`` (all if None): ``(worst, its path, median)``."""
    if set(got) != set(want):
        return (NO_NUMBER, f"leaves differ: "
                f"{sorted(set(got) ^ set(want))[:3]}", NO_NUMBER)
    gaps = {p: g for p, g in leaf_gaps(got, want).items()
            if moved is None or p in moved}
    worst = max(gaps, key=gaps.get)
    return (_finite(gaps[worst]), worst,
            _finite(np.median(list(gaps.values()))))


def moving_leaves(ref_grad_norms, share=1e-3):
    """Leaves whose reference gradient is not nought to rounding: under
    Adam the others move by round-off alone (a key's bias under
    softmax), so their change is no measure of anything."""
    floor = share * float(np.median(list(ref_grad_norms.values())))
    return {p for p, g in ref_grad_norms.items() if float(g) >= floor}


def compare_training(program, reference, limits):
    """``program`` and ``reference``: ``{"losses": [...], "grad_norms":
    {path: norm}, "delta_norms": {path: norm}}``.  Returns ``{name:
    {"value", "limit"}}`` and notes for the log."""
    out, notes = {}, {}
    for i, (a, b) in enumerate(zip(program["losses"], reference["losses"]),
                               start=1):
        out[f"loss{i}_gap"] = abs(float(a) - float(b)) / abs(float(b))
    (out["grad_norm_gap"], notes["grad_norm_gap"],
     out["grad_norm_gap_median"]) = norm_gaps(
        program["grad_norms"], reference["grad_norms"])
    moved = moving_leaves(reference["grad_norms"])
    (out["delta_norm_gap"], notes["delta_norm_gap"],
     out["delta_norm_gap_median"]) = norm_gaps(
        program["delta_norms"], reference["delta_norms"], moved)
    notes["leaves_left_out_of_delta"] = len(reference["grad_norms"]) \
        - len(moved)
    notes["not_compared"] = {k: v for k, v in out.items()
                             if k not in limits}
    return _against(out, limits), notes


def compare_served(gaps, limits, mass_above=(), top_p=None):
    """``gaps``: for every served token of the greedy sample, how far
    its logit lies below the reference's best at that position.
    ``mass_above``: for every served token of the sampled sample, the
    mass the reference ranks above it; a token drawn from the nucleus
    has less than ``top_p`` there, so the number is the widest excess."""
    gaps = np.asarray(gaps, np.float64)
    mass = np.asarray(mass_above, np.float64)
    out = {"served_gap_max": float(np.max(gaps)) if gaps.size else NO_NUMBER}
    if mass.size and top_p is not None:
        out["sampled_over_top_p"] = float(np.max(mass)) - top_p
    notes = {"tokens_compared": int(gaps.size),
             "served_gap_mean": float(np.mean(gaps)) if gaps.size else None,
             "sampled_tokens_read": int(mass.size),
             "not_compared": {k: v for k, v in out.items()
                              if k not in limits}}
    return _against(out, limits), notes


def _against(values, limits):
    """Pair each number with its limit.  A number the configuration
    sets no limit for is not compared (``PERF.md`` says which and why)
    and goes to the notes."""
    return {name: {"value": _finite(v), "limit": float(limits[name])}
            for name, v in values.items() if name in limits}


def verdict(compared):
    return bool(compared) and all(c["value"] <= c["limit"]
                                  for c in compared.values())


def report(compared, notes, stream):
    """Each number beside its limit, as the last lines on stderr."""
    for name, c in compared.items():
        mark = "ok" if c["value"] <= c["limit"] else "OVER"
        print(f"compared {name} = {c['value']:.6g} (limit {c['limit']:g}) "
              f"{mark}", file=stream)
    print(f"compared notes {notes}", file=stream, flush=True)
