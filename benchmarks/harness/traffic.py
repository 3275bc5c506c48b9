"""One generator of traffic, driven by a mix's file of parameters.

Steadiness: two seeds must give the same work, or a run's numbers
follow the seed and not the code.  So the *set* of a mix's shapes (how
long each request is, how long after the last one it arrives) is drawn
from the mix's own ``shape_seed``; the run's ``--seed`` deals them out
in another order, lengths and arrival gaps each by a deal of their own,
and draws the token ids and the sampling streams.  A deal moves nothing
out of its stratum of ``stratum`` consecutive requests, so every
stretch of the run carries the same load whatever the seed, and no two
seeds replay one schedule.
"""

import dataclasses

import numpy as np


def _lognormal(rng, n, median, sigma, lo, hi):
    x = np.exp(rng.normal(np.log(median), sigma, n))
    return np.clip(np.rint(x), lo, hi).astype(np.int64)


def _lengths(rng, n, spec):
    if spec["dist"] == "lognormal":
        return _lognormal(rng, n, spec["median"], spec["sigma"],
                          spec["min"], spec["max"])
    if spec["dist"] == "uniform":
        return rng.integers(spec["min"], spec["max"] + 1, n)
    raise ValueError(f"unknown length distribution {spec['dist']!r}")


def _deal(rng, n, stratum):
    """A permutation of ``range(n)`` that moves nothing out of its
    stratum of ``stratum`` consecutive places."""
    order = np.arange(n)
    for a in range(0, n, stratum):
        rng.shuffle(order[a:a + stratum])
    return order


# -- training: packed documents -------------------------------------------

def packed_batches(mix, seed, batch, seq, vocab):
    """Endless (batch, seq) int32 batches: documents of log-normal
    length, ids uniform from the seed, joined by the separator and cut
    into rows of ``seq``, as GPT-2's own data was packed.  Every row
    differs, and every row is full, so every step is the same work."""
    rng = np.random.default_rng([int(seed), 1])
    d = mix["documents"]
    sep = mix["separator_id"]
    while True:
        rows = rng.integers(0, vocab - 1, (batch, seq), dtype=np.int32)
        rows[rows >= sep] += 1              # the separator is not drawn
        flat = rows.reshape(-1)
        ends = np.cumsum(_lognormal(rng, 2 * flat.size // d["median"] + 8,
                                    d["median"], d["sigma"],
                                    d["min"], d["max"]) + 1) - 1
        flat[ends[ends < flat.size]] = sep
        yield rows


# -- serving ------------------------------------------------------------------

@dataclasses.dataclass
class Planned:
    """One request as the generator plans it."""
    index: int
    due: float              # seconds from the generator's start (open loop)
    prompt: list
    max_new: int
    greedy: bool
    sample_seed: int


def _shapes(mix, n):
    """``n`` prompt lengths, output lengths and greedy flags: the mix's
    fixed shape."""
    rng = np.random.default_rng(mix["shape_seed"])
    prompts = _lengths(rng, n, mix["prompt"])
    outputs = _lengths(rng, n, mix["output"])
    outputs = np.minimum(outputs, mix["max_total"] - prompts)
    share = mix.get("greedy_share", 1.0)
    # every 1/share-th request is greedy: spread evenly, not drawn
    greedy = (np.floor((np.arange(n) + 1) * share)
              > np.floor(np.arange(n) * share))
    return prompts, outputs, greedy


def planned_count(mix, seconds, drain_s):
    """How many requests a run plans.  Open loop: what ``rate_per_s``
    sends through the fill, the window and the longest drain; closed
    loop: the mix's own ``planned_requests``, more than its clients can
    send in a run (no rate says how many that is)."""
    if mix["loop"] == "open":
        return int(np.ceil(mix["rate_per_s"]
                           * (mix["fill_s"] + seconds + drain_s)))
    return int(mix["planned_requests"])


def plan_requests(mix, seed, n, vocab):
    """The first ``n`` requests of a run.  Open loop: Poisson arrivals
    at ``rate_per_s``, the exponential gaps between them dealt out by
    the seed; closed loop: ``due`` is 0 and the runner sends a client's
    next request when its last ended."""
    prompts, outputs, greedy = _shapes(mix, n)
    rng = np.random.default_rng([int(seed), 2])
    order = _deal(rng, n, mix["stratum"])
    if mix["loop"] == "open":
        gaps = np.random.default_rng(mix["shape_seed"] + 1).exponential(
            1.0 / mix["rate_per_s"], n)
        due = np.cumsum(gaps[_deal(np.random.default_rng([int(seed), 5]),
                                   n, mix["stratum"])])
    else:
        due = np.zeros(n)
    plan = []
    for i in range(n):
        j = order[i]
        plan.append(Planned(
            index=i, due=float(due[i]),
            prompt=rng.integers(0, vocab, int(prompts[j])).tolist(),
            max_new=int(outputs[j]), greedy=bool(greedy[j]),
            sample_seed=int(rng.integers(0, 2 ** 31 - 1))))
    return plan
