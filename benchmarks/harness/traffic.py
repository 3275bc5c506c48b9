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
    return prompts, outputs, _greedy(n, mix.get("greedy_share", 1.0))


def _greedy(n, share):
    """``n`` flags, every 1/share-th set: spread evenly, not drawn."""
    return (np.floor((np.arange(n) + 1) * share)
            > np.floor(np.arange(n) * share))


def planned_count(mix, seconds, drain_s):
    """How many requests a run plans.  Open loop: what ``rate_per_s``
    sends through the fill, the window and the longest drain; closed
    loop: the mix's own ``planned_requests``, more than its clients can
    send in a run (no rate says how many that is)."""
    if mix["loop"] == "open":
        return int(np.ceil(mix["rate_per_s"]
                           * (mix["fill_s"] + seconds + drain_s)))
    return int(mix["planned_requests"])


def _due(mix, seed, n):
    """When each of ``n`` requests is due.  Open loop: Poisson arrivals
    at ``rate_per_s``, the exponential gaps between them dealt out by
    the seed; closed loop: 0, and the runner sends a client's next
    request when its last ended."""
    if mix["loop"] != "open":
        return np.zeros(n)
    gaps = np.random.default_rng(mix["shape_seed"] + 1).exponential(
        1.0 / mix["rate_per_s"], n)
    return np.cumsum(gaps[_deal(np.random.default_rng([int(seed), 5]),
                                n, mix["stratum"])])


def plan_requests(mix, seed, n, vocab):
    """The first ``n`` requests of a run, ``due`` as ``_due`` gives it.
    A mix that states ``"shared"`` asks several requests of one
    document (``_plan_shared``); any other plans requests that share
    nothing."""
    if "shared" in mix:
        return _plan_shared(mix, seed, n, vocab)
    prompts, outputs, greedy = _shapes(mix, n)
    rng = np.random.default_rng([int(seed), 2])
    order = _deal(rng, n, mix["stratum"])
    due = _due(mix, seed, n)
    plan = []
    for i in range(n):
        j = order[i]
        plan.append(Planned(
            index=i, due=float(due[i]),
            prompt=rng.integers(0, vocab, int(prompts[j])).tolist(),
            max_new=int(outputs[j]), greedy=bool(greedy[j]),
            sample_seed=int(rng.integers(0, 2 ** 31 - 1))))
    return plan


def _plan_shared(mix, seed, n, vocab):
    """Requests that share a prefix.  ``mix["shared"]`` states how many
    requests are asked of one document (``asks``, a length spec), how
    long each ask's own suffix is (``suffix``), and how many places
    apart in the plan the asks of one document lie (``apart``);
    ``prompt`` is then the document's length, and an ask's prompt is
    the document's ids followed by its own suffix.

    The set of documents (length, asks, each ask's suffix and output
    length) is the mix's own, from ``shape_seed``.  The seed deals whole
    documents within strata of ``stratum`` documents, and draws the
    ids.  Every ``apart`` consecutive documents are then asked in turn,
    one ask each, until none has an ask left; ``stratum`` is a multiple
    of ``apart``, so a stratum's asks fill the same places of the plan
    whatever the seed."""
    sh = mix["shared"]
    apart, stratum = int(sh["apart"]), int(mix["stratum"])
    if apart < 1 or stratum % apart:
        raise ValueError(f"stratum {stratum} is not a multiple of "
                         f"shared.apart {apart}")
    shape = np.random.default_rng(mix["shape_seed"])
    docs = _lengths(shape, n, mix["prompt"])     # n documents are enough
    asks = np.maximum(_lengths(shape, n, sh["asks"]), 1)
    first = np.concatenate([[0], np.cumsum(asks)])
    suffixes = _lengths(shape, int(first[-1]), sh["suffix"])
    outputs = _lengths(shape, int(first[-1]), mix["output"])

    rng = np.random.default_rng([int(seed), 2])
    order = _deal(rng, n, stratum)
    places = []                                   # (document, its k-th ask)
    for a in range(0, n, apart):
        group = order[a:a + apart]
        for k in range(int(asks[group].max())):
            places += [(d, k) for d in group if k < asks[d]]
        if len(places) >= n:
            break
    greedy = _greedy(int(first[-1]), mix.get("greedy_share", 1.0))
    due = _due(mix, seed, n)
    ids, plan = {}, []
    for i, (d, k) in enumerate(places[:n]):
        if d not in ids:
            ids[d] = rng.integers(0, vocab, int(docs[d])).tolist()
        ask = int(first[d] + k)         # its number in the mix's shape
        suffix = int(suffixes[ask])
        plan.append(Planned(
            index=i, due=float(due[i]),
            prompt=ids[d] + rng.integers(0, vocab, suffix).tolist(),
            max_new=int(min(outputs[ask],
                            mix["max_total"] - docs[d] - suffix)),
            greedy=bool(greedy[ask]),
            sample_seed=int(rng.integers(0, 2 ** 31 - 1))))
    return plan
