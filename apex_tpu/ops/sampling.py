"""On-device sampling primitives for the serving engine.

The synchronous serve loop's per-step device→host transfer is a
``(B, V)`` logits block that exists only to be sampled on the host —
the transfer (and the host sampling behind it) is what forces the step
loop to block on ``np.asarray(logits)`` before the scheduler may plan
the next iteration.  Fusing sampling into the compiled program shrinks
the transfer to a ``(B,)`` int32 vector and lets JAX async dispatch
run the device ahead of the host (``docs/serving.md``, "Pipelined
serve loop").

Two families live here:

- the GREEDY primitives (:func:`greedy_argmax` / :func:`finite_rows`),
  bit-exact against the host path (pinned by
  ``tests/L0/test_pipeline.py``);
- the STOCHASTIC suite (:class:`SamplingParams` /
  :func:`sample_tokens`), temperature / top-k / top-p sampling with
  **per-request counter-based PRNG keys**, so stochastic traffic keeps
  both fast paths — the pipelined loop AND speculative decoding —
  instead of falling back to the synchronous logits path
  (``docs/serving.md``, "Stochastic sampling").

Determinism contract (the load-bearing property; pinned by
``tests/L0/test_sampling.py``):

The token sampled at sequence position ``i`` of a request is a pure
function of ``(seed, i, logits)``: the PRNG key is derived
counter-style as ``fold_in(fold_in(PRNGKey(seed), i), salt)`` — no
global RNG state, no draw-order dependence — and the draw is realized
as Gumbel-max over the processed (temperature/top-k/top-p-masked)
logits.  Consequences, each one an oracle somewhere in the test/chaos
tier:

- **replay**: re-submitting the same (prompt, params, seed) yields the
  byte-identical completion — the chaos soak's bit-exact-replay
  invariant extends to stochastic traffic unchanged;
- **preemption stability**: a preempted-then-resumed request resamples
  the identical tokens — re-prefill reproduces the K/V (and therefore
  the logits) bit-exactly, and position ``i``'s key does not care how
  many times the request was rescheduled;
- **speculation invariance**: speculative decoding emits the exact
  same stream as plain decode (see :func:`sample_tokens` on the
  Gumbel-max coupling), so drafts and pool pressure never change
  outputs, only throughput.
"""

from __future__ import annotations

import dataclasses
from typing import Optional

import jax
import jax.numpy as jnp

__all__ = ["SamplingParams", "finite_rows", "greedy_argmax",
           "sample_tokens"]

# counter-key salts: position key -> fold_in(salt) separates the
# categorical draw (SALT_SAMPLE) from any future per-position draw
# families; keeping the gumbel draw at salt 0 pins today's streams
SALT_SAMPLE = 0

# the temperature floor substituted on GREEDY rows only, so the
# stochastic lane's division never produces inf/NaN that could slow a
# fused program down with fp exceptions; greedy rows discard the lane
_TEMP_FLOOR = 1e-6


def greedy_argmax(logits):
    """(…, V) logits -> (…,) int32 argmax token ids, on device.

    Semantics are exactly ``np.argmax``'s: the FIRST maximum along the
    axis wins, so the fused program's token choice is bit-identical to
    materializing the logits and sampling on the host
    (``serving.greedy_sample``), ties included.

    Implemented as max → equality → iota-min rather than
    ``jnp.argmax``: XLA:CPU lowers the combined value+index argmax
    reduction to a scalar loop (~5x slower than the three
    vectorizable passes here at serving vocab sizes), and the
    decomposition picks the LOWEST index among maxima by construction
    — the same tie rule.  A row whose max is NaN matches nothing and
    clamps to the last id; such rows are always flagged by
    :func:`finite_rows` and their token is never consumed."""
    v = logits.shape[-1]
    m = jnp.max(logits, axis=-1, keepdims=True)
    iota = jax.lax.broadcasted_iota(jnp.int32, logits.shape,
                                    logits.ndim - 1)
    idx = jnp.min(jnp.where(logits == m, iota, jnp.int32(v)), axis=-1)
    return jnp.minimum(idx, v - 1).astype(jnp.int32)


def finite_rows(logits):
    """(…, V) logits -> (…,) bool: True where every vocab entry of the
    row is finite.  The device half of the serve loop's non-finite
    step guard: rows flagged False are failed (``"nonfinite"``) at
    retire time without their logits ever reaching the host."""
    return jnp.all(jnp.isfinite(logits), axis=-1)


@dataclasses.dataclass(frozen=True)
class SamplingParams:
    """Per-request sampling knobs (``docs/serving.md``, "Stochastic
    sampling").  The default instance is GREEDY — bit-identical to the
    historical argmax path, so ``SamplingParams()`` requests ride the
    exact programs and token streams they always have.

    Args:
      temperature: softmax temperature.  ``0.0`` (the default) means
        greedy argmax — ``top_k``/``top_p`` are then irrelevant (the
        argmax is inside every mask).  Values > 0 sample from
        ``softmax(logits / temperature)`` after masking.
      top_k: keep only the ``top_k`` highest-probability tokens
        (``None`` = no top-k filter).  Ties AT the k-th value are all
        kept — the mask is a value threshold, so the kept set is
        deterministic and shard-layout-independent.
      top_p: nucleus sampling — keep the smallest set of
        highest-probability tokens whose cumulative probability
        reaches ``top_p`` (the boundary-crossing token is INCLUDED,
        and ties at the boundary value are all kept).  ``1.0`` (the
        default) keeps everything.  Applied on the
        temperature-scaled distribution; composes with ``top_k`` as
        an intersection of the two keep sets.
      seed: the per-request PRNG seed.  The full determinism contract
        (module docstring): position ``i``'s token is a pure function
        of ``(seed, i, logits)`` — same seed + same prompt + same
        params = the byte-identical completion, replayed across
        preemption, eviction, OOM-retry, speculation, pipelining, and
        tensor parallelism.  Distinct requests wanting distinct
        streams must carry distinct seeds (the front door does NOT
        fold a request uid into the key: uids are process-local
        counters, and folding them in would break bit-exact replay on
        a fresh process — the chaos soak's core oracle).

    Validation raises a messaged :class:`ValueError` for
    ``temperature < 0``, ``top_k < 1``, or ``top_p`` outside
    ``(0, 1]``.
    """

    temperature: float = 0.0
    top_k: Optional[int] = None
    top_p: float = 1.0
    seed: int = 0

    def __post_init__(self):
        if self.temperature < 0:
            raise ValueError(
                f"temperature must be >= 0 (0 = greedy argmax), got "
                f"{self.temperature}")
        if self.top_k is not None and self.top_k < 1:
            raise ValueError(
                f"top_k must be >= 1 (or None to disable), got "
                f"{self.top_k}")
        if not 0.0 < self.top_p <= 1.0:
            raise ValueError(
                f"top_p must be in (0, 1], got {self.top_p}")

    @property
    def is_greedy(self) -> bool:
        """True when this request takes the bit-exact argmax path
        (``temperature == 0``)."""
        return self.temperature == 0.0

    @property
    def klass(self) -> str:
        """The request's traffic class for ``stats()["sampling"]``
        accounting: ``greedy`` / ``temperature`` / ``top_k`` /
        ``top_p`` / ``top_k_top_p``."""
        if self.is_greedy:
            return "greedy"
        k, p = self.top_k is not None, self.top_p < 1.0
        if k and p:
            return "top_k_top_p"
        if k:
            return "top_k"
        if p:
            return "top_p"
        return "temperature"


def _row_keys(seeds, positions, salt: int):
    """Counter-based per-row PRNG keys: flat ``(N,)`` seeds/positions
    -> ``(N, 2)`` uint32 key data via
    ``fold_in(fold_in(PRNGKey(seed), position), salt)``.  Pure
    counter-mode — no sequential state — which is what makes replay,
    preemption resume, and speculative/plain-path agreement exact."""

    def one(s, p):
        k = jax.random.PRNGKey(s)
        k = jax.random.fold_in(k, p)
        return jax.random.fold_in(k, salt)

    return jax.vmap(one)(seeds, positions)


def sampling_noise(seeds, positions, vocab: int):
    """The per-position Gumbel noise vector: ``(…,)`` seeds/positions
    -> ``(…, vocab)`` float32 Gumbel(0,1) draws keyed counter-style
    (:func:`_row_keys`).  Shared verbatim by the unsharded sampler and
    the vocab-parallel one (``ops.vocab_parallel``): both generate the
    SAME ``(vocab,)`` vector per row — noise is compute, not
    communication — which is what makes sharded-vs-unsharded token
    streams agree."""
    shape = jnp.shape(seeds)
    flat_s = jnp.reshape(seeds, (-1,))
    flat_p = jnp.reshape(positions, (-1,))
    keys = _row_keys(flat_s, flat_p, SALT_SAMPLE)
    g = jax.vmap(
        lambda k: jax.random.gumbel(k, (vocab,), jnp.float32))(keys)
    return jnp.reshape(g, shape + (vocab,))


# the signed 32-bit image of a float32: ``image(a) < image(b)`` exactly
# where ``a < b`` (and -0.0 one below +0.0).  The map is its own inverse.
_MAGNITUDE = 0x7FFFFFFF
_IMAGE_NEG_INF = -0x7F800001        # image(-inf): below every finite value
_IMAGE_ABOVE_INF = 0x7F800001       # image(+inf) + 1: above every value
_TURNS = 32                         # halvings that close the span between them


def _flip(x):
    """int32 bits of a float32 <-> its order-preserving image (sign bit
    kept, the magnitude bits inverted under a set sign)."""
    return x ^ ((x >> 31) & _MAGNITUDE)


def _thresholds(scaled, k, top_p):
    """``(N, V)`` float32 rows, ``(N,)`` int32 ``k`` in ``[1, V]`` and
    ``(N,)`` float32 ``top_p`` -> ``(kth, pth)``, each ``(N, 1)``: the
    largest value ``t`` of the row with ``count(row >= t) >= k``, and
    the largest with ``sum(e[row >= t]) / sum(e) >= top_p``.  Both
    predicates fall monotonically as ``t`` rises (a float32 sum taken
    in one fixed order never shrinks when a non-negative term joins
    it), so each threshold is where its predicate turns, found by
    halving the span of float32 IMAGES between ``-inf`` and ``+inf``:
    32 turns, each one compare and two masked sums over the row.  A
    candidate image is compared as the float it stands for, which
    orders as the images do, so no image of the vocabulary is ever
    written; nothing is sorted, accumulated or gathered."""
    e = jnp.exp(scaled - jnp.max(scaled, axis=-1, keepdims=True))
    total = jnp.sum(e, axis=-1, keepdims=True)
    k, top_p = k[:, None], top_p[:, None]

    def value(image):
        return jax.lax.bitcast_convert_type(_flip(image), jnp.float32)

    def halve(lo, hi, holds):
        # floor((lo + hi) / 2) with no overflow; ``lo`` always holds
        mid = (lo & hi) + ((lo ^ hi) >> 1)
        keep = holds(scaled >= value(mid))
        return jnp.where(keep, mid, lo), jnp.where(keep, hi, mid)

    def turn(_, spans):
        k_lo, k_hi, p_lo, p_hi = spans
        return (
            *halve(k_lo, k_hi, lambda m: jnp.sum(
                m, axis=-1, keepdims=True, dtype=jnp.int32) >= k),
            *halve(p_lo, p_hi, lambda m: jnp.sum(
                jnp.where(m, e, 0.0), axis=-1, keepdims=True)
                / total >= top_p))

    lo = jnp.full(k.shape, _IMAGE_NEG_INF, jnp.int32)
    hi = jnp.full(k.shape, _IMAGE_ABOVE_INF, jnp.int32)
    k_lo, _, p_lo, _ = jax.lax.fori_loop(0, _TURNS, turn,
                                         (lo, hi, lo, hi))
    return value(k_lo), value(p_lo)


def processed_logits(logits, temperature, top_k, top_p):
    """Temperature-scale then top-k/top-p-mask one batch of logits:
    ``(…, V)`` float logits + broadcast-shaped ``(…,)`` params ->
    ``(…, V)`` float32 masked scaled logits (dropped tokens at
    ``-inf``).  The mask is a VALUE threshold — the k-th largest value
    and the nucleus-boundary value, whichever is higher — so ties at
    either boundary are all kept and the kept set is independent of
    any ordering of equal values or of a shard layout.

    Both thresholds are found by SELECTION (:func:`_thresholds`): the
    row is never sorted.  The nucleus boundary is the largest value
    whose inclusive mass (it and everything above it) reaches
    ``top_p``, so the token that crosses ``top_p`` is kept (pinned by
    ``tests/L0/test_sampling.py``).  The kept set equals that of a
    float64 reference (sort, cumulative sum, the same inclusion rule)
    except where the inclusive mass at the boundary lies within
    float32 accumulation error of ``top_p``; there it is the
    reference's set with one more or one fewer distinct value.

    ``top_k <= 0`` disables the top-k filter; ``top_p >= 1`` disables
    the nucleus filter (never "keep only tokens above the underflowed
    tail", which a literal mass threshold would produce when the
    scaled tail rounds to probability zero)."""
    v = logits.shape[-1]
    lg = logits.astype(jnp.float32)
    t = jnp.maximum(temperature, _TEMP_FLOOR)[..., None]
    scaled = lg / t
    rows = scaled.shape[:-1]
    k = jnp.clip(jnp.where(top_k <= 0, v, top_k), 1, v)
    kth, pth = _thresholds(scaled.reshape(-1, v), k.reshape(-1),
                           top_p.reshape(-1))
    kth = jnp.where((top_k <= 0)[..., None], -jnp.inf,
                    kth.reshape(rows + (1,)))
    pth = jnp.where((top_p >= 1.0)[..., None], -jnp.inf,
                    pth.reshape(rows + (1,)))
    return jnp.where(scaled >= jnp.maximum(kth, pth), scaled, -jnp.inf)


def sample_tokens(logits, temperature, top_k, top_p, seeds, positions):
    """The on-device sampling suite: ``(…, V)`` logits + per-row
    params -> ``(ids (…,) int32, finite (…,) bool)``.

    Per row: rows with ``temperature <= 0`` take the bit-exact greedy
    lane (:func:`greedy_argmax` on the RAW logits — byte-identical to
    the historical argmax path, ties included); stochastic rows draw
    one token from ``softmax(processed_logits)`` via **Gumbel-max**:

        ``token = argmax(processed_logits + gumbel(key(seed, pos)))``

    which samples the masked categorical exactly, with the counter key
    of the module docstring's determinism contract.  The mask's two
    thresholds are selected, never sorted for
    (:func:`processed_logits`, which also says how far the kept set is
    a sorted reference's): one path for every row shape and vocabulary,
    with no short-list and no cap on ``top_k`` or on the nucleus (the
    sharded twin's clamp to ``SHARD_CANDIDATES`` is its own).  ``finite`` is
    :func:`finite_rows` on the raw logits for every row — the serve
    loop's non-finite guard is sampling-agnostic.

    Args:
      logits: ``(…, V)`` floating point (``(B, V)`` decode,
        ``(B, K, V)`` verify, ``(1, V)`` prefill).
      temperature / top_k / top_p / seeds: ``(…,)`` per-row parameter
        arrays (:class:`SamplingParams` batched by the scheduler into
        the launch struct; ``top_k = 0`` means disabled).
      positions: ``(…,)`` int32 — the SEQUENCE INDEX of the token
        being sampled (number of tokens preceding it: prompt length
        for the prefill token, ``position + 1`` for a decode step,
        ``start + 1 + column`` for verify rows).  This is the counter
        of the key derivation, and the reason a resumed/replayed/
        speculated request resamples identical tokens.

    Speculation (the Gumbel-max coupling): because the draw at
    position ``i`` is a deterministic function of ``(seed, i,`` the
    processed distribution ``p_i)``, speculative verify simply samples
    EVERY fed column with its own positional key and the host accepts
    a drafted token iff it EQUALS the column's sample.  That realizes
    exactly the textbook rejection-sampling probabilities for a delta
    draft ``q``: accept prob ``P(sample == d) = p_i(d) =
    min(1, p_i(d)/q(d))``, and the emitted token on first rejection is
    the column's own sample — distributed as the normalized residual
    ``p_i(x)/(1 - p_i(d))`` for ``x != d`` — so the output
    distribution is exactly ``p`` (Leviathan et al.'s construction).
    Stronger still: the emitted token at position ``i`` is the SAME
    token whether it arrived via an accepted draft, a rejection
    resample, or a plain decode step — so speculation, draft depth,
    and lookahead pressure change throughput, never bytes
    (``docs/serving.md``, "Stochastic sampling")."""
    greedy = temperature <= 0.0
    masked = processed_logits(logits, temperature, top_k, top_p)
    noise = sampling_noise(seeds, positions, logits.shape[-1])
    ids = jnp.where(greedy, greedy_argmax(logits),
                    greedy_argmax(masked + noise))
    return ids.astype(jnp.int32), finite_rows(logits)


# host-side twin of the fused in-kernel call — the synchronous logits
# path samples materialized logits through the SAME jitted function,
# so pipelined-vs-synchronous stochastic streams agree bit-for-bit
_sample_tokens_jit = jax.jit(sample_tokens)


def sample_tokens_host(logits, temperature, top_k, top_p, seeds,
                       positions):
    """Jit-cached host entry for :func:`sample_tokens` (one compile
    per shape); the synchronous serve loop's stochastic sampler."""
    return _sample_tokens_jit(logits, temperature, top_k, top_p,
                              seeds, positions)
