"""Seeded chaos composition — many faults at once, deterministically.

Single-fault tests (``tests/L0/test_serving_faults.py``,
``test_resilience.py``) prove each containment mechanism in
isolation; what they cannot prove is that the mechanisms *compose* —
that a non-finite logits step during an OOM burst while the queue is
overflowing with mixed-priority traffic still leaves every invariant
intact.  This module is the composition harness:

- :class:`ChaosConfig` — rates and ranges for every fault axis;
- :class:`ChaosSchedule` — the config expanded, via one seeded
  ``random.Random``, into a concrete per-iteration plan: bursty
  arrivals with random priorities/deadlines/shared prefixes, the
  iterations whose decode row gets poisoned non-finite, the
  iterations whose engine calls raise :class:`MemoryError`, and a
  list of :class:`FaultPlan` crash plans (the existing training
  fault vocabulary, composed in as ``InjectedCrash`` raised between
  serve iterations).  The same ``(config, seed)`` always expands to
  the same schedule — a chaos failure replays exactly;
- :class:`ChaosEngine` — a duck-typed wrapper around
  ``serving.DecodeEngine`` that injects the schedule's engine faults
  (everything else delegates to the wrapped engine);
- :func:`run_soak` — drives a full ``InferenceServer`` against the
  schedule for thousands of iterations, asserting the global
  invariants EVERY step (allocator/prefix-cache audits, terminal
  uniqueness) and at the end (bit-exact healthy outputs vs an
  unfaulted replay, counter reconciliation).  ``tools/chaos_soak.py``
  is its CLI; the ``chaos`` build-matrix axis runs it at 2000
  iterations.  The replay oracle is whatever ``make_replay`` builds —
  the ``--kv-quant`` soak variant builds a QUANT-ON replica
  (``docs/serving.md``, "Quantized KV cache"), so bit-exact replay
  continues to hold on the int8 pool: both computations live on the
  same quantized grid, and the invariant then proves quantized
  blocks+scales survive every composed fault path bit-consistently.

This module never imports the :mod:`apex_tpu.serving` *stack* at
module scope (``serving.api`` imports :mod:`resilience.breaker`; a
top-level import back would cycle) — the server is passed in via
factories.  The one exception is :mod:`apex_tpu.serving.reasons`,
the finish-reason constants module, which by contract imports
NOTHING and is therefore cycle-safe even while either package is
mid-init (``tests/L0/test_reasons.py`` pins both import directions).
"""

from __future__ import annotations

import dataclasses
import os
import random
from typing import Callable, Dict, List, Optional, Set, Tuple

from apex_tpu.resilience.faults import FaultPlan, InjectedCrash
from apex_tpu.serving.reasons import (
    CANCELLED,
    HEALTHY_REASONS,
    ROUTER_TERMINAL_REASONS,
    TERMINAL_REASONS,
)

__all__ = ["Arrival", "ChaosConfig", "ChaosEngine", "ChaosSchedule",
           "ChaosTransport", "ReplicaKillSwitch",
           "ROUTER_TERMINAL_REASONS", "TERMINAL_REASONS",
           "run_elastic_soak", "run_router_soak", "run_soak"]


@dataclasses.dataclass(frozen=True)
class Arrival:
    """One scheduled request: submitted at iteration ``iter``.

    ``sampling`` is the stochastic-traffic class's parameter tuple
    ``(temperature, top_k_or_None, top_p, seed)`` (None = greedy, the
    historical default) — kept as a plain tuple so the schedule stays
    import-light; :func:`_sampling_params` inflates it to a
    ``SamplingParams`` at submit time."""

    iter: int
    prompt: Tuple[int, ...]
    max_new_tokens: int
    priority: int
    deadline_iters: Optional[int]
    deadline_s: Optional[float]
    sampling: Optional[Tuple] = None


def _sampling_params(sampling: Optional[Tuple]):
    """Inflate an :class:`Arrival`'s sampling tuple (lazy import: this
    module must not pull the serving/ops stack at module scope)."""
    if sampling is None:
        return None
    from apex_tpu.ops.sampling import SamplingParams

    t, k, p, s = sampling
    return SamplingParams(temperature=t, top_k=k, top_p=p, seed=s)


@dataclasses.dataclass(frozen=True)
class ChaosConfig:
    """Rates and ranges for every chaos axis.  All probabilities are
    per serve iteration; all ranges are inclusive."""

    iters: int = 2000
    vocab: int = 61

    # traffic: a Bernoulli arrival per iteration, occasionally a burst
    # (the thundering-herd shape that overflows bounded queues), with
    # some prompts sharing a prefix so the prefix cache/COW paths run
    arrival_rate: float = 0.3
    burst_rate: float = 0.06
    burst_size: Tuple[int, int] = (3, 8)
    prompt_len: Tuple[int, int] = (2, 20)
    max_new: Tuple[int, int] = (1, 16)
    shared_prefix_rate: float = 0.3
    shared_prefix_len: int = 8

    # speculation traffic class (docs/serving.md): some prompts are a
    # short pattern repeated to length, so n-gram/prompt-lookup drafts
    # actually fire and accept — exercising verify, greedy acceptance,
    # and lookahead KV rollback under every composed fault.  The
    # default 0.0 keeps legacy (config, seed) schedules byte-identical
    # (no extra RNG draws).
    repetitive_rate: float = 0.0
    repetitive_period: Tuple[int, int] = (1, 4)

    # stochastic-sampling traffic class (docs/serving.md, "Stochastic
    # sampling"): this fraction of arrivals carries per-request
    # temperature/top-k/top-p params with a seeded per-request PRNG
    # seed — so stochastic requests soak the sampled-stochastic
    # programs, the rejection-sampling acceptance path, and the
    # counter-key determinism (the bit-exact-replay oracle holds
    # UNCHANGED: the Gumbel-max coupling makes the stream a pure
    # function of (prompt, params, seed)).  The default 0.0 keeps
    # legacy (config, seed) schedules byte-identical (no extra RNG
    # draws).
    stochastic_rate: float = 0.0
    stochastic_temperature: Tuple[float, float] = (0.3, 1.2)
    stochastic_top_k: Tuple = (None, None, 8, 2)
    stochastic_top_p: Tuple = (1.0, 0.95, 0.8)

    # request shape: priority classes (0 = foreground .. lowest) and
    # random deadlines (iteration budget; wall budget on the soak's
    # deterministic iteration clock)
    priority_max: int = 2
    deadline_iters_rate: float = 0.1
    deadline_iters: Tuple[int, int] = (5, 80)
    deadline_s_rate: float = 0.05
    deadline_s: Tuple[float, float] = (5.0, 80.0)

    # faults
    nonfinite_rate: float = 0.02     # poison one decode row
    oom_rate: float = 0.01          # start an engine MemoryError burst
    oom_burst: Tuple[int, int] = (1, 3)
    crash_every: int = 500          # one FaultPlan InjectedCrash per
    #                                 ~N iterations (0 = off)

    # hand-off fault class (docs/serving.md, "Disaggregated
    # prefill/decode"; the --disagg soak arms it): a DELAYED transfer
    # raises before any block moves (the hand-off stays queued and
    # retries), a TORN transfer copies only a prefix of the pairs
    # before raising — the retry re-copies the WHOLE table, so a torn
    # hand-off must be indistinguishable from a delayed one in the
    # output.  Defaults 0.0 keep legacy (config, seed) schedules
    # byte-identical (no extra RNG draws).
    handoff_oom_rate: float = 0.0
    handoff_torn_rate: float = 0.0

    # client-disconnect fault class (docs/serving.md, "Streaming &
    # cancellation"; the --streaming soak arms it): on each scheduled
    # iteration one live streamed request's consumer "hangs up" —
    # its stream closes and the server cancels it mid-whatever it was
    # doing (mid-prefill-chunk, mid-speculation-window, mid-pipelined
    # launch), which must free its blocks/holds with audit() clean
    # and leave its delivered tokens a bit-exact prefix of the
    # replay.  Default 0.0 keeps legacy (config, seed) schedules
    # byte-identical (no extra RNG draws).
    disconnect_rate: float = 0.0

    # session-continuation traffic class (docs/serving.md,
    # "Hierarchical KV offload"; the --kv-offload soak arms it): a
    # prior arrival's prompt is resubmitted after a gap of at least
    # ``resume_min_gap`` iterations — the returning-session shape
    # whose prefix the offload tiers exist to keep warm (same prompt,
    # same sampling tuple, fresh token budget/priority).  Default 0.0
    # keeps legacy (config, seed) schedules byte-identical (no extra
    # RNG draws) — precedent: stochastic_rate, disconnect_rate.
    resume_rate: float = 0.0
    resume_min_gap: int = 20

    # hierarchical-offload fault classes (docs/serving.md,
    # "Hierarchical KV offload"; the --kv-offload soak arms them): a
    # TORN SPILL corrupts a demoted payload after its crc was
    # recorded (import must reject it whole -> cold prefill,
    # bit-identical), and PROMOTE-AT-CAPACITY makes import_blocks
    # raise a transient MemoryError (the payload goes back to the
    # store; the admission cold-prefills).  Neither is engine-OOM
    # accounted — offload failures degrade to slow, never to the
    # serve loop's fault isolation.  Defaults 0.0 keep legacy
    # (config, seed) schedules byte-identical.
    offload_torn_rate: float = 0.0
    offload_capacity_rate: float = 0.0

    # transport fault classes (docs/serving.md, "KV transport"; the
    # --transport-faults soak arms them) — the network-grade fault
    # model on the KV transport envelope.  RESET drops the connection
    # before delivery (first attempt only; the retry lands), RESET
    # AFTER drops it after the handler ran but before the ack (the
    # retry must dedup against the ledger — exactly-once's hard
    # case), STALL blows the per-transfer deadline
    # (deadline_exceeded, not retried), DUP delivers the same
    # transfer id twice (the second must answer from the ledger), and
    # CORRUPT flips one byte of one leaf in flight (the checksummed
    # import must reject it whole).  Defaults 0.0 keep legacy
    # (config, seed) schedules byte-identical (no extra RNG draws).
    transport_reset_rate: float = 0.0
    transport_reset_after_rate: float = 0.0
    transport_stall_rate: float = 0.0
    transport_dup_rate: float = 0.0
    transport_corrupt_rate: float = 0.0

    # flash-crowd arrival class (``serving/elastic``; the --elastic
    # soak and bench arm arm it): for ``flash_crowd_len`` iterations
    # starting at ``flash_crowd_iter``, EVERY iteration adds
    # ``randint(*flash_crowd_arrivals)`` extra arrivals on top of the
    # Bernoulli/burst baseline — the sustained thundering herd an
    # autoscaler exists for, as opposed to ``burst_rate``'s one-shot
    # spikes.  ``None`` (the default) draws no RNG, so legacy
    # (config, seed) schedules stay byte-identical.
    flash_crowd_iter: Optional[int] = None
    flash_crowd_len: int = 0
    flash_crowd_arrivals: Tuple[int, int] = (2, 4)

    # forced invariant violation (the postmortem build-matrix axis,
    # docs/observability.md): at the first iteration >= this with a
    # finished request, the soak deliberately corrupts the terminal
    # bookkeeping (re-appends an already-finished request) so the
    # finished-twice invariant MUST trip — proving the violation
    # detector and the postmortem auto-dump end-to-end.  None (the
    # default) draws no RNG, so legacy (config, seed) schedules stay
    # byte-identical.
    force_violation_iter: Optional[int] = None

    def __post_init__(self):
        if self.iters < 1:
            raise ValueError(f"iters must be >= 1, got {self.iters}")
        if self.prompt_len[0] < 1:
            raise ValueError("prompt_len must start >= 1")


class ChaosSchedule:
    """A :class:`ChaosConfig` expanded into concrete per-iteration
    events by one seeded RNG — build with :meth:`generate`."""

    def __init__(self, cfg: ChaosConfig, seed: int,
                 arrivals: Dict[int, List[Arrival]],
                 nonfinite_iters: Set[int],
                 oom_iters: Set[int],
                 fault_plans: List[FaultPlan],
                 handoff_oom_iters: Optional[Set[int]] = None,
                 handoff_torn_iters: Optional[Set[int]] = None,
                 disconnect_iters: Optional[Set[int]] = None,
                 offload_torn_iters: Optional[Set[int]] = None,
                 offload_capacity_iters: Optional[Set[int]] = None,
                 transport_reset_iters: Optional[Set[int]] = None,
                 transport_reset_after_iters: Optional[Set[int]] = None,
                 transport_stall_iters: Optional[Set[int]] = None,
                 transport_dup_iters: Optional[Set[int]] = None,
                 transport_corrupt_iters: Optional[Set[int]] = None):
        self.cfg = cfg
        self.seed = seed
        self.arrivals = arrivals
        self.nonfinite_iters = nonfinite_iters
        self.oom_iters = oom_iters
        self.fault_plans = fault_plans
        self.handoff_oom_iters = handoff_oom_iters or set()
        self.handoff_torn_iters = handoff_torn_iters or set()
        self.disconnect_iters = disconnect_iters or set()
        self.offload_torn_iters = offload_torn_iters or set()
        self.offload_capacity_iters = offload_capacity_iters or set()
        self.transport_reset_iters = transport_reset_iters or set()
        self.transport_reset_after_iters = \
            transport_reset_after_iters or set()
        self.transport_stall_iters = transport_stall_iters or set()
        self.transport_dup_iters = transport_dup_iters or set()
        self.transport_corrupt_iters = transport_corrupt_iters or set()

    @property
    def num_arrivals(self) -> int:
        return sum(len(v) for v in self.arrivals.values())

    @classmethod
    def generate(cls, cfg: ChaosConfig, seed: int) -> "ChaosSchedule":
        rng = random.Random(seed)
        shared = [rng.randrange(cfg.vocab)
                  for _ in range(cfg.shared_prefix_len)]

        def one_arrival(i: int) -> Arrival:
            n = rng.randint(*cfg.prompt_len)
            if cfg.repetitive_rate \
                    and rng.random() < cfg.repetitive_rate:
                # speculation-friendly: a short pattern repeated to
                # length, the shape prompt-lookup drafts predict well
                period = rng.randint(*cfg.repetitive_period)
                pat = [rng.randrange(cfg.vocab) for _ in range(period)]
                prompt = (pat * (n // period + 1))[:n]
            else:
                prompt = [rng.randrange(cfg.vocab) for _ in range(n)]
            if rng.random() < cfg.shared_prefix_rate:
                prompt = shared + prompt
            d_it = (rng.randint(*cfg.deadline_iters)
                    if rng.random() < cfg.deadline_iters_rate else None)
            d_s = (rng.uniform(*cfg.deadline_s)
                   if rng.random() < cfg.deadline_s_rate else None)
            sampling = None
            if cfg.stochastic_rate \
                    and rng.random() < cfg.stochastic_rate:
                # per-request temperature/top-k/top-p mix, seeded: the
                # stream stays a pure function of (prompt, params,
                # seed), so the replay oracle holds bit-exactly
                sampling = (
                    round(rng.uniform(*cfg.stochastic_temperature), 3),
                    rng.choice(cfg.stochastic_top_k),
                    rng.choice(cfg.stochastic_top_p),
                    rng.randrange(1 << 31))
            return Arrival(iter=i, prompt=tuple(prompt),
                           max_new_tokens=rng.randint(*cfg.max_new),
                           priority=rng.randint(0, cfg.priority_max),
                           deadline_iters=d_it, deadline_s=d_s,
                           sampling=sampling)

        arrivals: Dict[int, List[Arrival]] = {}
        nonfinite: Set[int] = set()
        oom: Set[int] = set()
        handoff_oom: Set[int] = set()
        handoff_torn: Set[int] = set()
        disconnect: Set[int] = set()
        offload_torn: Set[int] = set()
        offload_capacity: Set[int] = set()
        transport_reset: Set[int] = set()
        transport_reset_after: Set[int] = set()
        transport_stall: Set[int] = set()
        transport_dup: Set[int] = set()
        transport_corrupt: Set[int] = set()
        prior: List[Arrival] = []
        for i in range(cfg.iters):
            batch: List[Arrival] = []
            if rng.random() < cfg.arrival_rate:
                batch.append(one_arrival(i))
            if rng.random() < cfg.burst_rate:
                batch.extend(one_arrival(i)
                             for _ in range(rng.randint(*cfg.burst_size)))
            # rate-None guard first: legacy schedules draw nothing
            if cfg.flash_crowd_iter is not None \
                    and cfg.flash_crowd_iter <= i \
                    < cfg.flash_crowd_iter + cfg.flash_crowd_len:
                batch.extend(
                    one_arrival(i) for _ in
                    range(rng.randint(*cfg.flash_crowd_arrivals)))
            # rate-0 guard: legacy schedules draw nothing.  A resumed
            # SESSION replays an earlier arrival's exact prompt (and
            # sampling tuple — same seeded stream) after a cool-down
            # gap, so its prefix has had time to evict and demote; a
            # fresh token budget/priority makes it a new request, not
            # a duplicate.
            if cfg.resume_rate and rng.random() < cfg.resume_rate:
                pool = [a for a in prior
                        if a.iter <= i - cfg.resume_min_gap]
                if pool:
                    src = pool[rng.randrange(len(pool))]
                    batch.append(dataclasses.replace(
                        src, iter=i,
                        max_new_tokens=rng.randint(*cfg.max_new),
                        priority=rng.randint(0, cfg.priority_max)))
            if batch:
                arrivals[i] = batch
                prior.extend(batch)
            if rng.random() < cfg.nonfinite_rate:
                nonfinite.add(i)
            if rng.random() < cfg.oom_rate:
                # clamp to the schedule: a burst reaching past the
                # last iteration would leave drain() retrying a
                # permanently-OOM engine forever
                oom.update(x for x in
                           range(i, i + rng.randint(*cfg.oom_burst))
                           if x < cfg.iters)
            # rate-0 guards: legacy (config, seed) schedules draw
            # nothing extra and stay byte-identical
            if cfg.handoff_oom_rate \
                    and rng.random() < cfg.handoff_oom_rate:
                handoff_oom.add(i)
            if cfg.handoff_torn_rate \
                    and rng.random() < cfg.handoff_torn_rate:
                handoff_torn.add(i)
            if cfg.disconnect_rate \
                    and rng.random() < cfg.disconnect_rate:
                disconnect.add(i)
            if cfg.offload_torn_rate \
                    and rng.random() < cfg.offload_torn_rate:
                offload_torn.add(i)
            if cfg.offload_capacity_rate \
                    and rng.random() < cfg.offload_capacity_rate:
                offload_capacity.add(i)
            if cfg.transport_reset_rate \
                    and rng.random() < cfg.transport_reset_rate:
                transport_reset.add(i)
            if cfg.transport_reset_after_rate \
                    and rng.random() < cfg.transport_reset_after_rate:
                transport_reset_after.add(i)
            if cfg.transport_stall_rate \
                    and rng.random() < cfg.transport_stall_rate:
                transport_stall.add(i)
            if cfg.transport_dup_rate \
                    and rng.random() < cfg.transport_dup_rate:
                transport_dup.add(i)
            if cfg.transport_corrupt_rate \
                    and rng.random() < cfg.transport_corrupt_rate:
                transport_corrupt.add(i)
        # compose the EXISTING fault vocabulary: one FaultPlan per
        # scheduled crash, ticked by iteration number (crash_kind
        # "raise" — SIGKILL would end the soak process, which the
        # crash_resume build-matrix axis already covers)
        plans: List[FaultPlan] = []
        if cfg.crash_every:
            step = cfg.crash_every
            for base in range(step, cfg.iters, step):
                plans.append(FaultPlan(
                    crash_step=base + rng.randint(0, step // 4),
                    crash_kind="raise"))
        return cls(cfg, seed, arrivals, nonfinite, oom, plans,
                   handoff_oom_iters=handoff_oom,
                   handoff_torn_iters=handoff_torn,
                   disconnect_iters=disconnect,
                   offload_torn_iters=offload_torn,
                   offload_capacity_iters=offload_capacity,
                   transport_reset_iters=transport_reset,
                   transport_reset_after_iters=transport_reset_after,
                   transport_stall_iters=transport_stall,
                   transport_dup_iters=transport_dup,
                   transport_corrupt_iters=transport_corrupt)


class ChaosEngine:
    """Duck-typed ``DecodeEngine`` wrapper injecting schedule faults.

    Installed post-construction (``server.engine = ChaosEngine(...)``)
    so the real engine, allocator, and cache stay exactly as the
    server built them.  Per :meth:`begin_iter`:

    - a scheduled :class:`FaultPlan` crash raises
      :class:`InjectedCrash` (the soak catches it around ``step()``
      and carries on — no scheduler state has moved);
    - an OOM iteration makes every engine call raise
      :class:`MemoryError` (the serve loop's isolation skips and
      retries bit-identically);
    - a non-finite iteration overwrites one random decode row with
      NaN after the real computation — the KV writes are real, only
      the returned logits are poisoned, exactly the failure mode of
      a numerically-diverged model.
    """

    def __init__(self, inner, schedule: ChaosSchedule, *,
                 rng_salt: int = 0x5EED, injected=None,
                 tick_plans: bool = True):
        self.inner = inner
        self.schedule = schedule
        # runtime draws (victim rows) come from a separate stream so
        # schedule generation and injection stay independent.  A
        # second wrapper (the disaggregated PREFILL pool's engine)
        # salts its own stream and SHARES the injected tallies, so
        # fault accounting reconciles server-wide while neither
        # wrapper perturbs the other's draw sequence.
        self.rng = random.Random(schedule.seed ^ rng_salt)
        self.iter = -1
        self.injected = injected if injected is not None else {
            "oom": 0, "nonfinite_rows": 0, "crashes": 0,
            "handoff_oom": 0, "handoff_torn": 0,
            "offload_torn": 0, "offload_capacity": 0,
            "transport_reset": 0, "transport_reset_after": 0,
            "transport_stall": 0, "transport_dup": 0,
            "transport_corrupt": 0}
        self._tick_plans = tick_plans

    def begin_iter(self, i: int) -> None:
        self.iter = i
        if not self._tick_plans:
            # a secondary wrapper must not double-tick the shared
            # FaultPlan crash schedule
            return
        for plan in self.schedule.fault_plans:
            if plan.crash_step == i:
                self.injected["crashes"] += 1
            plan.tick(i)

    def _oom_gate(self) -> None:
        if self.iter in self.schedule.oom_iters:
            self.injected["oom"] += 1
            raise MemoryError(
                f"chaos: injected engine OOM at iteration {self.iter}")

    def chunk_prefill(self, tokens, start, block_table, pad_to):
        self._oom_gate()
        return self.inner.chunk_prefill(tokens, start, block_table,
                                        pad_to=pad_to)

    def copy_blocks(self, pairs):
        self._oom_gate()
        return self.inner.copy_blocks(pairs)

    def copy_blocks_from(self, src_engine, pairs):
        # the hand-off fault class (docs/serving.md, "Disaggregated
        # prefill/decode"): a TORN transfer really moves a prefix of
        # the blocks before failing — the server must re-copy the
        # whole table on retry, so output stays bit-exact; a DELAYED
        # transfer fails before anything moves.  Both surface as the
        # MemoryError skip-and-retry the serve loop already isolates.
        if self.iter in self.schedule.handoff_torn_iters:
            self.injected["handoff_torn"] += 1
            if len(pairs) > 1:
                self.inner.copy_blocks_from(src_engine,
                                            pairs[:len(pairs) // 2])
            raise MemoryError(
                f"chaos: torn hand-off transfer at iteration "
                f"{self.iter}")
        if self.iter in self.schedule.handoff_oom_iters:
            self.injected["handoff_oom"] += 1
            raise MemoryError(
                f"chaos: delayed hand-off transfer at iteration "
                f"{self.iter}")
        self._oom_gate()
        return self.inner.copy_blocks_from(src_engine, pairs)

    def decode(self, tokens, positions, tables):
        import numpy as np

        self._oom_gate()
        out = np.asarray(self.inner.decode(tokens, positions, tables))
        if self.iter in self.schedule.nonfinite_iters:
            row = self.rng.randrange(out.shape[0])
            out = out.copy()
            out[row] = np.nan
            self.injected["nonfinite_rows"] += 1
        return out

    def verify(self, tokens, lengths, positions, tables):
        # the speculative analog of decode(): same OOM gate, and the
        # non-finite poison hits one slot's whole (K, V) logits block —
        # the serve loop must evict exactly that request before any of
        # its drafted tokens can be accepted
        import numpy as np

        self._oom_gate()
        out = np.asarray(self.inner.verify(tokens, lengths,
                                           positions, tables))
        if self.iter in self.schedule.nonfinite_iters:
            row = self.rng.randrange(out.shape[0])
            out = out.copy()
            out[row] = np.nan
            self.injected["nonfinite_rows"] += 1
        return out

    # -- fused on-device-sampling twins (the pipelined serve loop) ---------
    # Same gates, same per-iteration RNG draw sequence as the logits
    # methods, so a (config, seed) schedule injects identical faults
    # whichever loop the server runs.  The non-finite poison flips the
    # victim row's finite FLAG via a lazy device op — no
    # materialization, so injection never collapses the dispatch-ahead
    # window it is trying to fault.

    def chunk_prefill_sampled(self, tokens, start, block_table,
                              pad_to, sampling=None):
        self._oom_gate()
        return self.inner.chunk_prefill_sampled(tokens, start,
                                                block_table,
                                                pad_to=pad_to,
                                                sampling=sampling)

    def decode_sampled(self, tokens, positions, tables,
                       sampling=None):
        self._oom_gate()
        ids, fin = self.inner.decode_sampled(tokens, positions,
                                             tables, sampling=sampling)
        if self.iter in self.schedule.nonfinite_iters:
            row = self.rng.randrange(int(fin.shape[0]))
            fin = fin.at[row].set(False)
            self.injected["nonfinite_rows"] += 1
        return ids, fin

    def verify_sampled(self, tokens, lengths, positions, tables,
                       sampling=None):
        self._oom_gate()
        ids, fin = self.inner.verify_sampled(tokens, lengths,
                                             positions, tables,
                                             sampling=sampling)
        if self.iter in self.schedule.nonfinite_iters:
            # one slot's whole flag row — the same blast radius as
            # NaN-ing its (K, V) logits block on the logits path
            row = self.rng.randrange(int(fin.shape[0]))
            fin = fin.at[row].set(False)
            self.injected["nonfinite_rows"] += 1
        return ids, fin

    # -- hierarchical-offload fault twins ----------------------------------
    # (docs/serving.md, "Hierarchical KV offload").  Neither calls
    # _oom_gate(): offload failures are contained inside the prefix
    # cache's promote/demote paths (cold prefill, never _note_oom), so
    # they must stay OUT of the engine-OOM reconciliation invariant.

    def export_blocks(self, block_ids, **kwargs):
        # a TORN SPILL: the demote really happens, but one leaf's
        # bytes rot after the crc was recorded — the checksummed
        # import path must reject the payload whole on promote, and
        # the admission must cold-prefill bit-identically
        payload = self.inner.export_blocks(block_ids, **kwargs)
        if self.iter in self.schedule.offload_torn_iters:
            import numpy as np

            name = min(payload["leaves"])
            arr = payload["leaves"][name].copy()
            arr.view(np.uint8).flat[0] ^= 0xFF
            payload = dict(payload,
                           leaves=dict(payload["leaves"], **{name: arr}))
            self.injected["offload_torn"] += 1
        return payload

    def import_blocks(self, block_ids, payload):
        # PROMOTE-AT-CAPACITY: the device-side scatter fails
        # transiently — the store keeps the payload (put-back) and
        # the admission cold-prefills this once
        if self.iter in self.schedule.offload_capacity_iters:
            self.injected["offload_capacity"] += 1
            raise MemoryError(
                f"chaos: injected promote-at-capacity at iteration "
                f"{self.iter}")
        return self.inner.import_blocks(block_ids, payload)

    def __getattr__(self, name):
        return getattr(self.inner, name)


class _TransportFaultPlan:
    """One transfer's injected fault, handed to the transport's send
    envelope (``KVTransport.chaos`` seam).  ``before(payload)`` runs
    at the top of EVERY attempt (it may raise, or return a corrupted
    copy); ``after(redeliver)`` runs after a successful delivery (it
    may re-deliver the same transfer id, or drop the ack on the
    floor).  ``_fired`` makes each fault one-shot, so a retried
    attempt sees a healthy wire — exactly a transient network fault."""

    def __init__(self, kind: str, injected: Dict[str, int]):
        self.kind = kind
        self.injected = injected
        self._fired = False

    def before(self, payload):
        from apex_tpu.serving.transport.base import (
            TransportConnectionError, TransportTimeoutError)

        if self._fired or self.kind in ("dup", "reset_after"):
            return payload
        self._fired = True
        if self.kind == "reset":
            # connection reset mid-frame, before anything ingested:
            # retried by the envelope; the retry lands
            self.injected["transport_reset"] += 1
            raise TransportConnectionError(
                "chaos: connection reset mid-frame")
        if self.kind == "stall":
            # stall past the per-transfer deadline: NOT retried —
            # the consumer's degradation path must fire
            self.injected["transport_stall"] += 1
            raise TransportTimeoutError(
                "chaos: transfer stalled past its deadline")
        if self.kind == "corrupt":
            # one byte of one leaf flips in flight AFTER the payload
            # crc was recorded — the checksummed import must reject
            # the payload whole (the ChaosEngine torn-spill idiom)
            import numpy as np

            self.injected["transport_corrupt"] += 1
            name = min(payload["leaves"])
            arr = np.asarray(payload["leaves"][name]).copy()
            arr.view(np.uint8).flat[0] ^= 0xFF
            return dict(payload,
                        leaves=dict(payload["leaves"], **{name: arr}))
        return payload

    def after(self, redeliver) -> None:
        from apex_tpu.serving.transport.base import \
            TransportConnectionError

        if self._fired:
            return
        if self.kind == "dup":
            # duplicated delivery: the same transfer id arrives twice;
            # the receiver ledger must answer the second from cache
            # (dedup_hits) without re-importing a single block
            self._fired = True
            self.injected["transport_dup"] += 1
            redeliver()
        elif self.kind == "reset_after":
            # the HARD exactly-once case: the handler ran (blocks
            # imported, ack recorded) but the ack died on the wire —
            # the envelope retries, and the retry MUST dedup against
            # the ledger instead of double-importing
            self._fired = True
            self.injected["transport_reset_after"] += 1
            raise TransportConnectionError(
                "chaos: connection reset after dispatch, ack lost")


class ChaosTransport:
    """The transport half of the chaos plane: attach via
    ``transport.chaos = ChaosTransport(schedule, injected)`` and call
    :meth:`begin_iter` alongside the engine wrappers'.  Each scheduled
    fault kind arms once per scheduled iteration and STAYS armed until
    a send consumes it (one fault per send, in arming order) — sends
    are much sparser than iterations on real traffic, and a
    fire-only-if-coincident model would leave whole fault classes
    untested on short soaks.  Faults still waiting at the end of the
    run fire nothing: the ``injected`` tallies count FIRED faults
    only, which is what the soak invariants reconcile against."""

    _KINDS = ("reset", "reset_after", "stall", "dup", "corrupt")

    def __init__(self, schedule: ChaosSchedule,
                 injected: Dict[str, int]):
        self.schedule = schedule
        self.injected = injected
        self.iter = -1
        self._armed: List[str] = []

    def begin_iter(self, i: int) -> None:
        self.iter = i
        sch = self.schedule
        self._armed.extend(kind for kind, iters in (
            ("reset", sch.transport_reset_iters),
            ("reset_after", sch.transport_reset_after_iters),
            ("stall", sch.transport_stall_iters),
            ("dup", sch.transport_dup_iters),
            ("corrupt", sch.transport_corrupt_iters),
        ) if i in iters)

    def plan_send(self, peer: str):
        """One fault plan per armed kind, consumed in arming order by
        successive sends; ``None`` once the backlog is spent (the
        common case with the default 0.0 rates)."""
        if not self._armed:
            return None
        return _TransportFaultPlan(self._armed.pop(0), self.injected)


class ReplicaKillSwitch:
    """Engine wrapper that makes EVERY device call raise while armed —
    the router chaos arm's replica kill (``docs/serving.md``,
    "Multi-replica routing").  Unlike :class:`ChaosEngine`'s transient
    ``MemoryError`` (which the serve loop skips-and-retries in place),
    a :class:`RuntimeError` escapes the step loop entirely — the
    in-process analogue of a replica process dying — so the ROUTER's
    per-replica breaker, not the server's internal isolation, must
    contain it.  Disarming models the replica coming back (a restart
    that kept its host state), which the router's half-open probes
    must discover on their own."""

    _GATED = ("chunk_prefill", "copy_blocks", "decode", "verify",
              "chunk_prefill_sampled", "decode_sampled",
              "verify_sampled")

    def __init__(self, inner):
        self.inner = inner
        self.dead = False
        self.kills = 0          # engine calls refused while dead

    def __getattr__(self, name):
        target = getattr(self.inner, name)
        if name in self._GATED and callable(target):
            def gated(*a, _t=target, **k):
                if self.dead:
                    self.kills += 1
                    raise RuntimeError("chaos: replica killed")
                return _t(*a, **k)
            return gated
        return target


def run_router_soak(make_fleet: Callable, cfg: ChaosConfig, seed: int,
                    *, kill_iter: int, recover_iter: int,
                    victim: int = 0,
                    make_replay: Optional[Callable] = None,
                    log: Callable[[str], None] = lambda s: None,
                    postmortem_dir: Optional[str] = None) -> dict:
    """The multi-replica front door's chaos soak: seeded
    mixed-priority traffic routed through a fleet while one replica is
    KILLED (every engine call raises from ``kill_iter``) and later
    RECOVERED (``recover_iter``), asserting the router invariants
    (``docs/serving.md``, "Multi-replica routing"):

      1. per-replica scheduler/allocator/prefix-cache ``audit()``
         passes every step — including on the killed replica, whose
         host bookkeeping must stay consistent through evacuation;
      2. every routed request reaches EXACTLY ONE terminal state, on
         exactly one replica, with a reason from
         :data:`ROUTER_TERMINAL_REASONS` — re-enqueued requests
         neither vanish nor double-finish;
      3. the sum of per-replica finished counts equals the number of
         requests injected (nothing lost at the router: every routed
         request's final underlying request finished on exactly one
         replica, and none went unplaced);
      4. surviving (eos/length) outputs are bit-exact against a
         SINGLE-replica unfaulted replay oracle — routing, failover,
         and re-enqueue may move work but never change tokens — and
         cut-short requests (incl. ``replica_failed``) produced a
         bit-exact prefix of it;
      5. per-replica failure counters reconcile with the observed
         terminal reasons, and the router failed over at least once
         (the kill window is not allowed to pass silently);
      6. the killed replica RECOVERED: its router-side breaker is
         closed again at the end and the replica is back in rotation;
      7. (only when ``make_fleet`` arms ``enable_journeys=True``)
         journey reconciliation: every routed rid merges to exactly
         one COMPLETE journey — one finish hop, contiguous hop seqs
         across every replica it touched — the failover hop pair
         (evacuate -> reenqueue, causally adjacent) appears exactly
         once per re-enqueue, and hop tallies equal the router's
         reenqueued/handoffs/handoff_fallback counters.  The report
         grows a ``"journeys"`` key (and, with ``postmortem_dir``, a
         ``<postmortem_dir>/router_soak`` success bundle for
         ``tools/journey.py --assert-complete``); journeys-off
         reports stay byte-identical to pre-journey ones.

    ``make_fleet(clock)`` builds the ``RouterFleet`` on the soak's
    deterministic iteration clock (per-replica breakers must run on
    it too — the fleet default does); ``make_replay(clock)`` builds
    the roomy single-replica oracle.  Engine-fault injection beyond
    the kill is deliberately off: this soak attributes failures to
    the ROUTER tier (``tools/chaos_soak.py`` keeps the single-replica
    fault classes on their own axes)."""
    if not 0 <= kill_iter < recover_iter <= cfg.iters:
        raise ValueError(
            f"need 0 <= kill_iter ({kill_iter}) < recover_iter "
            f"({recover_iter}) <= iters ({cfg.iters})")
    schedule = ChaosSchedule.generate(cfg, seed)
    clock_state = {"t": 0.0}
    fleet = make_fleet(lambda: clock_state["t"])
    if not 0 <= victim < len(fleet.replicas):
        raise ValueError(f"victim {victim} out of range")
    vic = fleet.replicas[victim]
    kill = ReplicaKillSwitch(vic.server.engine)
    vic.server.engine = kill
    # transport faults ride the fleet's shared KV transport (hand-off
    # and warm sends); with the transport_* rates at their 0.0
    # defaults nothing arms and legacy (config, seed) runs are
    # untouched
    tinjected = {"transport_reset": 0, "transport_reset_after": 0,
                 "transport_stall": 0, "transport_dup": 0,
                 "transport_corrupt": 0}
    tchaos = ChaosTransport(schedule, tinjected)
    fleet.kv_transport.chaos = tchaos

    tracked: Dict[int, Tuple] = {}      # rid -> (RouterRequest, Arrival)
    terminal: Dict[int, str] = {}       # rid -> finish_reason
    seen_uids: Set[int] = set()         # finished underlying uids
    cursors = [0] * len(fleet.replicas)
    report = {"iters": cfg.iters, "seed": seed,
              "replicas": len(fleet.replicas),
              "kill_iter": kill_iter, "recover_iter": recover_iter,
              "victim": vic.name}
    victim_finished_at_recovery = 0

    def absorb_finished():
        """Invariant 2's per-step half: every newly finished
        underlying request finishes once, with a legal reason."""
        for i, rep in enumerate(fleet.replicas):
            fin = rep.server.scheduler.finished
            for req in fin[cursors[i]:]:
                assert req.uid not in seen_uids, \
                    f"request uid {req.uid} finished twice"
                seen_uids.add(req.uid)
                assert req.finished and \
                    req.finish_reason in ROUTER_TERMINAL_REASONS, \
                    (f"request {req.uid} finished with bad reason "
                     f"{req.finish_reason!r} on {rep.name}")
            cursors[i] = len(fin)
        for rid, (rr, _a) in tracked.items():
            if rr.finished and rid not in terminal:
                terminal[rid] = rr.finish_reason

    def _postmortem_and_reraise(e: AssertionError):
        if postmortem_dir is None:
            raise e
        bundle = os.path.join(postmortem_dir,
                              "router_invariant_violation")
        fleet.dump_postmortem(bundle, reason="invariant_violation",
                              extra={"error": str(e), "seed": seed})
        log(f"postmortem bundle written: {bundle}")
        raise AssertionError(f"{e} [postmortem: {bundle}]") from e

    try:
        for i in range(cfg.iters):
            clock_state["t"] = float(i)
            tchaos.begin_iter(i)
            if i == kill_iter:
                kill.dead = True
                log(f"iter {i}: KILLED {vic.name}")
            if i == recover_iter:
                kill.dead = False
                victim_finished_at_recovery = len(
                    vic.server.scheduler.finished)
                log(f"iter {i}: recovered {vic.name}")
            for a in schedule.arrivals.get(i, ()):
                rr = fleet.submit(list(a.prompt), a.max_new_tokens,
                                  priority=a.priority,
                                  deadline_iters=a.deadline_iters,
                                  deadline_s=a.deadline_s)
                tracked[rr.rid] = (rr, a)
            fleet.step()
            for rep in fleet.replicas:              # invariant 1
                rep.server.scheduler.audit()
            absorb_finished()
            if i and i % 200 == 0:
                log(f"iter {i}: {len(terminal)}/{len(tracked)} "
                    f"terminal, victim breaker="
                    f"{vic.breaker.state}")

        clock_state["t"] = float(cfg.iters)
        tchaos.begin_iter(cfg.iters)
        fleet.drain()
        for rep in fleet.replicas:
            rep.server.scheduler.audit()
        absorb_finished()

        router = fleet.stats()["router"]
        # transport-fault reconciliation (trivially 0 == 0 with the
        # default rates): every fired fault left its exact fingerprint
        # on the shared transport, and every failed send degraded to
        # the monolithic fallback — which invariants 2-4 then prove
        # produced the same tokens
        tstats = fleet.stats()["transport"]
        assert tstats["dedup_hits"] == (
            tinjected["transport_dup"]
            + tinjected["transport_reset_after"]), \
            (f"dedup_hits={tstats['dedup_hits']} != injected "
             f"dup={tinjected['transport_dup']} + reset_after="
             f"{tinjected['transport_reset_after']}")
        assert tstats["deadline_exceeded"] == \
            tinjected["transport_stall"], \
            (f"deadline_exceeded={tstats['deadline_exceeded']} != "
             f"injected stalls={tinjected['transport_stall']}")
        assert tstats["retries"] == (
            tinjected["transport_reset"]
            + tinjected["transport_reset_after"]), \
            (f"retries={tstats['retries']} != injected reset="
             f"{tinjected['transport_reset']} + reset_after="
             f"{tinjected['transport_reset_after']}")
        for rid, (rr, _a) in tracked.items():       # invariant 2
            assert rr.finished and rid in terminal, \
                f"routed request {rid} never reached a terminal state"
            assert terminal[rid] == rr.finish_reason, \
                (f"routed request {rid} changed terminal reason "
                 f"{terminal[rid]!r} -> {rr.finish_reason!r}")
        per_replica_finished = {
            rep.name: len(rep.server.scheduler.finished)
            for rep in fleet.replicas}
        assert router["unplaced"] == 0, \
            (f"{router['unplaced']} requests went unplaced — the "
             f"fleet had healthy replicas the whole soak")
        assert sum(per_replica_finished.values()) == len(tracked), \
            (f"per-replica finished {per_replica_finished} sums to "
             f"{sum(per_replica_finished.values())} != "
             f"{len(tracked)} injected")           # invariant 3
        assert router["failovers"] >= 1, \
            "the kill window passed without a failover"  # invariant 5
        assert vic.breaker.state == "closed", \
            (f"victim breaker still {vic.breaker.state} after "
             f"recovery")                           # invariant 6

        # invariant 5's counter half: per-replica failure counters
        # reconcile with the reasons actually observed
        tally: Dict[str, int] = {}
        for reason in terminal.values():
            tally[reason] = tally.get(reason, 0) + 1
        for reason, n in tally.items():
            if reason in HEALTHY_REASONS:
                continue
            got = sum(rep.server.failures.count(
                f"requests_failed_{reason}")
                for rep in fleet.replicas)
            assert got == n, \
                (f"counter requests_failed_{reason}={got} != {n} "
                 f"observed")

        # invariant 7 (journey reconciliation, armed only when
        # make_fleet built with enable_journeys=True — legacy
        # (config, seed) reports stay byte-identical without it;
        # docs/observability.md, "Request journeys & exemplars"):
        # every routed rid merges to EXACTLY ONE complete journey
        # (one finish hop, contiguous hop seqs across every replica
        # it touched), the failover hop pair (evacuate -> reenqueue,
        # consecutive seqs) appears once per re-enqueue, and the hop
        # tallies reconcile with the router's own counters.
        jreport = None
        if fleet.journeys.enabled:
            from apex_tpu.observability import merge_journeys

            jcensus = fleet.stats()["journeys"]
            assert jcensus["dropped"] == 0, \
                (f"journey ring dropped {jcensus['dropped']} hop(s) "
                 f"— raise the log capacity for this soak length")
            journeys = merge_journeys(fleet._journey_logs())
            hop_counts: Dict[str, int] = {}
            pairs = 0
            for rid in tracked:
                j = journeys.get(rid)
                assert j is not None, \
                    f"finished rid {rid} never opened a journey"
                assert j.complete, \
                    (f"rid {rid}'s journey is incomplete: "
                     f"{[ (h['seq'], h['kind']) for h in j.hops ]}")
                for kind, n in j.counts().items():
                    hop_counts[kind] = hop_counts.get(kind, 0) + n
                for a_h, b_h in zip(j.hops, j.hops[1:]):
                    if a_h["kind"] == "evacuate" \
                            and b_h["kind"] == "reenqueue":
                        pairs += 1
            assert len(journeys) == len(tracked), \
                (f"{len(journeys)} journeys merged != {len(tracked)} "
                 f"routed requests — phantom or lost rids")
            assert hop_counts.get("reenqueue", 0) \
                == router["reenqueued"], \
                (f"{hop_counts.get('reenqueue', 0)} reenqueue hop(s) "
                 f"!= router reenqueued={router['reenqueued']}")
            assert hop_counts.get("evacuate", 0) \
                >= hop_counts.get("reenqueue", 0), \
                "a reenqueue hop without its evacuate half"
            assert pairs == hop_counts.get("reenqueue", 0), \
                (f"{pairs} consecutive evacuate->reenqueue pair(s) "
                 f"!= {hop_counts.get('reenqueue', 0)} reenqueue "
                 f"hop(s) — the failover pair must be causally "
                 f"adjacent")
            assert hop_counts.get("handoff_ingest", 0) \
                == router["handoffs"], \
                (f"{hop_counts.get('handoff_ingest', 0)} ingest "
                 f"hop(s) != router handoffs={router['handoffs']}")
            assert hop_counts.get("handoff_fallback", 0) \
                == router["handoff_fallback"], \
                (f"{hop_counts.get('handoff_fallback', 0)} fallback "
                 f"hop(s) != router "
                 f"handoff_fallback={router['handoff_fallback']}")
            jreport = {
                "complete": len(tracked),
                "hops": jcensus["hops"],
                "evacuate_hops": hop_counts.get("evacuate", 0),
                "reenqueue_hops": hop_counts.get("reenqueue", 0),
                "failover_pairs": pairs,
                "handoff_ingest_hops":
                    hop_counts.get("handoff_ingest", 0),
            }
    except AssertionError as e:
        _postmortem_and_reraise(e)

    # invariant 4: bit-exact survivors / prefixes vs a single-replica
    # unfaulted replay — the oracle never saw a router, so equality
    # proves routing/failover changed placement, not tokens
    make_replay_fn = make_replay or make_fleet
    replay = make_replay_fn(lambda: 0.0)
    outputs: Dict[Tuple, List[int]] = {}
    by_budget: Dict[int, List[Tuple]] = {}
    for rr, a in tracked.values():
        key = (a.prompt, rr.max_new_tokens)
        if key not in outputs:
            outputs[key] = None
            by_budget.setdefault(rr.max_new_tokens, []).append(key)
    for budget, keys in sorted(by_budget.items()):
        outs = replay.generate([list(k[0]) for k in keys], budget)
        for key, out in zip(keys, outs):
            outputs[key] = out
    checked = prefix_checked = 0
    try:
        for rr, a in tracked.values():
            ref = outputs[(a.prompt, rr.max_new_tokens)]
            if rr.finish_reason in HEALTHY_REASONS:
                assert list(rr.generated) == ref, \
                    (f"surviving request {rr.rid} diverged from the "
                     f"single-replica replay: {rr.generated} != {ref}")
                checked += 1
            elif rr.generated:
                assert list(rr.generated) == ref[:len(rr.generated)], \
                    (f"{rr.finish_reason} request {rr.rid}'s partial "
                     f"output is not a prefix of the replay")
                prefix_checked += 1
    except AssertionError as e:
        _postmortem_and_reraise(e)

    stats = fleet.stats()
    report.update(
        submitted=len(tracked),
        finished=dict(sorted(tally.items())),
        per_replica_finished=per_replica_finished,
        bit_exact_checked=checked,
        prefix_checked=prefix_checked,
        reenqueued=router["reenqueued"],
        failovers=router["failovers"],
        replica_failed=router["replica_failed"],
        unplaced=router["unplaced"],
        kills_refused=kill.kills,
        victim_breaker=vic.breaker.state_snapshot(),
        victim_finished_post_recovery=(
            per_replica_finished[vic.name]
            - victim_finished_at_recovery),
        affinity=router["affinity"],
        pressure_peak=stats["pressure_peak"],
        transport={k: stats["transport"][k] for k in (
            "backend", "attempts", "retries", "delivered", "rejects",
            "failures", "deadline_exceeded", "breaker_fastfail",
            "ingested", "dedup_hits")},
    )
    if jreport is not None:
        report["journeys"] = jreport
        if postmortem_dir is not None:
            # success bundle: the soak's merged journeys, written so
            # tools/journey.py --assert-complete can gate the SAME
            # artifact CI would pull after a failure (the journey
            # build-matrix axis consumes this)
            bundle = os.path.join(postmortem_dir, "router_soak")
            fleet.dump_postmortem(bundle, reason="soak_complete",
                                  extra={"seed": seed})
            jreport["bundle"] = bundle
            log(f"journey bundle written: {bundle}")
    return report


def run_elastic_soak(make_fleet: Callable, cfg: ChaosConfig, seed: int,
                     *, rollout_iter: int, expect_final_size: int = 1,
                     make_replay: Optional[Callable] = None,
                     log: Callable[[str], None] = lambda s: None,
                     postmortem_dir: Optional[str] = None) -> dict:
    """The ELASTIC fleet's chaos soak (``docs/serving.md``, "Elastic
    fleet"): seeded traffic with a sustained ``flash_crowd`` arrival
    window routed through an autoscaling ``RouterFleet``, with a
    zero-downtime weight ROLLOUT fired mid-crowd — the worst
    realistic composition: membership churn, rolling drains, and a
    version swap all while the queue is the deepest.  Invariants:

      1. per-replica scheduler/allocator/prefix-cache ``audit()``
         passes every step, across every membership change;
      2. exactly-once terminals: every routed request reaches ONE
         terminal state with a legal reason — across scale-ups,
         rolling scale-down drains, and the rollout's drain/swap/
         revive cycles, requests neither vanish nor double-finish
         (zero healthy-request loss);
      3. the sum of finished counts over live AND retired replicas
         equals the number injected, and nothing went unplaced;
      4. the flash crowd forced at least one scale-UP, and after the
         crowd passed the fleet converged back to
         ``expect_final_size`` replicas;
      5. the mid-crowd rollout reported ``"ok"`` and the fleet ends
         on a SINGLE weights version — the rollout's, on every
         surviving replica;
      6. SLO debt is BOUNDED: once the crowd has passed and capacity
         caught up, the shed-token debt stops growing (zero growth
         over the soak's final fifth);
      7. surviving outputs are bit-exact vs a single-replica
         unfaulted replay oracle (cut-short ones bit-exact prefixes)
         — scaling and rolling weights that pass the parity gate may
         move work but never change tokens;
      8. failure counters reconcile with the observed terminal
         reasons (retired replicas included).

    ``make_fleet(clock)`` must build the fleet with
    ``enable_elastic=True``; the rollout checkpoint is the fleet's
    OWN params published to a temp dir (output-equivalent by
    construction — the parity gate's happy path), so the soak needs
    no external checkpoint.  ``cfg.flash_crowd_iter`` must be set and
    ``rollout_iter`` must land inside the crowd window."""
    if cfg.flash_crowd_iter is None or cfg.flash_crowd_len <= 0:
        raise ValueError(
            "elastic soak needs cfg.flash_crowd_iter/_len set — the "
            "crowd IS the scenario")
    if not (cfg.flash_crowd_iter <= rollout_iter
            < cfg.flash_crowd_iter + cfg.flash_crowd_len):
        raise ValueError(
            f"rollout_iter {rollout_iter} must land inside the flash "
            f"crowd [{cfg.flash_crowd_iter}, "
            f"{cfg.flash_crowd_iter + cfg.flash_crowd_len})")
    import shutil
    import tempfile

    from apex_tpu.utils import checkpoint as _ckpt

    schedule = ChaosSchedule.generate(cfg, seed)
    clock_state = {"t": 0.0}
    fleet = make_fleet(lambda: clock_state["t"])
    if fleet.autoscaler is None:
        raise ValueError(
            "make_fleet must build with enable_elastic=True")

    tracked: Dict[int, Tuple] = {}      # rid -> (RouterRequest, Arrival)
    terminal: Dict[int, str] = {}       # rid -> finish_reason
    seen_uids: Set[int] = set()
    # membership changes mid-soak: cursors are keyed by replica NAME
    # (stable across scale churn), not list position
    cursors: Dict[str, int] = {}
    crowd_end = cfg.flash_crowd_iter + cfg.flash_crowd_len
    tail_start = cfg.iters - max(1, cfg.iters // 5)
    size_peak = len(fleet.replicas)
    debt_at_tail = None
    rollout_report = None
    report = {"iters": cfg.iters, "seed": seed,
              "start_replicas": len(fleet.replicas),
              "flash_crowd": [cfg.flash_crowd_iter, crowd_end],
              "rollout_iter": rollout_iter}

    def all_reps():
        return fleet.replicas + fleet.retired_replicas

    def absorb_finished():
        for rep in all_reps():
            fin = rep.server.scheduler.finished
            for req in fin[cursors.get(rep.name, 0):]:
                assert req.uid not in seen_uids, \
                    f"request uid {req.uid} finished twice"
                seen_uids.add(req.uid)
                assert req.finished and \
                    req.finish_reason in ROUTER_TERMINAL_REASONS, \
                    (f"request {req.uid} finished with bad reason "
                     f"{req.finish_reason!r} on {rep.name}")
            cursors[rep.name] = len(fin)
        for rid, (rr, _a) in tracked.items():
            if rr.finished and rid not in terminal:
                terminal[rid] = rr.finish_reason

    def _postmortem_and_reraise(e: AssertionError):
        if postmortem_dir is None:
            raise e
        bundle = os.path.join(postmortem_dir,
                              "elastic_invariant_violation")
        fleet.dump_postmortem(bundle, reason="invariant_violation",
                              extra={"error": str(e), "seed": seed})
        log(f"postmortem bundle written: {bundle}")
        raise AssertionError(f"{e} [postmortem: {bundle}]") from e

    # the rollout checkpoint: the fleet's own params, published
    # atomically — output-equivalent by construction, so the parity
    # gate must pass and the soak exercises the FULL promote path
    ckpt_dir = tempfile.mkdtemp(prefix="elastic_soak_ckpt_")
    try:
        _ckpt.CheckpointManager(ckpt_dir).save(1, fleet.params)
        try:
            for i in range(cfg.iters):
                clock_state["t"] = float(i)
                if i == rollout_iter:
                    pre = len(fleet.replicas)
                    rollout_report = fleet.rollout(ckpt_dir)
                    log(f"iter {i}: mid-crowd rollout -> "
                        f"{rollout_report['status']} "
                        f"({rollout_report['replicas_rolled']} "
                        f"replicas)")
                    assert rollout_report["status"] == "ok", \
                        (f"mid-crowd rollout failed: "
                         f"{rollout_report}")
                    assert rollout_report["replicas_rolled"] == pre, \
                        (f"rollout promoted "
                         f"{rollout_report['replicas_rolled']} of "
                         f"{pre} replicas")
                for a in schedule.arrivals.get(i, ()):
                    rr = fleet.submit(list(a.prompt),
                                      a.max_new_tokens,
                                      priority=a.priority,
                                      deadline_iters=a.deadline_iters,
                                      deadline_s=a.deadline_s)
                    tracked[rr.rid] = (rr, a)
                fleet.step()
                for rep in fleet.replicas:          # invariant 1
                    rep.server.scheduler.audit()
                absorb_finished()
                size_peak = max(size_peak, len(fleet.replicas))
                if i == tail_start:
                    debt_at_tail = fleet.shed_debt_tokens()
                if i and i % 200 == 0:
                    log(f"iter {i}: {len(terminal)}/{len(tracked)} "
                        f"terminal, {len(fleet.replicas)} replicas, "
                        f"debt={fleet.shed_debt_tokens()}")

            # convergence is judged BEFORE the final drain (draining
            # parks the autoscaler)
            elastic = fleet.stats()["elastic"]      # invariant 4
            assert elastic["scale_ups"] >= 1, \
                "the flash crowd passed without a single scale-up"
            assert len(fleet.replicas) == expect_final_size, \
                (f"fleet ended at {len(fleet.replicas)} replicas, "
                 f"expected convergence to {expect_final_size}")
            versions = elastic["weights_versions"]  # invariant 5
            assert rollout_report is not None
            want_v = rollout_report["version"]
            assert set(versions) == {want_v}, \
                (f"fleet ends on versions {versions}, expected only "
                 f"{want_v!r}")
            debt_end = fleet.shed_debt_tokens()     # invariant 6
            assert debt_at_tail is not None
            assert debt_end == debt_at_tail, \
                (f"SLO debt still growing after the crowd: "
                 f"{debt_at_tail} -> {debt_end} over the final "
                 f"fifth")

            clock_state["t"] = float(cfg.iters)
            fleet.drain()
            for rep in fleet.replicas:
                rep.server.scheduler.audit()
            absorb_finished()

            router = fleet.stats()["router"]
            for rid, (rr, _a) in tracked.items():   # invariant 2
                assert rr.finished and rid in terminal, \
                    (f"routed request {rid} never reached a "
                     f"terminal state")
                assert terminal[rid] == rr.finish_reason, \
                    (f"routed request {rid} changed terminal reason "
                     f"{terminal[rid]!r} -> {rr.finish_reason!r}")
            per_replica_finished = {
                rep.name: len(rep.server.scheduler.finished)
                for rep in all_reps()}
            assert router["unplaced"] == 0, \
                (f"{router['unplaced']} requests went unplaced")
            assert sum(per_replica_finished.values()) \
                == len(tracked), \
                (f"per-replica finished {per_replica_finished} sums "
                 f"to {sum(per_replica_finished.values())} != "
                 f"{len(tracked)} injected")        # invariant 3

            tally: Dict[str, int] = {}
            for reason in terminal.values():
                tally[reason] = tally.get(reason, 0) + 1
            for reason, n in tally.items():         # invariant 8
                if reason in HEALTHY_REASONS:
                    continue
                got = sum(rep.server.failures.count(
                    f"requests_failed_{reason}")
                    for rep in all_reps())
                assert got == n, \
                    (f"counter requests_failed_{reason}={got} != "
                     f"{n} observed")
        except AssertionError as e:
            _postmortem_and_reraise(e)

        # invariant 7: bit-exact survivors / prefixes vs a
        # single-replica unfaulted replay
        if make_replay is None:
            raise ValueError(
                "elastic soak needs make_replay (a single-server "
                "factory — the fleet factory autoscales and cannot "
                "be the oracle)")
        replay = make_replay(lambda: 0.0)
        outputs: Dict[Tuple, List[int]] = {}
        by_budget: Dict[int, List[Tuple]] = {}
        for rr, a in tracked.values():
            key = (a.prompt, rr.max_new_tokens)
            if key not in outputs:
                outputs[key] = None
                by_budget.setdefault(rr.max_new_tokens,
                                     []).append(key)
        for budget, keys in sorted(by_budget.items()):
            outs = replay.generate([list(k[0]) for k in keys],
                                   budget)
            for key, out in zip(keys, outs):
                outputs[key] = out
        checked = prefix_checked = 0
        try:
            for rr, a in tracked.values():
                ref = outputs[(a.prompt, rr.max_new_tokens)]
                if rr.finish_reason in HEALTHY_REASONS:
                    assert list(rr.generated) == ref, \
                        (f"surviving request {rr.rid} diverged from "
                         f"the replay: {rr.generated} != {ref}")
                    checked += 1
                elif rr.generated:
                    assert list(rr.generated) \
                        == ref[:len(rr.generated)], \
                        (f"{rr.finish_reason} request {rr.rid}'s "
                         f"partial output is not a prefix of the "
                         f"replay")
                    prefix_checked += 1
        except AssertionError as e:
            _postmortem_and_reraise(e)
    finally:
        shutil.rmtree(ckpt_dir, ignore_errors=True)

    stats = fleet.stats()
    elastic = stats["elastic"]
    report.update(
        submitted=len(tracked),
        finished=dict(sorted(tally.items())),
        per_replica_finished=per_replica_finished,
        bit_exact_checked=checked,
        prefix_checked=prefix_checked,
        size_peak=size_peak,
        final_replicas=len(fleet.replicas),
        retired_replicas=len(fleet.retired_replicas),
        scale_ups=elastic["scale_ups"],
        scale_downs=elastic["scale_downs"],
        weights_versions=elastic["weights_versions"],
        rollout=rollout_report,
        shed_debt_tokens=fleet.shed_debt_tokens(),
        reenqueued=stats["router"]["reenqueued"],
        unplaced=stats["router"]["unplaced"],
        pressure_peak=stats["pressure_peak"],
    )
    return report


def run_soak(make_server: Callable, cfg: ChaosConfig, seed: int, *,
             make_replay: Optional[Callable] = None,
             log: Callable[[str], None] = lambda s: None,
             postmortem_dir: Optional[str] = None) -> dict:
    """Drive a full server through the chaos schedule, asserting the
    global invariants; returns a report dict (raises AssertionError
    with context on the first violation).

    ``make_server(clock)`` must build a fresh ``InferenceServer``
    whose wall clock (and breaker clock) is the given callable — the
    soak drives it in whole iterations, so the entire run, including
    breaker cooldowns and ``deadline_s`` expiries, is deterministic
    for a given ``(cfg, seed)``.  ``make_replay(clock)`` (default:
    ``make_server``) builds the unfaulted replay server — typically
    with a roomy pool so replays never hit capacity.

    ``postmortem_dir``: when set, ANY invariant violation dumps a
    postmortem bundle (``docs/observability.md``, "Flight recorder &
    postmortems") to ``<postmortem_dir>/invariant_violation`` — the
    soaked server's flight-recorder ring, metrics snapshot, and trace
    at the moment of the violation, plus the chaos injection counts —
    before re-raising with the bundle path appended.  Build the server
    with a ``FlightRecorder`` (``tools/chaos_soak.py`` does) or the
    bundle's flight log is empty.

    Invariants, per step:
      1. scheduler/allocator/prefix-cache ``audit()`` passes;
      2. every newly finished request has exactly one terminal
         ``finish_reason`` from :data:`TERMINAL_REASONS`, and no
         request finishes twice;
      3. no finished request lingers in the waiting queue or batch.
    At the end (after ``drain()``):
      4. every submitted request reached a terminal state;
      5. healthy (eos/length) requests are bit-exact against the
         unfaulted replay, and cut-short requests (timeout / shed /
         capacity / nonfinite) produced a bit-exact PREFIX of it;
      6. ``stats()`` reconciles with observed outcomes: finished
         count, per-reason failure counters, breaker rejections, and
         injected-vs-counted OOM events all agree;
      7. an armed hang watchdog (``tools/chaos_soak.py`` arms one on
         the real clock) recorded ZERO stalls — composed faults are
         not hangs, and a soak is the strongest false-positive trial
         the detector gets.

    Streaming (``docs/serving.md``, "Streaming & cancellation"): when
    the soaked server has a :class:`~serving.streaming.StreamBroker`
    (``enable_streaming=True``), every tracked request ALSO gets a
    token stream opened at submit time and drained every iteration,
    and two more invariants ride the whole soak:
      8. delivered tokens are byte-identical to ``req.generated`` for
         every finished request (greedy AND counter-keyed
         stochastic), and the stream's terminal event carries exactly
         the request's ``finish_reason``;
      9. a ``disconnect_rate`` fault (client hangs up: stream closed,
         request cancelled mid-decode — mid-chunk, mid-speculation-
         window, or mid-pipelined-launch, whatever the iteration
         composed) leaves the delivered prefix bit-exact vs the
         replay, the terminal ``"cancelled"``, and the pool
         audit-clean — cancellation must actually free the blocks.

    Journeys (``docs/observability.md``, "Request journeys &
    exemplars"): when ``make_server`` arms ``enable_journeys=True``
    (``tools/chaos_soak.py --journeys``), every submitted uid must
    merge to exactly one COMPLETE journey (one finish hop, contiguous
    hop seqs) through every composed fault, with preempt hops equal
    to the preemption ledger and offload_promote block sums equal to
    the promote counters; the report grows a ``"journeys"`` key.
    Journeys-off reports (the default) stay byte-identical.
    """
    schedule = ChaosSchedule.generate(cfg, seed)
    clock_state = {"t": 0.0}
    server = make_server(lambda: clock_state["t"])
    chaos = ChaosEngine(server.engine, schedule)
    server.engine = chaos
    # a disaggregated server's PREFILL pool soaks under the same fault
    # schedule through its own wrapper (independent victim-draw
    # stream, shared tallies; plans tick once, on the primary)
    pchaos = None
    if getattr(server, "prefill_engine", None) is not None:
        pchaos = ChaosEngine(server.prefill_engine, schedule,
                             rng_salt=0x9F11, injected=chaos.injected,
                             tick_plans=False)
        server.prefill_engine = pchaos
    # the transport fault class rides the server's KV transport
    # envelope (docs/serving.md, "KV transport") and shares the
    # injected tallies; with every transport_*_rate at 0 it arms
    # nothing and the envelope's chaos seam short-circuits
    tchaos = ChaosTransport(schedule, chaos.injected)
    server.kv_transport.chaos = tchaos

    sched = server.scheduler
    all_scheds = [sched]
    if getattr(server, "prefill_scheduler", None) is not None:
        all_scheds.append(server.prefill_scheduler)
    tracked: Dict[int, object] = {}     # uid -> Request
    terminal: Dict[int, str] = {}       # uid -> finish_reason
    # streaming delivery (invariants 8 + 9): a stream per tracked
    # request, drained every iteration like a well-behaved consumer;
    # disconnect faults draw their victims from their own salted
    # stream so arming them never perturbs the schedule's draws
    streaming = getattr(server, "stream_broker", None) is not None
    streams: Dict[int, object] = {}     # uid -> TokenStream
    delivered: Dict[int, List[int]] = {}
    disconnected: Set[int] = set()
    cancelled_uids: Set[int] = set()    # cancel() actually landed
    drng = random.Random(seed ^ 0xD15C)
    report = {"iters": cfg.iters, "seed": seed, "crashes_caught": 0,
              "streaming": streaming, "disconnects": 0}

    def absorb_finished():
        """Walk newly finished requests (invariants 2 + 3)."""
        for req in sched.finished[len(terminal):]:
            assert req.uid not in terminal, \
                f"request {req.uid} finished twice"
            assert req.finished and req.finish_reason in TERMINAL_REASONS, \
                (f"request {req.uid} finished with bad reason "
                 f"{req.finish_reason!r}")
            assert req.finished_at is not None, \
                f"request {req.uid} finished without finished_at"
            terminal[req.uid] = req.finish_reason

    def _postmortem_and_reraise(e: AssertionError):
        """Invariant tripped: preserve the black box (the soaked
        server's flight ring + metrics + trace) before propagating."""
        if postmortem_dir is None:
            raise e
        bundle = os.path.join(postmortem_dir, "invariant_violation")
        server.dump_postmortem(
            bundle, reason="invariant_violation",
            extra={"error": str(e), "seed": seed,
                   "injected": dict(chaos.injected)})
        log(f"postmortem bundle written: {bundle}")
        raise AssertionError(f"{e} [postmortem: {bundle}]") from e

    try:
        forced = False
        for i in range(cfg.iters):
            clock_state["t"] = float(i)
            for a in schedule.arrivals.get(i, ()):
                req = server.submit(list(a.prompt), a.max_new_tokens,
                                    priority=a.priority,
                                    deadline_iters=a.deadline_iters,
                                    deadline_s=a.deadline_s,
                                    sampling=_sampling_params(
                                        a.sampling))
                tracked[req.uid] = (req, a)
                if streaming:
                    streams[req.uid] = server.stream(req)
                    delivered[req.uid] = []
            try:
                chaos.begin_iter(i)
                if pchaos is not None:
                    pchaos.begin_iter(i)
                tchaos.begin_iter(i)
                server.step()
            except InjectedCrash:
                # a FaultPlan crash between engine steps: nothing was
                # half-applied, so the very next iteration carries on
                report["crashes_caught"] += 1
            if streaming:
                if i in schedule.disconnect_iters:
                    # one live consumer hangs up: RIGHT after a step,
                    # with the pipelined window still in flight, so
                    # the cancel exercises the flush-then-free path
                    # mid-whatever this iteration composed
                    live = sorted(
                        uid for uid, (req, _a) in tracked.items()
                        if not req.finished
                        and uid not in disconnected)
                    if live:
                        uid = drng.choice(live)
                        delivered[uid].extend(streams[uid].drain())
                        streams[uid].close()
                        if server.cancel(uid):
                            cancelled_uids.add(uid)
                        disconnected.add(uid)
                        report["disconnects"] += 1
                for uid, s in streams.items():
                    if uid not in disconnected and not s.done:
                        delivered[uid].extend(s.drain())
            if (cfg.force_violation_iter is not None and not forced
                    and i >= cfg.force_violation_iter and sched.finished):
                # deliberately corrupt the terminal bookkeeping: the
                # duplicate MUST trip absorb_finished's finished-twice
                # invariant (the postmortem axis proves detection +
                # bundle dump end-to-end)
                sched.finished.append(sched.finished[0])
                forced = True
            for s in all_scheds:
                s.audit()                               # invariant 1
            absorb_finished()
            for s in all_scheds:
                for req in s.waiting:
                    assert not req.finished, \
                        f"finished request {req.uid} still waiting"
                for req in s.running.values():
                    assert not req.finished, \
                        f"finished request {req.uid} still in the batch"
            if i and i % 500 == 0:
                log(f"iter {i}: {len(terminal)}/{len(tracked)} "
                    f"terminal, pressure={sched.pressure():.2f}, "
                    f"breaker={server.breaker.state}")

        clock_state["t"] = float(cfg.iters)
        chaos.begin_iter(cfg.iters)  # past the schedule: drain unfaulted
        if pchaos is not None:
            pchaos.begin_iter(cfg.iters)
        tchaos.begin_iter(cfg.iters)
        server.drain()
        for s in all_scheds:
            s.audit()
        absorb_finished()
        for uid, (req, _) in tracked.items():           # invariant 4
            assert req.finished and uid in terminal, \
                f"request {uid} never reached a terminal state"
        assert not any(s.has_work for s in all_scheds), \
            "drained server still has work"
        if streaming:                                   # invariant 8
            for uid, (req, _a) in tracked.items():
                s, d = streams[uid], delivered[uid]
                if uid in disconnected:
                    # the consumer left early: whatever it saw must
                    # be a byte-exact prefix of the request's output
                    assert d == list(req.generated)[:len(d)], \
                        (f"disconnected stream {uid} delivered "
                         f"tokens that are not a prefix of its own "
                         f"output")
                    continue
                d.extend(s.drain())
                assert d == list(req.generated), \
                    (f"stream {uid} delivered {len(d)} token(s) != "
                     f"request output {len(req.generated)} — "
                     f"delivery must be byte-identical")
                assert s.finish_reason == req.finish_reason, \
                    (f"stream {uid} terminal "
                     f"{s.finish_reason!r} != request "
                     f"{req.finish_reason!r}")
            assert server.stream_broker.active == 0, \
                (f"{server.stream_broker.active} stream(s) still "
                 f"active after every request reached a terminal — "
                 f"the broker must self-prune")
            for uid in sorted(disconnected):            # invariant 9
                # a hang-up whose cancel landed MUST end "cancelled";
                # one that lost the race (the window flush finished
                # the request first) keeps whatever terminal it won
                if uid in cancelled_uids:
                    assert terminal[uid] == CANCELLED, \
                        (f"cancelled request {uid} ended "
                         f"{terminal[uid]!r}, not {CANCELLED!r}")
    except AssertionError as e:
        _postmortem_and_reraise(e)

    # invariant 5: bit-exact healthy outputs / prefixes vs an
    # unfaulted replay of the same prompts.  Greedy decoding makes
    # the comparison an equality — and so does stochastic sampling:
    # counter-based keys make each stream a pure function of
    # (prompt, params, seed), so the replay key carries the sampling
    # tuple and equality still means "the fault surface never
    # corrupted a token", not a tolerance
    make_replay = make_replay or make_server
    replay = make_replay(lambda: 0.0)
    outputs: Dict[Tuple, List[int]] = {}
    by_budget: Dict[int, List[Tuple]] = {}
    for req, a in tracked.values():
        key = (a.prompt, req.max_new_tokens, a.sampling)
        if key not in outputs:
            outputs[key] = None
            by_budget.setdefault(req.max_new_tokens, []).append(key)
    for budget, keys in sorted(by_budget.items()):
        outs = replay.generate(
            [list(k[0]) for k in keys], budget,
            sampling=[_sampling_params(k[2]) for k in keys])
        for key, out in zip(keys, outs):
            outputs[key] = out
    checked = prefix_checked = 0
    try:
        for req, a in tracked.values():
            ref = outputs[(a.prompt, req.max_new_tokens, a.sampling)]
            if req.finish_reason in HEALTHY_REASONS:
                assert list(req.generated) == ref, \
                    (f"healthy request {req.uid} diverged from replay: "
                     f"{req.generated} != {ref}")
                checked += 1
            elif req.generated:
                assert list(req.generated) == ref[:len(req.generated)], \
                    (f"{req.finish_reason} request {req.uid}'s partial "
                     f"output is not a prefix of the replay")
                prefix_checked += 1

        # invariant 6: counters reconcile with observed outcomes
        stats = server.stats()
        tally: Dict[str, int] = {}
        for reason in terminal.values():
            tally[reason] = tally.get(reason, 0) + 1
        assert stats["requests_finished"] == len(terminal), \
            (f"stats requests_finished={stats['requests_finished']} != "
             f"{len(terminal)} observed")
        failure_tally = {r: n for r, n in tally.items()
                         if r not in HEALTHY_REASONS}
        for reason, n in failure_tally.items():
            got = stats["requests_failed"].get(
                f"requests_failed_{reason}", 0)
            assert got == n, \
                (f"counter requests_failed_{reason}={got} != {n} "
                 f"observed")
        assert stats["requests_failed_total"] == \
            sum(failure_tally.values())
        breaker_rejects = stats["breaker_events"].get(
            "breaker_rejections", 0)
        assert breaker_rejects == tally.get("breaker_open", 0), \
            (f"breaker counted {breaker_rejects} rejections, observed "
             f"{tally.get('breaker_open', 0)} breaker_open finishes")
        injected_oom = (chaos.injected["oom"]
                        + chaos.injected.get("handoff_oom", 0)
                        + chaos.injected.get("handoff_torn", 0))
        assert stats["oom_events"] == injected_oom, \
            (f"server counted {stats['oom_events']} OOM events, chaos "
             f"injected {injected_oom} (incl. hand-off faults)")
        assert report["crashes_caught"] == chaos.injected["crashes"]
        # invariant 7: every offload crc reject traces to an injected
        # corruption — a torn spill or an in-flight transport corrupt;
        # a reject WITHOUT an injection would mean the demote/promote
        # path corrupts payloads on its own.  (<=, not ==: a torn
        # payload only rejects if a resumed session actually tries to
        # promote it before the host LRU drops it.)
        inj_corruptions = (chaos.injected.get("offload_torn", 0)
                           + chaos.injected.get("transport_corrupt", 0))
        if stats["offload"]["enabled"]:
            assert stats["offload"]["crc_rejects"] <= inj_corruptions, \
                (f"offload rejected {stats['offload']['crc_rejects']} "
                 f"payload(s) but chaos only injected "
                 f"{inj_corruptions} corruption(s) (torn spills + "
                 f"in-flight corrupts) — the offload path corrupted "
                 f"data on its own")
        # invariant 10: the transport envelope reconciles EXACTLY
        # against the injected network faults (docs/serving.md, "KV
        # transport").  Exactly-once: every duplicated delivery and
        # every retry-behind-a-lost-ack answered from the dedup
        # ledger, never by a second import; every stall became one
        # deadline_exceeded (not retried); every reset became exactly
        # one retry; every envelope give-up degraded the consumer
        # (promote is this soak's only transport consumer) — no more,
        # no fewer.
        t = stats["transport"]
        inj = chaos.injected
        assert t["dedup_hits"] == (inj.get("transport_dup", 0)
                                   + inj.get("transport_reset_after", 0)), \
            (f"transport answered {t['dedup_hits']} duplicate(s) from "
             f"the ledger, chaos injected "
             f"{inj.get('transport_dup', 0)} dup(s) + "
             f"{inj.get('transport_reset_after', 0)} lost ack(s) — "
             f"exactly-once bookkeeping leaked")
        assert t["deadline_exceeded"] == inj.get("transport_stall", 0), \
            (f"transport counted {t['deadline_exceeded']} deadline "
             f"expiries, chaos injected "
             f"{inj.get('transport_stall', 0)} stall(s)")
        assert t["retries"] == (inj.get("transport_reset", 0)
                                + inj.get("transport_reset_after", 0)), \
            (f"transport retried {t['retries']} time(s), chaos "
             f"injected {inj.get('transport_reset', 0)} reset(s) + "
             f"{inj.get('transport_reset_after', 0)} lost ack(s)")
        if stats["offload"]["enabled"]:
            assert stats["offload"]["transport_skips"] == t["failures"], \
                (f"promote skipped {stats['offload']['transport_skips']} "
                 f"transfer(s) on transport failure but the envelope "
                 f"counted {t['failures']} — a failed transfer leaked "
                 f"past its degradation path")
        # an armed hang watchdog must ride the whole soak — thousands
        # of iterations of composed faults, none of them a hang —
        # without a single false positive (docs/observability.md,
        # "Ops plane & watchdog")
        if stats["watchdog"]["enabled"]:
            assert stats["watchdog"]["stalls"] == 0, \
                (f"watchdog fired {stats['watchdog']['stalls']} "
                 f"time(s) on a healthy soak (deadline "
                 f"{stats['watchdog']['deadline_s']}s)")
        # journey reconciliation, single-server half (armed only by
        # --journeys; docs/observability.md, "Request journeys &
        # exemplars"): without a router the rid IS the uid, and every
        # tracked uid must merge to exactly one complete journey —
        # exactly one finish hop, contiguous seqs across enqueue /
        # admit / preempt / offload-promote / hand-off / finish,
        # through every composed fault.  Hop tallies reconcile with
        # the pinned counters: preempt hops against the preemption
        # ledger, offload_promote block sums against the promote
        # counters.
        jreport = None
        if server.journeys.enabled:
            from apex_tpu.observability import merge_journeys

            jcensus = stats["journeys"]
            assert jcensus["dropped"] == 0, \
                (f"journey ring dropped {jcensus['dropped']} hop(s) "
                 f"— raise the log capacity for this soak length")
            journeys = merge_journeys([server.journeys])
            hop_counts: Dict[str, int] = {}
            for uid in tracked:
                j = journeys.get(uid)
                assert j is not None, \
                    f"finished uid {uid} never opened a journey"
                assert j.complete, \
                    (f"uid {uid}'s journey is incomplete: "
                     f"{[(h['seq'], h['kind']) for h in j.hops]}")
                for kind, n in j.counts().items():
                    hop_counts[kind] = hop_counts.get(kind, 0) + n
            assert len(journeys) == len(tracked), \
                (f"{len(journeys)} journeys merged != {len(tracked)} "
                 f"submitted requests — phantom or lost uids")
            assert hop_counts.get("preempt", 0) \
                == stats["preemptions"], \
                (f"{hop_counts.get('preempt', 0)} preempt hop(s) != "
                 f"stats preemptions={stats['preemptions']}")
            if stats["offload"]["enabled"]:
                promoted_blocks = sum(
                    h.get("blocks", 0) for j in journeys.values()
                    for h in j.hops if h["kind"] == "offload_promote")
                counted = (stats["offload"]["promotes_host"]
                           + stats["offload"]["promotes_disk"])
                assert promoted_blocks == counted, \
                    (f"offload_promote hops carry {promoted_blocks} "
                     f"block(s) != {counted} counted promotes")
            jreport = {
                "complete": len(tracked),
                "hops": jcensus["hops"],
                "preempt_hops": hop_counts.get("preempt", 0),
                "offload_promote_hops":
                    hop_counts.get("offload_promote", 0),
            }
    except AssertionError as e:
        _postmortem_and_reraise(e)

    report.update(
        submitted=len(tracked),
        finished=dict(sorted(tally.items())),
        bit_exact_checked=checked,
        prefix_checked=prefix_checked,
        injected=dict(chaos.injected),
        sheds=tally.get("shed", 0),
        breaker_open=tally.get("breaker_open", 0),
        preemptions=stats["preemptions"],
        pressure_peak=stats["pressure_peak"],
        breaker_state=stats["breaker_state"],
        oom_events=stats["oom_events"],
        speculation=stats["speculation"]["enabled"],
        acceptance_rate=stats["speculation"]["acceptance_rate"],
        sampling_requests=stats["sampling"]["requests"],
        stoch_acceptance_rate=stats["sampling"]["rejection"][
            "acceptance_rate"],
        stoch_resamples=stats["sampling"]["rejection"]["resamples"],
        drafted_tokens=stats["speculation"]["drafted_tokens"],
        tokens_per_engine_step=stats["speculation"][
            "tokens_per_engine_step"],
        flight_steps=stats["flight"]["steps_recorded"],
        goodput_ratio=stats["slo"]["goodput_ratio"],
        kv_live_peak=stats["memory"]["blocks_live_peak"],
        watchdog_armed=stats["watchdog"]["enabled"],
        watchdog_stalls=stats["watchdog"]["stalls"],
        disagg=stats["disagg"]["enabled"],
        handoff=(stats["disagg"].get("handoff")
                 if stats["disagg"]["enabled"] else None),
        kv_offload=stats["offload"]["enabled"],
        offload=({k: stats["offload"][k] for k in
                  ("demotes", "promotes_host", "promotes_disk",
                   "spills", "crc_rejects", "capacity_skips",
                   "transport_skips", "disk_torn")}
                 if stats["offload"]["enabled"] else None),
        transport={k: stats["transport"][k] for k in
                   ("backend", "attempts", "retries", "delivered",
                    "rejects", "failures", "deadline_exceeded",
                    "breaker_fastfail", "ingested", "dedup_hits")},
    )
    if jreport is not None:
        report["journeys"] = jreport
    if streaming:
        bst = server.stream_broker.stats()
        report.update(
            streams_opened=bst["opened"],
            stream_published_tokens=bst["published_tokens"],
            stream_backpressure_drops=bst["backpressure_drops"],
            cancelled=tally.get(CANCELLED, 0),
        )
    return report
