"""Continuous-batching request scheduler (Orca-style iteration-level).

The unit of scheduling is one DECODE ITERATION, not one request: every
iteration the scheduler (1) admits waiting requests into free batch
slots while the block pool can hold their prompts, (2) grows each
running request's block table just-in-time for its next token —
preempting the youngest request back to the waiting queue when the
pool runs dry — and (3) retires finished requests immediately, so
their slot and blocks are reusable on the very next iteration.  A
short request never waits for a long one to finish (the ~10x
throughput result of iteration-level batching), and memory is
committed a block at a time instead of worst-case up front.

Two serving-perf layers ride on top (``docs/serving.md``):

**Prefix caching** (:mod:`serving.prefix_cache`).  At admission the
request's context is matched against the block-level prefix index;
matched full blocks enter the table SHARED (one
``BlockAllocator.incref`` per table) and only the uncached tail is
prefilled.  Blocks are registered into the index as they fill (during
prefill chunks and as decode crosses block boundaries), and a
finished request's registered blocks are held evictable-LRU instead
of freed — reclaimed by :meth:`Scheduler._try_alloc` only when the
pool actually runs low.  When the ENTIRE context is cached (token
count block-aligned and fully matched) the last matched block is
duplicated copy-on-write — the request must recompute the final
token's logits and re-write its K/V, which may not touch a shared
block; the engine performs the device copy and :meth:`cow_done` drops
the extra ref.

**Chunked prefill** (Sarathi-style).  :meth:`prefill_plan` hands out
the uncached tail ``chunk_size`` tokens at a time; the step loop runs
ONE chunk per prefilling request per iteration, interleaved with the
decode step, so a long prompt stalls running decodes by at most one
chunk rather than one full prefill.  The chunk engine program carries
the KV position (``start``), so generation is bit-stable across any
chunking of the same context.

**Speculative lookahead** (``serving.speculation``).  A decoding
request with drafts needs room for up to K token writes this
iteration, not one: :meth:`lookahead_capacity` grows the table
opportunistically (evicting idle cache holds but never preempting — a
bad drafter must not degrade its neighbors; a draft that doesn't fit
is trimmed), and :meth:`rollback_lookahead` frees the blocks holding
only rejected-suffix positions after every verify step, so
speculation borrows pool space within an iteration instead of
keeping it.  Under a quantized pool (``docs/serving.md``, "Quantized
KV cache") a freed block releases its scale-sidecar rows with it —
scales are indexed by the same slots — and the rejected-suffix
garbage (int8 payload AND scales) sits beyond ``num_cached`` where
the context bias masks it, exactly like the full-width pool's.

The scheduler is pure host-side bookkeeping over the engine's
geometry; it never touches device arrays.  ``serving.api`` composes it
with the :class:`serving.engine.DecodeEngine` into the step loop.

Preemption = recompute (vLLM's default): the victim's blocks are
freed, and on re-admission its full sequence so far re-prefills as a
pseudo-prompt.  The already-sampled tokens are NOT re-sampled — the
re-prefilled context is ``prompt + generated[:-1]``, its logits are
discarded, and the pending last token re-enters the decode loop
unchanged — so generation is bit-stable across preemptions under
greedy decoding.  (With the prefix cache on, the victim's registered
blocks usually survive as LRU holds and re-admission matches them
back — preemption recovery becomes a cache hit.)

Failure isolation: a pathological request fails ALONE.  A request
whose context can never fit the pool — at admission or by outgrowing
it mid-flight with no victim left to preempt — is finished with
``finish_reason="capacity"`` via :meth:`Scheduler.fail` instead of
raising ``MemoryError`` into the step loop (which killed every
in-flight request).  A bounded waiting queue (``max_waiting``) rejects
at submission with :class:`QueueFullError`; expired deadlines and
non-finite logits are detected by ``serving.api`` and routed through
the same :meth:`Scheduler.fail` (reasons ``timeout`` / ``nonfinite``).
``docs/resilience.md`` has the full failure catalogue.

Overload control (:mod:`serving.overload`, on by default through
``InferenceServer``): requests carry a priority class and a
block-cost estimate; when the queue or pool crosses the policy's
pressure threshold the scheduler sheds the lowest-priority, newest
waiting work (``finish_reason="shed"``) instead of blindly bouncing
the next arrival, queue-full arrivals displace lower-priority queued
work, and the preemption victim is chosen worst-priority-first.
"""

from __future__ import annotations

import dataclasses
import itertools
from collections import deque
from typing import Deque, Dict, List, Optional, Tuple

import numpy as np

from apex_tpu.observability import NULL_JOURNEY_LOG, NULL_TRACER
from apex_tpu.ops.sampling import SamplingParams
from apex_tpu.serving.kv_cache import BlockAllocator
from apex_tpu.serving import reasons
from apex_tpu.serving.overload import OverloadPolicy
from apex_tpu.serving.prefix_cache import ROOT, PrefixCache

_uid = itertools.count()

# registration-cursor sentinel: once a request's chain breaks (COW
# duplicate or a key collision) none of its later blocks may register —
# their chain parent is unindexed, and an entry dangling off a reusable
# block id could alias onto garbage after that id is reallocated
_REG_STOPPED = 1 << 60


class QueueFullError(RuntimeError):
    """The bounded waiting queue is at ``max_waiting``; the request was
    NOT enqueued.  Explicit backpressure beats an unbounded queue whose
    tail silently times out."""


@dataclasses.dataclass
class Request:
    """One generation request and its full lifecycle state."""

    prompt: List[int]
    max_new_tokens: int
    eos_id: Optional[int] = None
    uid: int = dataclasses.field(default_factory=lambda: next(_uid))

    # per-request sampling knobs (``docs/serving.md``, "Stochastic
    # sampling"): the default instance is greedy argmax, bit-identical
    # to the historical path.  Stochastic params keep BOTH fast paths
    # (pipelined loop + speculation) — the scheduler batches them into
    # per-slot launch arrays, and the counter-keyed draws make the
    # stream deterministic across preemption/replay/speculation.
    sampling: SamplingParams = dataclasses.field(
        default_factory=SamplingParams)

    # overload-control inputs (``serving.overload``): ``priority`` is
    # nice-style — 0 is the default/foreground class, larger numbers
    # are lower priority and sheddable under pressure.  ``cost_blocks``
    # is the completion-size estimate (prompt + budget, in KV blocks),
    # stamped by ``Scheduler.submit``; queued demand feeds the
    # pressure signal.
    priority: int = 0
    cost_blocks: int = 0

    # per-request budgets (None = unbounded).  ``deadline_iters`` is a
    # count of scheduler iterations from submission; ``deadline_s`` a
    # wall budget.  Both expire to ``finish_reason="timeout"``, checked
    # by the step loop (``serving.api``) at the top of each iteration.
    deadline_iters: Optional[int] = None
    deadline_s: Optional[float] = None
    submit_iter: int = 0            # server iteration at submission
    submitted_at: float = 0.0       # server clock at submission

    # per-request timeline (server clock, stamped by ``serving.api``):
    # enqueue -> admit -> first token -> finish.  ``admitted_at`` keeps
    # its FIRST value across preemption re-admits so queue-wait and
    # TTFT measure the user-visible request, not scheduler internals.
    admitted_at: Optional[float] = None
    first_token_at: Optional[float] = None
    finished_at: Optional[float] = None

    # inter-token-latency accounting (``docs/observability.md``,
    # "SLO & goodput"): the wall gap before each token after the
    # first, stamped by the server as tokens are APPLIED — tokens
    # accepted together in one verify step land as one real gap plus
    # near-zero followers, which is exactly what a streaming consumer
    # would see.  Feeds the per-request ITL p99 the SLO tracker bounds
    # and the disaggregation bench floors.
    itl_gaps: List[float] = dataclasses.field(default_factory=list)
    last_token_at: Optional[float] = None

    # runtime state (owned by the scheduler)
    generated: List[int] = dataclasses.field(default_factory=list)
    slot: int = -1                  # decode batch slot; -1 = not running
    block_table: List[int] = dataclasses.field(default_factory=list)
    num_cached: int = 0             # tokens with K/V materialized
    next_input: Optional[int] = None  # pending token for the next decode
    finished: bool = False
    finish_reason: Optional[str] = None
    preemptions: int = 0

    # speculation accounting (``serving.speculation``): lifetime drafted
    # and accepted token counts for this request — the per-request view
    # behind the server-level acceptance rate.  Drafts are a function
    # of the history, recomputed each iteration; ``draft_index`` is the
    # draft source's cache of that history (``NgramDraft``'s n-gram
    # index), which the history only ever extends, so nothing here
    # needs resetting across preemption and it dies with the request.
    spec_drafted: int = 0
    spec_accepted: int = 0
    draft_index: Optional[object] = None

    # prefill state machine (owned by the scheduler): the context being
    # chunk-prefilled, whether the final chunk's logits sample a token
    # (False after preemption — the pending token continues instead),
    # an admission-time COW copy the engine must perform before the
    # first chunk, prefix-cache accounting, and the block-registration
    # cursor (full blocks [0, _reg_blocks) are already in the index)
    prefill_ctx: Optional[List[int]] = None
    prefill_sample: bool = True
    pending_cow: Optional[Tuple[int, int]] = None   # (src, dst)
    cached_prefix_tokens: int = 0
    _reg_blocks: int = 0

    # journey correlation (``observability.journey``): the
    # :class:`JourneyContext` traveling with this request across
    # replicas — None when journeys are off, so every stamping site
    # can guard on it and the disabled path allocates nothing
    journey: Optional[object] = None

    @property
    def running(self) -> bool:
        return self.slot >= 0 and not self.finished

    @property
    def prefilling(self) -> bool:
        """Admitted but with context K/V still being materialized — the
        decode batch skips it until the last chunk lands."""
        return self.prefill_ctx is not None

    def record_token(self, token: int) -> None:
        """Account one sampled token and evaluate termination."""
        self.generated.append(int(token))
        self.next_input = int(token)
        if self.eos_id is not None and int(token) == self.eos_id:
            self.finished = True
            self.finish_reason = reasons.EOS
        elif len(self.generated) >= self.max_new_tokens:
            self.finished = True
            self.finish_reason = reasons.LENGTH

    def timeline(self) -> dict:
        """The request's lifecycle timestamps (server clock seconds)
        plus derived waits — the per-request record behind the TTFT /
        queue-wait / decode-latency histograms
        (``docs/observability.md``)."""
        out = {
            "uid": self.uid,
            "priority": self.priority,
            "submitted_at": self.submitted_at,
            "admitted_at": self.admitted_at,
            "first_token_at": self.first_token_at,
            "finished_at": self.finished_at,
            "finish_reason": self.finish_reason,
            "tokens": len(self.generated),
            "preemptions": self.preemptions,
        }
        if self.admitted_at is not None:
            out["queue_wait_s"] = self.admitted_at - self.submitted_at
        if self.first_token_at is not None:
            out["ttft_s"] = self.first_token_at - self.submitted_at
        if (self.finished_at is not None
                and self.first_token_at is not None
                and len(self.generated) >= 2):
            out["decode_token_s"] = (
                (self.finished_at - self.first_token_at)
                / (len(self.generated) - 1))
        if self.itl_gaps:
            gaps = sorted(self.itl_gaps)
            n = len(gaps)
            out["itl_p99_s"] = gaps[min(n - 1, -(-99 * n // 100) - 1)]
            out["itl_max_s"] = gaps[-1]
        if self.journey is not None:
            # journey correlation: the fleet-stable rid this timeline
            # belongs to (absent when journeys are off, so the legacy
            # timeline shape is untouched)
            out["rid"] = self.journey.rid
        return out


class Scheduler:
    """Slot + block bookkeeping for continuous batching.

    Args mirror the engine's geometry: ``max_batch_size`` decode
    slots, ``block_size`` tokens per block, ``max_context`` per
    request, and the shared :class:`BlockAllocator`.  ``max_waiting``
    bounds the waiting queue (:class:`QueueFullError` past it);
    ``counters`` is an optional :class:`apex_tpu.utils.CounterMeter`
    fed one ``requests_failed_<reason>`` increment per failure.

    ``prefix_cache``: optional :class:`PrefixCache` enabling
    block-level prefix sharing at admission (None = every prompt
    prefills from scratch, the pre-cache behavior).  ``chunk_size``:
    prefill tail chunk in tokens (None = the whole tail in one
    :meth:`prefill_plan` call, i.e. chunked prefill off).

    ``overload``: optional :class:`OverloadPolicy` enabling
    priority-aware load shedding (queue-full displacement,
    pressure shedding of best-effort waiting work, worst-priority
    preemption victims — :mod:`serving.overload`).  None preserves
    the pre-overload behavior exactly: queue-full raises
    :class:`QueueFullError`, preemption evicts the youngest."""

    def __init__(self, allocator: BlockAllocator, *,
                 max_batch_size: int, block_size: int,
                 max_context: int, max_waiting: Optional[int] = None,
                 counters=None,
                 prefix_cache: Optional[PrefixCache] = None,
                 chunk_size: Optional[int] = None,
                 overload: Optional[OverloadPolicy] = None,
                 tracer=None, journeys=None,
                 ring_rows: Optional[int] = None):
        self.allocator = allocator
        # a model with window layers keeps their rows in a ring of
        # ``ring_rows`` a slot (``serving.kv_cache``): a slot is a ring,
        # so admission counts nothing more; what the rings let go is
        # tallied where a request leaves its slot
        self.ring_rows = ring_rows
        self.ring_rows_let_go = 0
        self.tracer = tracer if tracer is not None else NULL_TRACER
        # journey correlation plane (``observability.journey``): the
        # server's hop log; scheduler decisions (admit / preempt /
        # hand-off / offload promote) stamp hops for requests carrying
        # a JourneyContext.  NULL by default — zero cost when off.
        self.journeys = journeys if journeys is not None \
            else NULL_JOURNEY_LOG
        self.max_batch_size = max_batch_size
        self.block_size = block_size
        self.max_context = max_context
        if max_waiting is not None and max_waiting < 1:
            raise ValueError(
                f"max_waiting must be >= 1, got {max_waiting}")
        if chunk_size is not None and chunk_size < 1:
            raise ValueError(
                f"chunk_size must be >= 1, got {chunk_size}")
        self.max_waiting = max_waiting
        self.counters = counters
        self.prefix_cache = prefix_cache
        self.chunk_size = chunk_size
        self.overload = overload
        self.waiting: Deque[Request] = deque()
        self.running: Dict[int, Request] = {}      # slot -> request
        self._free_slots = list(range(max_batch_size - 1, -1, -1))
        self.finished: List[Request] = []
        # memory-observability tallies (``stats()["memory"]`` and the
        # flight recorder): lifetime preemptions and speculative
        # lookahead blocks granted / rolled back
        self.preemption_count = 0
        self.lookahead_granted = 0
        self.lookahead_rolled_back = 0
        # admission order among running requests — the preemption
        # victim is always the youngest (LIFO), which converges:
        # the oldest request monotonically keeps its blocks
        self._admit_order: List[Request] = []
        # in-flight hold (docs/serving.md, "Pipelined serve loop"):
        # requests whose launched device step has NOT been retired yet.
        # Their blocks are pinned — the pending program is still going
        # to write K/V through those tables, so preempting or failing
        # them out from under the launch would let the write land in
        # reallocated blocks.  The serve loop holds at launch and
        # releases at retire; audit() checks the pin.
        self.inflight: Dict[int, Request] = {}      # uid -> request

    # -- submission -------------------------------------------------------

    def submit(self, req: Request) -> Request:
        if len(req.prompt) == 0:
            raise ValueError("empty prompt")
        if req.max_new_tokens < 1:
            raise ValueError(
                f"max_new_tokens must be >= 1, got "
                f"{req.max_new_tokens}")
        if len(req.prompt) >= self.max_context:
            raise ValueError(
                f"prompt length {len(req.prompt)} must be < "
                f"max_context {self.max_context}")
        req.cost_blocks = BlockAllocator.blocks_for(
            len(req.prompt) + req.max_new_tokens, self.block_size)
        if self.max_waiting is not None \
                and len(self.waiting) >= self.max_waiting:
            # overload control: an arrival that outranks the worst
            # queued request displaces it (victim finishes "shed")
            # instead of being bounced by arrival order; an arrival
            # that outranks nobody is rejected exactly as before
            victim = (self._shed_candidate()
                      if self.overload is not None
                      and self.overload.displace else None)
            if victim is None or victim.priority <= req.priority:
                raise QueueFullError(
                    f"waiting queue full ({self.max_waiting} "
                    f"requests); request {req.uid} rejected")
            self.fail(victim, reasons.SHED)
        self.waiting.append(req)
        return req

    def withdraw_waiting(self) -> List[Request]:
        """Remove and return EVERY waiting request WITHOUT finishing
        it — the multi-replica router's failover/drain re-enqueue
        path (``serving.router``): queued work on a sick or draining
        replica has generated nothing yet, so it can restart on a
        healthy replica bit-identically instead of dying here.  The
        withdrawn requests hold no slots or blocks (waiting requests
        never do — :meth:`audit` pins that), so this is pure queue
        surgery; the caller owns re-submission and the terminal
        exactly-once guarantee."""
        out = list(self.waiting)
        self.waiting.clear()
        return out

    def _shed_candidate(self) -> Optional[Request]:
        """The waiting request overload policy would shed first:
        lowest priority class (highest number), newest among equals.
        None when the queue is empty."""
        if not self.waiting:
            return None
        return max(self.waiting, key=lambda r: (r.priority, r.uid))

    @property
    def num_waiting(self) -> int:
        return len(self.waiting)

    @property
    def num_running(self) -> int:
        return len(self.running)

    @property
    def has_work(self) -> bool:
        return bool(self.waiting or self.running)

    # -- overload pressure (``serving.overload``) --------------------------

    def pressure(self) -> float:
        """The overload signal: max of the queue fill fraction and
        ``(live blocks + queued demand + prefill backlog) / usable
        blocks``.  Queued demand is the sum of waiting requests'
        ``cost_blocks``, so a burst of expensive prompts reads as
        pressure before the pool physically fills; the value may
        exceed 1.0.

        The prefill backlog term prices the REMAINING chunk tokens of
        partially-prefilled running requests (their blocks are already
        live, but the compute to fill them is still queued) — without
        it a replica midway through a long chunked prefill looks idle
        to the router and keeps receiving placements it cannot start
        for many iterations (``serving.router``)."""
        q = (len(self.waiting) / self.max_waiting
             if self.max_waiting else 0.0)
        usable = self.allocator.cfg.num_blocks - 1
        reclaimable = self.allocator.num_free + (
            self.prefix_cache.num_evictable
            if self.prefix_cache is not None else 0)
        live = usable - reclaimable
        demand = sum(r.cost_blocks for r in self.waiting)
        demand += self.prefill_backlog_blocks()
        return max(q, (live + demand) / usable)

    def prefill_backlog_blocks(self) -> int:
        """Remaining-to-prefill tokens of running requests, in block
        equivalents — the compute-backlog term of :meth:`pressure`
        (those blocks are already allocated; this prices the work
        still owed to fill them)."""
        bs = self.block_size
        backlog = 0
        for r in self.running.values():
            if r.prefill_ctx is not None:
                rem = len(r.prefill_ctx) - r.num_cached
                if rem > 0:
                    backlog += -(-rem // bs)
        return backlog

    def shed_overload(self) -> List[Request]:
        """Shed best-effort waiting work (priority >=
        ``overload.best_effort_priority``), worst-first, while
        :meth:`pressure` sits at or above ``overload.shed_threshold``.
        Foreground (priority-0) work is never pressure-shed.  Called
        once per step by the serve loop; returns the shed requests
        (each finished ``"shed"`` via :meth:`fail`)."""
        if self.overload is None or not self.waiting:
            return []
        shed: List[Request] = []
        while self.pressure() >= self.overload.shed_threshold:
            candidates = [r for r in self.waiting
                          if self.overload.sheddable(r.priority)]
            if not candidates:
                break
            victim = max(candidates, key=lambda r: (r.priority, r.uid))
            self.fail(victim, reasons.SHED)
            shed.append(victim)
        return shed

    # -- allocation with cache pressure -----------------------------------

    def _try_alloc(self, n: int) -> Optional[List[int]]:
        """Allocate ``n`` blocks, evicting prefix-cache LRU holds as
        needed; None when the pool is genuinely dry (nothing free,
        nothing evictable)."""
        if n <= 0:
            return []
        while not self.allocator.can_alloc(n):
            # reclaim the whole deficit in ONE evict call: victims
            # demote to the offload tier (when attached) as a single
            # batched export, not one device gather per block
            deficit = n - self.allocator.num_free
            if self.prefix_cache is None \
                    or not self.prefix_cache.evict(max(1, deficit)):
                return None
            if self.tracer.enabled:
                self.tracer.instant("evict", blocks=max(1, deficit))
        return self.allocator.alloc(n)

    # -- iteration-level decisions ---------------------------------------

    def admit(self) -> List[Request]:
        """Fill free slots from the waiting queue (FIFO) while the
        pool can hold each candidate's prefill context plus one decode
        block.  Matched prefix blocks come shared from the cache; only
        the uncached tail needs fresh blocks (and one extra for a
        whole-context match's COW duplicate).  Returns the newly
        admitted requests, now in the prefilling state — the caller
        runs their chunks via :meth:`prefill_plan` (resolving any
        ``pending_cow`` first).

        A head request whose context can NEVER fit — it needs more
        blocks than the whole pool owns — is failed alone with
        ``finish_reason="capacity"`` and admission moves on to the
        next waiting request; one oversized request must not raise
        into the step loop or wedge the queue behind it."""
        admitted = []
        bs = self.block_size
        pool_blocks = self.allocator.cfg.num_blocks - 1
        while self.waiting and self._free_slots:
            req = self.waiting[0]
            ctx = self._prefill_context(req)
            need = BlockAllocator.blocks_for(len(ctx) + 1, bs)
            if need > pool_blocks:
                self.fail(req, reasons.CAPACITY)
                continue
            if self.prefix_cache is not None:
                with self.tracer.span("prefix_match", uid=req.uid,
                                      ctx_tokens=len(ctx)):
                    matched = self.prefix_cache.match(ctx)
                # hierarchical offload (docs/serving.md,
                # "Hierarchical KV offload"): where the device-tier
                # walk stopped, continue by content hash through the
                # host/disk store — promoted blocks re-materialize
                # into fresh device blocks (checksummed import) and
                # extend `matched` in place BEFORE the hit/cow/fresh
                # math below, so a three-tier hit plans its prefill
                # exactly like a device-tier hit of the same depth
                promoted = self.prefix_cache.promote(ctx, matched,
                                                     self._try_alloc)
            else:
                matched = []
                promoted = 0
            hit = len(matched) * bs
            # a whole-context match (len(ctx) block-aligned and every
            # block cached) still must recompute the last token's
            # logits — and its K/V write may not land in a shared
            # block, so the final matched block is duplicated COW
            cow = bool(matched) and hit >= len(ctx)
            fresh = self._try_alloc(need - len(matched) + (1 if cow else 0))
            if fresh is None:
                if matched:
                    self.prefix_cache.cancel(matched)
                break               # fits once running requests retire
            self.waiting.popleft()
            req.slot = self._free_slots.pop()
            if cow:
                req.pending_cow = (matched[-1], fresh[0])
                req.block_table = matched[:-1] + [fresh[0]] + fresh[1:]
                req.num_cached = len(ctx) - 1
            else:
                req.block_table = matched + fresh
                req.num_cached = hit
            req.cached_prefix_tokens = min(hit, len(ctx))
            req.prefill_ctx = ctx
            req.prefill_sample = not req.generated
            # matched full blocks are already indexed; start the
            # registration cursor past them.  A COW duplicate stays
            # private (its key belongs to the original), which breaks
            # the chain — registration stops for good (_REG_STOPPED)
            req._reg_blocks = _REG_STOPPED if cow else len(matched)
            self.running[req.slot] = req
            self._admit_order.append(req)
            admitted.append(req)
            if self.journeys.enabled and req.journey is not None:
                # offload promotion is part of THIS admission's story:
                # blocks re-materialized from the host/disk tier to
                # satisfy the prefix match (0 when the device tier
                # covered it) — recorded before the admit hop so the
                # journey reads promote -> admit in causal order
                if promoted:
                    self.journeys.hop(req.journey, "offload_promote",
                                      uid=req.uid, blocks=promoted)
                self.journeys.hop(req.journey, "admit", uid=req.uid,
                                  cached=req.cached_prefix_tokens)
            if self.prefix_cache is not None:
                c = self.prefix_cache.counters
                c.incr("prefix_hit_tokens", req.cached_prefix_tokens)
                c.incr("prefix_miss_tokens",
                       len(ctx) - req.cached_prefix_tokens)
                c.incr("prefix_hit_requests" if matched
                       else "prefix_miss_requests")
                if cow:
                    c.incr("prefix_cow_blocks")
        return admitted

    def _prefill_context(self, req: Request) -> List[int]:
        """The tokens whose K/V the prefill must materialize: the
        prompt, plus — after a preemption — every generated token
        except the pending one (see module docstring)."""
        if req.generated:
            return req.prompt + req.generated[:-1]
        return list(req.prompt)

    def cow_done(self, req: Request) -> None:
        """The engine finished duplicating ``pending_cow``; drop the
        admission's extra ref on the shared source block."""
        src, _ = req.pending_cow
        req.pending_cow = None
        self.allocator.free([src])

    def prefill_plan(self, req: Request) -> Tuple[List[int], int, bool]:
        """The next chunk of ``req``'s pending prefill:
        ``(tokens, start, is_last)`` with ``start`` the absolute
        position of ``tokens[0]`` (== K/V already materialized).
        ``chunk_size=None`` returns the whole remaining tail at once.
        The caller runs the chunk through the engine, then
        :meth:`chunk_done`."""
        ctx = req.prefill_ctx
        assert ctx is not None, "prefill_plan on a non-prefilling request"
        start = req.num_cached
        n = len(ctx) - start
        if self.chunk_size is not None:
            n = min(n, self.chunk_size)
        return ctx[start:start + n], start, start + n == len(ctx)

    def chunk_done(self, req: Request, n: int) -> bool:
        """Account ``n`` freshly prefilled tokens; registers any newly
        full blocks into the prefix index.  True = the prefill is
        complete and ``req`` joins the decode batch (the caller samples
        from the final chunk's logits when ``req.prefill_sample``)."""
        req.num_cached += n
        self.register_progress(req)
        if req.num_cached == len(req.prefill_ctx):
            req.prefill_ctx = None
            return True
        return False

    def register_progress(self, req: Request) -> None:
        """Index every newly FULL block of ``req`` (prefill chunks and
        decode steps crossing a block boundary).  Stops for good at the
        first chain collision — descendants of an unindexed block can
        never be matched."""
        if self.prefix_cache is None:
            return
        bs = self.block_size
        full = req.num_cached // bs
        seq = req.prompt + req.generated
        while req._reg_blocks < full:
            i = req._reg_blocks
            parent = req.block_table[i - 1] if i else ROOT
            if not self.prefix_cache.register(
                    parent, tuple(seq[i * bs:(i + 1) * bs]),
                    req.block_table[i]):
                req._reg_blocks = _REG_STOPPED  # chain broken for good
                break
            req._reg_blocks += 1

    # -- disaggregated prefill/decode hand-off (docs/serving.md) -----------

    @property
    def has_free_slot(self) -> bool:
        return bool(self._free_slots)

    def admit_handoff(self, req: Request, block_table: List[int]) -> None:
        """Admit a request whose context K/V is ALREADY materialized in
        this scheduler's pool — the decode half of the disaggregated
        prefill/decode hand-off (``docs/serving.md``, "Disaggregated
        prefill/decode").  ``block_table`` must hold blocks allocated
        from THIS scheduler's allocator (the caller copied the K/V in
        via the engine's block-copy program, or imported it from
        another replica).  The request skips the prefill state machine
        entirely: it enters the decode batch at its carried
        ``num_cached`` position with ``next_input`` pending — exactly
        the state a just-finished local prefill would leave it in, so
        greedy decode from here is bit-identical to the monolithic
        engine's."""
        assert self._free_slots, "admit_handoff with no free slot"
        assert req.num_cached > 0 and req.next_input is not None, \
            (f"handoff request {req.uid} has no carried KV position "
             f"(num_cached={req.num_cached}, "
             f"next_input={req.next_input})")
        req.slot = self._free_slots.pop()
        req.block_table = list(block_table)
        req.prefill_ctx = None
        req.cached_prefix_tokens = 0
        # the handed-off blocks' contents are the request's own
        # context, so they register into this pool's prefix index (when
        # one exists) exactly like locally-prefilled blocks would
        req._reg_blocks = 0 if self.prefix_cache is not None \
            else _REG_STOPPED
        self.running[req.slot] = req
        self._admit_order.append(req)
        if self.journeys.enabled and req.journey is not None:
            self.journeys.hop(req.journey, "admit", uid=req.uid,
                              handoff=True,
                              carried_tokens=req.num_cached)

    def release_handoff(self, req: Request) -> None:
        """Free a request's slot and blocks in THIS pool after its
        context was copied out to another pool/replica — the prefill
        half of the hand-off.  Newly full blocks register into the
        prefix index first, so a prefill pool doubles as a warm
        shared-prefix cache: the handed-off request's blocks survive
        here as evictable LRU holds and the next shared-prefix
        admission matches them instead of re-prefilling."""
        self.register_progress(req)
        if self.journeys.enabled and req.journey is not None:
            self.journeys.hop(req.journey, "handoff_export",
                              uid=req.uid,
                              carried_tokens=req.num_cached)
        self._release(req)

    def ensure_decode_capacity(self, req: Request) -> bool:
        """Grow ``req``'s block table if its next token write needs a
        fresh block — evicting idle prefix-cache holds first, then
        preempting younger requests while the pool stays dry.  False =
        ``req`` has outgrown the pool with nothing left to evict or
        preempt; the caller must fail it with
        ``finish_reason="capacity"`` — preempting it would livelock,
        and raising would take the whole batch down."""
        need_blocks = req.num_cached // self.block_size + 1
        while len(req.block_table) < need_blocks:
            fresh = self._try_alloc(1)
            if fresh is not None:
                req.block_table.extend(fresh)
                continue
            victim = self._preempt_victim(exclude=req)
            if victim is None:
                return False
            self.preempt(victim)
        return True

    def lookahead_capacity(self, req: Request, tokens: int) -> int:
        """Grow ``req``'s table OPPORTUNISTICALLY so up to ``tokens``
        tokens can write at positions ``num_cached..`` — the K-token
        speculation lookahead.  Unlike :meth:`ensure_decode_capacity`
        this never preempts: lookahead is an optimization, and taking
        another request's blocks to verify guesses would let a bad
        drafter degrade its neighbors.  Evicting idle prefix-cache
        holds (via :meth:`_try_alloc`) is allowed — the same reclaim
        decode growth makes.  Returns how many tokens actually fit
        (>= 1 once :meth:`ensure_decode_capacity` succeeded); the
        caller trims its draft to ``fit - 1``."""
        bs = self.block_size
        tokens = min(tokens, self.max_context - req.num_cached)
        while len(req.block_table) * bs - req.num_cached < tokens:
            fresh = self._try_alloc(1)
            if fresh is None:
                break
            req.block_table.extend(fresh)
            self.lookahead_granted += 1
        return max(0, min(tokens,
                          len(req.block_table) * bs - req.num_cached))

    def rollback_lookahead(self, req: Request) -> int:
        """KV rollback after a verify step: free table blocks holding
        ONLY rejected-suffix positions (everything past the block the
        next token writes into).  Those blocks were lookahead-fresh —
        allocated this iteration, never registered, refcount 1 — so
        freeing them is exact; the garbage K/V inside the kept partial
        block sits beyond ``num_cached`` where the context bias masks
        it until a future write overwrites it.  Returns the number of
        blocks released."""
        keep = req.num_cached // self.block_size + 1
        tail = req.block_table[keep:]
        if not tail:
            return 0
        del req.block_table[keep:]
        self.allocator.free(tail)
        self.lookahead_rolled_back += len(tail)
        return len(tail)

    # -- pipelined in-flight hold (docs/serving.md) ------------------------

    def hold_inflight(self, reqs: List[Request]) -> None:
        """Pin ``reqs`` for the duration of a launched-but-not-retired
        device step: until :meth:`release_inflight`, they may not be
        preempted (their pending K/V writes would land in reallocated
        blocks).  One launch window at a time — holding while a hold
        is live is a serve-loop sequencing bug."""
        assert not self.inflight, \
            "hold_inflight while a launch window is already held"
        for req in reqs:
            assert req.running, \
                f"in-flight hold on non-running request {req.uid}"
            self.inflight[req.uid] = req

    def release_inflight(self) -> None:
        """The launched step's results were consumed (or its launch
        failed before enqueue): the window's requests are ordinary
        running requests again."""
        self.inflight.clear()

    # -- sampling-param batching (docs/serving.md, "Stochastic sampling") --

    @staticmethod
    def _pack_sampling(by_slot, width: int) -> Tuple[np.ndarray, ...]:
        """``{slot: SamplingParams}`` -> the per-slot launch arrays
        ``(temperature f32, top_k i32, top_p f32, seed i32)``, each
        ``(width,)``.  Unlisted slots get temperature 0 — the in-trace
        greedy lane — so idle and greedy rows cost the argmax path
        they always did."""
        temp = np.zeros((width,), np.float32)
        tk = np.zeros((width,), np.int32)
        tp = np.ones((width,), np.float32)
        seed = np.zeros((width,), np.int32)
        for slot, s in by_slot.items():
            temp[slot] = s.temperature
            tk[slot] = 0 if s.top_k is None else int(s.top_k)
            tp[slot] = s.top_p
            seed[slot] = int(s.seed) & 0x7FFFFFFF
        return temp, tk, tp, seed

    def sampling_inputs(self, requests) -> Optional[Tuple]:
        """The per-slot :class:`SamplingParams` arrays for one batched
        decode/verify launch — part of the engine's ONE-``device_put``
        launch struct.  None when every request is greedy: the caller
        then launches the historical argmax-only program (zero
        stochastic-lane cost for default traffic)."""
        if all(r.sampling.is_greedy for r in requests):
            return None
        return self._pack_sampling(
            {r.slot: r.sampling for r in requests},
            self.max_batch_size)

    @staticmethod
    def prefill_sampling(req: Request) -> Optional[Tuple]:
        """The ``(1,)``-wide sampling arrays for one request's
        prefill/chunk launch (None = greedy, the historical
        program)."""
        if req.sampling.is_greedy:
            return None
        return Scheduler._pack_sampling({0: req.sampling}, 1)

    def frag_slots(self) -> int:
        """Allocated-but-unwritten token slots across running tables —
        each request's last partial block's slack plus any lookahead
        slack it holds this instant.  The fragmentation numerator of
        ``stats()["memory"]`` (``docs/observability.md``): these slots
        cost HBM but hold no K/V yet."""
        bs = self.block_size
        return sum(len(r.block_table) * bs - r.num_cached
                   for r in self.running.values())

    def _preempt_victim(self, exclude: Request) -> Optional[Request]:
        """Priority-aware victim choice: the worst priority class
        (highest number) among running requests, youngest-admitted
        within the class — so foreground work monotonically keeps its
        blocks while best-effort work recomputes.  With uniform
        priorities this is exactly the historical youngest-first
        (LIFO) choice, so preemption bit-stability is unchanged."""
        victim = None
        victim_key = None
        for i, req in enumerate(self._admit_order):
            if req is exclude:
                continue
            if req.uid in self.inflight:
                # a launched-but-not-retired request's blocks are
                # pinned: its pending device step still writes K/V
                # through them (docs/serving.md, "Pipelined serve
                # loop")
                continue
            key = (req.priority, i)
            if victim_key is None or key > victim_key:
                victim, victim_key = req, key
        return victim

    def preempt(self, req: Request) -> None:
        """Evict ``req`` to the waiting queue's FRONT (it has seniority
        over never-started requests), freeing its slot and blocks."""
        assert req.running, "can only preempt a running request"
        req.preemptions += 1
        self.preemption_count += 1
        if self.tracer.enabled:
            self.tracer.instant("preempt", uid=req.uid,
                                blocks=len(req.block_table))
        if self.journeys.enabled and req.journey is not None:
            self.journeys.hop(req.journey, "preempt", uid=req.uid,
                              blocks=len(req.block_table))
        self._release(req)
        req.num_cached = 0
        self.waiting.appendleft(req)

    def retire(self, req: Request) -> None:
        """Return a finished request's slot and blocks to the pools
        (registered blocks become evictable cache holds — the shared
        prefix outlives the request)."""
        assert req.finished, "retire() is for finished requests"
        self.register_progress(req)
        self._release(req)
        self.finished.append(req)

    def fail(self, req: Request, reason: str) -> None:
        """Finish ``req`` with ``finish_reason=reason`` wherever it is
        in its lifecycle (waiting or running), returning any held slot
        and blocks — the single exit for ``capacity`` / ``timeout`` /
        ``nonfinite`` isolation.  Tokens generated so far stay on the
        request (a timed-out request returns its partial output)."""
        assert not req.finished, "fail() is for live requests"
        if req.running:
            self._release(req)
        elif req in self.waiting:
            self.waiting.remove(req)
        req.finished = True
        req.finish_reason = reason
        self.finished.append(req)
        if self.counters is not None:
            self.counters.incr(f"requests_failed_{reason}")

    def _release(self, req: Request) -> None:
        if self.ring_rows:
            self.ring_rows_let_go += max(0, req.num_cached - self.ring_rows)
        del self.running[req.slot]
        self._admit_order.remove(req)
        self.inflight.pop(req.uid, None)
        self._free_slots.append(req.slot)
        req.slot = -1
        req.prefill_ctx = None
        req._reg_blocks = 0
        req.cached_prefix_tokens = 0
        if req.pending_cow is not None:
            # admission COW never executed (failed/preempted before the
            # engine ran): drop the extra ref on the shared source
            self.allocator.free([req.pending_cow[0]])
            req.pending_cow = None
        if req.block_table:
            self.allocator.free(req.block_table)
            req.block_table = []

    # -- invariants (tests + bench) ---------------------------------------

    def audit(self) -> None:
        """Refcount/free-list invariants, asserted after scheduler
        steps in tests and the bench smoke: every block's refcount
        equals the number of running tables referencing it (plus a
        pending COW's source hold), ref-0 blocks are exactly free XOR
        cache-held, the free list and free set mirror each other, and
        waiting requests hold nothing."""
        alloc = self.allocator
        table_refs: Dict[int, int] = {}
        for req in self.running.values():
            for b in req.block_table:
                table_refs[b] = table_refs.get(b, 0) + 1
            if req.pending_cow is not None:
                src = req.pending_cow[0]
                table_refs[src] = table_refs.get(src, 0) + 1
        for req in self.waiting:
            assert not req.block_table, \
                f"waiting request {req.uid} holds blocks"
            assert req.pending_cow is None
        # the pipelined launch window: every in-flight request must
        # still be running with its table intact — a preempted/failed/
        # retired request lingering in the hold means the pending
        # device step will write through blocks the scheduler already
        # recycled (docs/serving.md, "Pipelined serve loop")
        for uid, req in self.inflight.items():
            assert req.running and self.running.get(req.slot) is req, \
                f"in-flight request {uid} is no longer running"
            assert req.block_table, \
                f"in-flight request {uid} holds no blocks"
        free = set(alloc._free)
        assert len(alloc._free) == len(free) == len(alloc._free_set)
        assert free == alloc._free_set, "free list / free set diverged"
        held = (self.prefix_cache.held_blocks()
                if self.prefix_cache is not None else set())
        for b in range(1, alloc.cfg.num_blocks):
            r = alloc.refs(b)
            t = table_refs.get(b, 0)
            assert r == t, \
                f"block {b}: refcount {r} != {t} table references"
            if r == 0:
                assert (b in free) != (b in held), \
                    (f"ref-0 block {b}: free={b in free} "
                     f"held={b in held} (must be exactly one)")
            else:
                assert b not in free and b not in held, \
                    f"live block {b} also free/held"
        if self.prefix_cache is not None:
            self.prefix_cache.audit()
