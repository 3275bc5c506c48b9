"""`InferenceServer` — the synchronous front door of `apex_tpu.serving`.

Composes the device half (:class:`serving.engine.DecodeEngine`: jitted
prefill/decode over the block-pool KV cache) with the host half
(:class:`serving.scheduler.Scheduler`: iteration-level continuous
batching) into a step loop, and meters it (queue depth, running-batch
occupancy, tokens/s — ``utils.RateMeter``/``GaugeMeter``).

Telemetry (``docs/observability.md``): every meter lives in a shared
:class:`apex_tpu.observability.MetricsRegistry` (one snapshot /
Prometheus scrape covers the server), each request carries an
enqueue → admit → first-token → finish timeline feeding TTFT,
queue-wait, and per-token decode-latency histograms surfaced in
:meth:`InferenceServer.stats`, and — when tracing is on
(``APEX_TPU_TRACE``) — the step loop emits admit / prefix-match /
chunk-prefill / decode / evict / preempt spans plus request-lifecycle
and engine-compile instants into a Perfetto-loadable Chrome trace.

Deep observability (``docs/observability.md``): an opt-in step-level
flight recorder (``flight_recorder=`` / ``postmortem_dir=`` /
``APEX_TPU_POSTMORTEM``; zero-allocation null when off) captures one
structured record per iteration — batch composition,
admit/shed/preempt/evict decisions, memory occupancy, speculation
outcomes, pressure, breaker state — and postmortem bundles (flight
JSONL + metrics snapshot + Chrome trace) dump on demand
(:meth:`InferenceServer.dump_postmortem`), on breaker-open
transitions, and on :meth:`InferenceServer.audit` failure;
``stats()["slo"]`` tracks per-priority-class SLO attainment and
goodput vs throughput, and ``stats()["memory"]`` the KV pool's
free/live/evictable occupancy, high-watermarks, and fragmentation.

Ops plane (``docs/observability.md``, "Ops plane & watchdog"): an
opt-in loopback HTTP endpoint (``ops_port=`` / ``APEX_TPU_OPS_PORT``)
serves ``/healthz`` (status-code health a router can key on),
``/metrics`` (Prometheus text under the proper content type),
``/statusz`` (full ``stats()``), ``/debug/flight`` and
``/debug/requests/<uid>`` live slices, and loopback-authenticated
``POST /drain`` / ``POST /postmortem`` triggers; an opt-in
:class:`observability.HangWatchdog` turns step-loop silence into a
detection — thread stacks + postmortem bundle + a 503 ``/healthz`` —
exactly once per stall; and per-compiled-program accounting
(``stats()["programs"]``, on by default) tallies every engine launch
per program/shape key so "where does the step go" is answerable per
program, not just per phase.

Pipelined serve loop (``docs/serving.md``, "Pipelined serve loop"; ON
by default, ``enable_pipeline=False`` opts out): each :meth:`step`
first RETIRES the previous iteration's launched decode/verify results
(token ids + finite flags, sampled on device by the engine's fused
programs), then plans and LAUNCHES this iteration's programs without
materializing them — so host scheduling for step N+1 overlaps device compute for step N, and
the per-step device→host transfer is a ``(B,)`` int32 vector instead
of a ``(B, V)`` logits block.  Output is bit-identical to the
synchronous loop: greedy argmax is computed by the same rule on
device, every host-side decision (deadlines, admission, shedding,
preemption, drafts) happens AFTER the prior step's results are
applied — exactly the state the synchronous loop would have seen —
and ``submit()`` flushes the window first so front-door decisions
(breaker, displacement) never race the in-flight step.

``generate()`` is batch-synchronous (submit N prompts, run the loop to
completion, return N completions) — the shape every test and bench
needs.  A live service would run :meth:`step` on its event loop and
stream ``Request.generated`` as it grows; both drive the identical
scheduler/engine machinery, so the offline numbers transfer.

Serving-perf layers (ON by default; ``enable_prefix_cache=False`` /
``enable_speculation=False`` opt out): block-level prefix caching
shares cached full blocks at admission so only the uncached tail
prefills; every prompt is prefilled in chunks of ``prefill_chunk``,
ONE chunk per prefilling request per iteration, so a long prompt
stalls the decode batch by at most one chunk; and speculative
decoding drafts up to ``spec_tokens`` guesses per request per
iteration (zero-weight prompt-lookup by default), scores them in one
fixed-width verify launch, and accepts exactly the prefix matching
the model's own argmax — several tokens per engine step on
repetitive traffic, output bit-identical to one-token decode by
construction.  Hit/miss/eviction/COW counters, the per-iteration
chunk gauge, and the speculation acceptance counters/histograms
surface in :meth:`InferenceServer.stats` (``docs/serving.md``).

Failure isolation (``docs/resilience.md``): the step loop never lets
one pathological request take the batch down.  Per iteration it (1)
expires per-request deadlines (iteration or wall budget →
``finish_reason="timeout"``), (2) routes impossible-capacity requests
— never-fits prompts at admission, pool-outgrowers mid-flight — to
``finish_reason="capacity"``, and (3) evicts any request whose logits
went non-finite (``finish_reason="nonfinite"``) before sampling can
poison the rest of the batch.  A bounded waiting queue rejects at
submission (``finish_reason="rejected"``).  A transient engine
``MemoryError`` (an HBM allocation burst) skips the affected engine
call for one iteration and retries — same inputs, same logits, so
generation stays bit-stable — instead of killing the batch.  Every
failure is counted by reason in a
:class:`apex_tpu.utils.CounterMeter` surfaced through
:meth:`InferenceServer.stats`.

Overload control & lifecycle (``docs/resilience.md``, "Overload
policy & lifecycle"): requests carry a
``priority`` class and a block-cost estimate; under queue/pool
pressure the scheduler sheds the lowest-priority, newest waiting work
(``finish_reason="shed"``) and preempts worst-priority-first
(:mod:`serving.overload`).  A :class:`resilience.CircuitBreaker`
guards ``submit`` — after a streak of non-finite/OOM failures it
fast-rejects with ``finish_reason="breaker_open"`` until a half-open
probe succeeds.  :meth:`InferenceServer.drain` stops admissions
(``finish_reason="draining"``) and runs every in-flight request to a
terminal state — in-flight generation is bit-identical whether or not
a drain begins mid-stream — and :meth:`InferenceServer.close` drains
exactly once and makes further submission an error.
"""

from __future__ import annotations

import contextlib
import faulthandler
import os
import time
from collections import deque
from typing import Callable, Dict, List, Optional, Sequence, Union

import jax
import numpy as np

from apex_tpu.observability import (
    JOURNEYS_ENV,
    NULL_FLIGHT_RECORDER,
    NULL_JOURNEY_LOG,
    NULL_WATCHDOG,
    OPS_PORT_ENV,
    POSTMORTEM_ENV,
    FlightRecorder,
    HangWatchdog,
    JourneyLog,
    MetricsRegistry,
    OpsServer,
    ProgramAccounting,
    SLOPolicy,
    SLOTracker,
    dump_journeys,
    get_tracer,
    merge_journeys,
    resolve_journeys,
    write_postmortem,
)
from apex_tpu.ops.decode_attention import page_windows
from apex_tpu.ops.sampling import SamplingParams, sample_tokens_host
from apex_tpu.resilience.breaker import CircuitBreaker
from apex_tpu.serving.engine import DecodeEngine
from apex_tpu.serving.kv_cache import KV_QUANT_ENV, resolve_kv_quant
from apex_tpu.serving.offload import (
    KV_OFFLOAD_ENV,
    OffloadStore,
    resolve_kv_offload,
)
from apex_tpu.serving.overload import AdmissionEstimator, OverloadPolicy
from apex_tpu.serving.prefix_cache import PrefixCache
from apex_tpu.serving import reasons
from apex_tpu.serving.scheduler import QueueFullError, Request, Scheduler
from apex_tpu.serving.speculation import DraftSource, NgramDraft
from apex_tpu.serving.streaming import StreamBroker, TokenStream
from apex_tpu.serving.transport import (
    InProcessTransport,
    KVTransport,
    TransportPolicy,
)
from apex_tpu.utils import CounterMeter, GaugeMeter, RateMeter

# the stats() window for "tokens/s right now" (RateMeter.rate_over) —
# long enough to smooth step-to-step jitter, short enough that a
# traffic change shows up within seconds
RECENT_RATE_WINDOW_S = 10.0

# default chunked-prefill width (tokens) when the caller doesn't pick
# one: small enough that a chunk costs roughly a decode step at typical
# model sizes, large enough to amortize the per-chunk context gather
DEFAULT_PREFILL_CHUNK = 256

# the no-ops-plane lock stand-in: reusable, reentrant, allocation-free
# on entry — servers without an ops endpoint never take a real lock
_NO_LOCK = contextlib.nullcontext()

_NO_RID: dict = {}


def _rid(req) -> dict:
    """``rid=`` for a per-request span where journeys are on (it rides
    beside ``uid=``, which the ``request_*`` instants share)."""
    return {"rid": req.journey.rid} if req.journey is not None else _NO_RID

# default speculation depth (max drafted tokens per verify step).  The
# verify program is spec_tokens + 1 columns wide; deeper speculation
# multiplies the best-case tokens/step but also the wasted columns when
# drafts miss, and acceptance decays geometrically with depth — 4 is
# the classic knee (docs/serving.md, "K tuning")
DEFAULT_SPEC_TOKENS = 4


def _hist_ms(hist) -> dict:
    """Milliseconds view of a seconds histogram for ``stats()`` /
    bench JSON: count + p50/p90/p99 + max."""
    if hist.count == 0:
        return {"count": 0}
    return {"count": hist.count,
            "p50": round(hist.p50 * 1e3, 3),
            "p90": round(hist.p90 * 1e3, 3),
            "p99": round(hist.p99 * 1e3, 3),
            "max": round(hist.max * 1e3, 3)}


def _hist_counts(hist) -> dict:
    """Unscaled view of a count-valued histogram (speculation
    drafted/accepted depths): count + p50/p90 + mean + max."""
    if hist.count == 0:
        return {"count": 0}
    return {"count": hist.count,
            "p50": round(hist.p50, 2),
            "p90": round(hist.p90, 2),
            "mean": round(hist.sum / hist.count, 3),
            "max": round(hist.max, 2)}


def greedy_sample(logits: np.ndarray) -> np.ndarray:
    """(…, V) logits -> (…,) argmax token ids — deterministic, which
    is what makes cached decode testable token-for-token against the
    full-recompute forward.

    Ties break toward the LOWEST token id (``np.argmax`` returns the
    first maximum).  That tie rule is part of the bit-exactness
    contract speculative decoding relies on: greedy acceptance
    compares drafted tokens against the verify rows' argmax, so every
    argmax over equal logits must resolve the same way it would in a
    plain one-token decode step — including exact ties.

    Non-floating logits are rejected: an integer array here is almost
    always token ids passed where logits belong, and argmaxing ids
    "works" while silently decoding garbage."""
    logits = np.asarray(logits)
    if not np.issubdtype(logits.dtype, np.floating):
        raise TypeError(
            f"greedy_sample expects floating-point logits, got dtype "
            f"{logits.dtype} (token ids passed where logits belong?)")
    return np.argmax(logits, axis=-1)


class _InflightStep:
    """One launched-but-not-retired device step (the depth-1
    dispatch-ahead window): the requests it covers, the draft map and
    per-slot lengths (verify only), the un-materialized device arrays
    (token ids + finite flags), and the launch-time clock — the
    timestamp device-side failures are anchored to when they are
    observed a step later."""

    __slots__ = ("kind", "running", "drafts", "lengths", "ids",
                 "finite", "launched_at")

    def __init__(self, kind, running, ids, finite, launched_at,
                 drafts=None, lengths=None):
        self.kind = kind                  # "decode" | "verify"
        self.running = running
        self.ids = ids
        self.finite = finite
        self.launched_at = launched_at
        self.drafts = drafts
        self.lengths = lengths


class _Handoff:
    """One finished prefill waiting to move pools (``enable_disagg``):
    the request, plus — under pipelining — the un-materialized
    (token ids, finite flags) handles of its final chunk's fused
    sampling, consumed when the hand-off processes next step."""

    __slots__ = ("req", "handles")

    def __init__(self, req, handles=None):
        self.req = req
        self.handles = handles


class InferenceServer:
    """Batched GPT inference with KV-cache + continuous batching.

    Args (beyond :class:`DecodeEngine`'s, which pass through —
    including ``kv_quant="int8"``, the quantized KV pool with its
    per-slot per-head scale sidecar; ``APEX_TPU_KV_QUANT=int8`` is
    its env twin, the kwarg wins — ``docs/serving.md``, "Quantized
    KV cache"):
      max_waiting: bound on the waiting queue; a submit past it comes
        back already finished with ``finish_reason="rejected"``
        (explicit backpressure at the front door).
      clock: wall-deadline time source (monotonic seconds) —
        injectable so deadline tests never sleep.
      enable_prefix_cache: block-level prefix sharing at admission
        (:mod:`serving.prefix_cache`) — shared-prefix traffic skips
        re-prefilling cached full blocks.  Opt out for strictly
        private workloads or A/B baselines.
      prefill_chunk: the width in tokens of the one compiled chunk
        program (default ``min(256, max_context)``).  Every prompt is
        prefilled in chunks of it, one per iteration, so a long prompt
        stalls running decodes by at most one chunk.
      enable_speculation: speculative decoding with bit-exact greedy
        acceptance (``docs/serving.md``): each decode iteration,
        requests with a draft feed the pending token plus up to
        ``spec_tokens`` guesses through the fixed-width verify program
        and keep the longest prefix matching the model's own argmax,
        plus the model's next token — up to ``spec_tokens + 1`` tokens
        per engine step, bit-identical output by construction.
        Stochastic requests (``SamplingParams``) keep speculation ON
        via rejection sampling — acceptance compares drafts against
        each column's counter-keyed sample, so the output
        distribution (and, by the Gumbel-max coupling, the exact
        stream) is unchanged.  Opt out for strictly
        non-repetitive traffic where drafting is pure overhead.
      spec_tokens: max drafted tokens per verify step (default 4); the
        verify program is ``spec_tokens + 1`` columns wide and
        compiles once.
      enable_pipeline: the dispatch-ahead step loop
        (``docs/serving.md``, "Pipelined serve loop"): decode/verify
        steps launch the engine's fused on-device-sampling programs
        and their results are retired at the START of the next
        iteration, so host scheduling overlaps device compute and the
        per-step transfer is token ids, not logits.  Output is
        bit-identical to the synchronous loop (sampling — argmax or
        counter-keyed stochastic — is computed by the same rule on
        device; every host decision sees post-retire state).
        Stochastic requests keep the pipeline ON.  Opt out to restore
        the strictly serial loop.
      draft_source: the :class:`serving.speculation.DraftSource`
        proposing drafts (default: zero-weight
        :class:`~serving.speculation.NgramDraft` prompt-lookup over
        each request's own history; pass a small-model drafter to run
        classic two-model speculation — acceptance, and therefore
        output, is identical either way).
      overload_policy: the :class:`serving.overload.OverloadPolicy`
        driving priority-aware load shedding (queue-full
        displacement, pressure shedding of best-effort waiting work,
        worst-priority preemption).  Default: a policy with stock
        thresholds.
      breaker: the :class:`apex_tpu.resilience.CircuitBreaker`
        guarding ``submit`` (default: stock thresholds on the
        server's ``clock``); after a streak of non-finite/OOM
        failures submissions fast-reject with
        ``finish_reason="breaker_open"`` until a half-open probe
        completes.
      registry: the :class:`apex_tpu.observability.MetricsRegistry`
        holding every counter/gauge/histogram this server feeds
        (default: a fresh private one).  Pass a shared registry to
        co-scrape serving and training metrics from one snapshot.
      tracer: span tracer for the step-loop phases (``step`` over
        retire / apply / plan / chunk_prefill / draft / inputs /
        launch / account) and per-request lifecycle instants; default
        is the process tracer, which records while a ``jax.profiler``
        session is active or ``APEX_TPU_TRACE`` is set and is a no-op
        otherwise — ``docs/observability.md``.
      slo_policy: per-priority-class SLO targets
        (:class:`observability.SLOPolicy`) behind the
        ``stats()["slo"]`` attainment/goodput block; the stock policy
        has no latency bounds (attainment = healthy completion +
        deadline holds) — pin real TTFT/decode budgets per class to
        make goodput mean something (``docs/observability.md``,
        "SLO & goodput").
      flight_recorder: a
        :class:`observability.FlightRecorder` enabling step-level
        postmortem capture — one structured record per :meth:`step`
        (batch composition, admit/shed/preempt/evict decisions,
        memory occupancy, speculation outcomes, pressure, breaker
        state) in a bounded ring.  Default: a fresh recorder when
        ``postmortem_dir`` (or ``APEX_TPU_POSTMORTEM``) is set, else
        the zero-allocation ``NULL_FLIGHT_RECORDER``.
      postmortem_dir: where auto-dumped postmortem bundles land
        (breaker-open transitions, :meth:`audit` failures, watchdog
        stalls; chaos-soak invariant violations via
        :func:`resilience.chaos.run_soak`).
        ``APEX_TPU_POSTMORTEM=/dir`` is the env twin.  On-demand
        bundles go wherever :meth:`dump_postmortem` is pointed.
      watchdog: a :class:`observability.HangWatchdog` arming hang
        detection on this server's step loop: :meth:`step` feeds it
        heartbeats, and a step (or a step *gap* with work pending)
        exceeding the watchdog's deadline dumps every thread's stack
        plus a postmortem bundle (under ``postmortem_dir``, when
        set), flips the ops plane's ``/healthz`` to 503, and
        increments ``serving_watchdog_stalls`` — exactly once per
        stall.  Default: disabled at zero per-step cost
        (``NULL_WATCHDOG``).  The server installs its stall handler
        and starts the watchdog thread; :meth:`close` stops it.
      ops_port: turn on the embedded HTTP ops plane
        (:class:`observability.OpsServer`) on this loopback port
        (0 = ephemeral; the bound port is ``server.ops.port``):
        ``/healthz``, ``/metrics``, ``/statusz``,
        ``/debug/flight``, ``/debug/requests/<uid>``,
        ``POST /drain`` / ``/postmortem``.  Default: off
        (``APEX_TPU_OPS_PORT`` is the env twin).  While attached,
        :meth:`step` serializes against ops reads through the ops
        lock; without it the loop takes no lock at all.

      enable_disagg: disaggregated prefill/decode pools
        (``docs/serving.md``, "Disaggregated prefill/decode"; OFF by
        default): a second engine with its OWN KV pool runs every
        prefill (and hosts the prefix cache), and the main engine
        becomes a pure-decode pool — finished prefills hand their
        blocks over through the fixed-shape cross-pool block copy one
        step after their final chunk, so long-prompt bursts queue
        against prefill capacity instead of inflating the decode
        inter-token tail.  Output is bit-exact vs the monolithic
        loop; speculation, the pipelined loop, and stochastic
        sampling stay ON in the decode pool.
      disagg_prefill_blocks: the prefill pool's size in blocks
        (incl. its own garbage block 0); default
        ``prefill_max_concurrent`` full-context prefills + 1.  This
        is RESERVED capacity the decode batch cannot borrow — budget
        it from the same HBM the monolithic pool would have used.
      prefill_max_concurrent: prefill-pool scheduler slots — the
        bound on chunk launches per step, i.e. the prefill duty
        cycle protecting the decode cadence (default 2).
      handoff_sink: cross-replica hand-off hook
        (``(request, payload) -> bool``): when set, finished prefills
        export their blocks as a checksummed host payload
        (:meth:`DecodeEngine.export_blocks`) and the sink — normally
        ``ReplicaRouter.handoff_sink_for`` — places the decode half
        on another replica (:meth:`ingest_handoff`); True moves
        ownership (this server finishes its half
        ``finish_reason="handoff"``), False falls back to the LOCAL
        decode pool.
      enable_streaming: per-token delivery (docs/serving.md,
        "Streaming & cancellation"): a :class:`StreamBroker` fans
        every retired token out to :meth:`stream` consumers at the
        point it is applied, and :meth:`cancel` frees a request's
        blocks/holds mid-decode with ``finish_reason="cancelled"``
        (cancel works even with streaming disabled).  Default on —
        the broker is O(1) no-op work per token when nobody streams.
      stream_queue_tokens: per-stream bounded queue depth; a slower
        consumer drops the oldest queued notification (backfilled on
        the next read) instead of ever stalling ``step()``.
      enable_kv_offload: hierarchical KV offload (docs/serving.md,
        "Hierarchical KV offload"; OFF by default, env twin
        ``APEX_TPU_KV_OFFLOAD``): cold evictable prefix-cache blocks
        demote into a bounded host-RAM store — optionally spilling
        to ``kv_offload_dir`` with checksummed atomic writes —
        instead of dying at eviction, and promote back into fresh
        device blocks (checksummed ``import_blocks``) when a later
        admission's radix walk wants them, so a cache hit spans
        device -> host -> disk at fixed HBM.  Every integrity or
        capacity failure on the offload path falls back to cold
        prefill bit-identically.
      kv_offload_host_bytes: the host-RAM tier's byte bound
        (default 64 MiB); coldest entries past it spill or drop.
      kv_offload_dir: optional disk spill tier directory; surviving
        entries are re-adopted on construction (content-addressed).
      kv_transport: the KV transport backend (``docs/serving.md``,
        "KV transport") the offload promote path rides — a
        :class:`~apex_tpu.serving.transport.KVTransport`; default a
        fresh :class:`~apex_tpu.serving.transport.InProcessTransport`
        on this server's clock (behavior-identical to the direct
        import call it wraps).  The server registers its ``"offload"``
        ingest peer on it; ``stats()["transport"]`` reports the
        envelope counters either way.

    Example::

        server = InferenceServer(cfg, params, max_batch_size=8)
        outs = server.generate(prompts, max_new_tokens=64, eos_id=50256)
    """

    def __init__(self, cfg, params, *,
                 max_batch_size: int = 8,
                 max_context: Optional[int] = None,
                 num_blocks: Optional[int] = None,
                 block_size: int = 16,
                 cache_dtype=None,
                 kv_quant: Optional[str] = None,
                 mesh=None,
                 tp_rules=None,
                 tp_axis: str = "model",
                 max_waiting: Optional[int] = None,
                 clock: Callable[[], float] = time.monotonic,
                 enable_prefix_cache: bool = True,
                 prefill_chunk: Optional[int] = None,
                 enable_speculation: bool = True,
                 spec_tokens: Optional[int] = None,
                 draft_source: Optional[DraftSource] = None,
                 enable_pipeline: bool = True,
                 overload_policy: Optional[OverloadPolicy] = None,
                 breaker: Optional[CircuitBreaker] = None,
                 registry: Optional[MetricsRegistry] = None,
                 tracer=None,
                 slo_policy: Optional[SLOPolicy] = None,
                 flight_recorder: Optional[FlightRecorder] = None,
                 postmortem_dir: Optional[str] = None,
                 watchdog: Optional[HangWatchdog] = None,
                 ops_port: Optional[int] = None,
                 enable_disagg: bool = False,
                 disagg_prefill_blocks: Optional[int] = None,
                 prefill_max_concurrent: int = 2,
                 handoff_sink: Optional[Callable] = None,
                 enable_streaming: bool = True,
                 stream_queue_tokens: int = 256,
                 enable_kv_offload: Optional[bool] = None,
                 kv_offload_host_bytes: int = 64 << 20,
                 kv_offload_dir: Optional[str] = None,
                 kv_transport: Optional[KVTransport] = None,
                 enable_journeys: Optional[bool] = None,
                 journey_replica: str = "server"):
        self.registry = registry if registry is not None \
            else MetricsRegistry()
        self.tracer = tracer if tracer is not None else get_tracer()
        # flight recorder (docs/observability.md, "Flight recorder &
        # postmortems"): explicitly passed, or resolved on by a
        # postmortem destination, else the zero-allocation null
        self._postmortem_dir = (postmortem_dir
                                or os.environ.get(POSTMORTEM_ENV))
        if flight_recorder is not None:
            self.recorder = flight_recorder
        else:
            self.recorder = (FlightRecorder() if self._postmortem_dir
                             else NULL_FLIGHT_RECORDER)
        self.slo = SLOTracker(slo_policy, registry=self.registry)
        # per-compiled-program accounting (docs/observability.md,
        # "Ops plane & watchdog"): every engine launch feeds the
        # stats()["programs"] table; observation only
        self.programs = ProgramAccounting(registry=self.registry)
        # quantized KV pool (docs/serving.md, "Quantized KV cache"):
        # the APEX_TPU_KV_QUANT env twin turns it on fleet-wide
        # without touching call sites; a PROVIDED kwarg wins — None
        # means "not provided" (defer to the env), so a caller that
        # must stay full-width under any environment pins
        # kv_quant="off" (the bench's legacy arms do)
        if kv_quant is None:
            kv_quant = os.environ.get(KV_QUANT_ENV)
        self.kv_quant = resolve_kv_quant(kv_quant)
        # speculation (docs/serving.md): the verify program is
        # spec_tokens + 1 rows a sequence, which a state layer's ring
        # holds beside what a launch reads
        self.spec_tokens = int(spec_tokens if spec_tokens is not None
                               else DEFAULT_SPEC_TOKENS)
        if self.spec_tokens < 1:
            raise ValueError(
                f"spec_tokens must be >= 1, got {self.spec_tokens}")
        self.engine = DecodeEngine(
            cfg, params, max_batch_size=max_batch_size,
            max_context=max_context, num_blocks=num_blocks,
            block_size=block_size, cache_dtype=cache_dtype,
            kv_quant=self.kv_quant,
            tracer=self.tracer, programs=self.programs,
            mesh=mesh, tp_rules=tp_rules, tp_axis=tp_axis,
            verify_rows=self.spec_tokens + 1)
        self.failures = CounterMeter(registry=self.registry,
                                     name="serving_failures",
                                     label="reason")
        self.prefix = CounterMeter(registry=self.registry,
                                   name="serving_prefix", label="event")
        self.prefill_chunk = int(
            prefill_chunk if prefill_chunk is not None
            else min(DEFAULT_PREFILL_CHUNK, self.engine.max_context))
        # a model with window layers (``models/family.py`` (d)) keeps
        # their rows in a ring a slot: what a launch may feed is
        # bounded by it.  That, or state layers ((e)), a ring a slot
        # too: the prefix cache cannot keep a finished request's rows
        # or state, and no block mover carries a ring
        windowed = self.engine.max_fed_rows is not None
        ringed = self.engine.layers is not None
        if windowed:
            if prefill_chunk is None:
                self.prefill_chunk = min(self.prefill_chunk,
                                         self.engine.max_fed_rows)
            if self.prefill_chunk > self.engine.max_fed_rows:
                # (the engine holds a verify launch to the same bound)
                raise ValueError(
                    f"prefill_chunk={self.prefill_chunk} is more than a "
                    f"window layer's ring holds beside its window "
                    f"({self.engine.max_fed_rows} rows)")
        if ringed and (enable_disagg or resolve_kv_offload(
                enable_kv_offload if enable_kv_offload is not None
                else os.environ.get(KV_OFFLOAD_ENV))):
            raise NotImplementedError(
                "enable_disagg / enable_kv_offload move a request's "
                "blocks between pools, and "
                + ("a window layer's rows are" if windowed
                   else "a state layer's state is")
                + " in no block but in its slot's ring: the block "
                "movers do not carry it.  Open work: ROADMAP.md Reach.")
        self.overload_policy = (overload_policy
                                if overload_policy is not None
                                else OverloadPolicy())
        # predictive admission (docs/resilience.md): learns service
        # rates from finished timelines and sheds provably
        # deadline-doomed arrivals at the front door.  Gated on the
        # policy flag so the default server carries no estimator at
        # all — cold-start behavior is byte-identical either way.
        self.admission = (
            AdmissionEstimator(
                min_history=self.overload_policy.admission_min_history,
                margin=self.overload_policy.admission_margin)
            if self.overload_policy.predictive_admission else None)
        # disaggregated prefill/decode pools (docs/serving.md,
        # "Disaggregated prefill/decode"; OFF by default): a second
        # engine with its OWN KV pool runs every prefill, and the main
        # engine becomes a pure-decode pool — the two pools' programs
        # share no array, so their device compute never serializes
        # through a common pool version.  Finished prefills hand their
        # blocks to the decode pool via the fixed-shape cross-pool
        # block copy, one step after their final chunk launches.
        self.disagg = bool(enable_disagg)
        self.handoff_sink = handoff_sink
        self.prefill_engine = None
        self.prefill_scheduler = None
        self._handoff: "deque" = None
        if self.disagg:
            if prefill_max_concurrent < 1:
                raise ValueError(
                    f"prefill_max_concurrent must be >= 1, got "
                    f"{prefill_max_concurrent}")
            if disagg_prefill_blocks is None:
                # room for prefill_max_concurrent full-context
                # prefills plus the garbage block — the prefill pool's
                # slack doubles as the shared-prefix cache's home
                disagg_prefill_blocks = (
                    prefill_max_concurrent * self.engine.blocks_per_seq
                    + 1)
            if disagg_prefill_blocks < self.engine.blocks_per_seq + 1:
                raise ValueError(
                    f"disagg_prefill_blocks={disagg_prefill_blocks} "
                    f"cannot hold one full-context prefill "
                    f"({self.engine.blocks_per_seq} blocks + garbage)")
            self.prefill_engine = DecodeEngine(
                cfg, params, max_batch_size=1,
                max_context=self.engine.max_context,
                num_blocks=int(disagg_prefill_blocks),
                block_size=block_size, cache_dtype=cache_dtype,
                kv_quant=self.kv_quant,
                tracer=self.tracer, programs=self.programs,
                mesh=mesh, tp_rules=tp_rules, tp_axis=tp_axis)
        # the prefix cache lives with whichever pool runs prefills:
        # the prefill pool under disaggregation (its released blocks
        # become the warm shared-prefix cache), the single pool
        # otherwise
        cache_alloc = (self.prefill_engine.allocator if self.disagg
                       else self.engine.allocator)
        # no prefix hit is taken for a model with window or state
        # layers: a hit of P tokens needs the window layers' rows
        # P - window .. P - 1, which the ring of the request that made
        # them has let go, or the state layers' state at P, which the
        # ring of that request has moved past
        self.prefix_cache = (
            PrefixCache(cache_alloc, self.engine.block_size,
                        counters=self.prefix)
            if enable_prefix_cache and not ringed else None)
        # hierarchical KV offload (docs/serving.md, "Hierarchical KV
        # offload"; OFF by default): cold evictable prefix blocks
        # demote into a bounded host-RAM store (optionally spilling
        # to disk) instead of dying, and promote back through the
        # checksummed import_blocks path at admission-time cache
        # hits.  The APEX_TPU_KV_OFFLOAD env twin turns it on
        # fleet-wide; a PROVIDED kwarg wins (None = defer to env), so
        # legacy bench/chaos arms pin enable_kv_offload=False.  The
        # meters exist unconditionally (stats()/flight records are
        # shape-stable offload-on or -off); the store and the cache
        # attachment only when enabled.
        if enable_kv_offload is None:
            enable_kv_offload = os.environ.get(KV_OFFLOAD_ENV)
        self.kv_offload = resolve_kv_offload(enable_kv_offload)
        # KV transport (docs/serving.md, "KV transport"): the offload
        # promote path — the one cross-pool block movement a bare
        # server owns — rides the policy envelope (deadline / retry /
        # breaker / exactly-once dedup).  The default in-process
        # backend on the server's clock is behavior-identical to the
        # direct import call it wraps: zero extra RNG draws, zero
        # extra branches on the healthy path.  The ingest handler
        # resolves the cache-home engine at CALL time so chaos
        # wrappers installed post-construction intercept.
        self.kv_transport = kv_transport if kv_transport is not None \
            else InProcessTransport(policy=TransportPolicy(clock=clock))
        self.kv_transport.register_peer("offload", self._offload_ingest)
        self.offload = CounterMeter(registry=self.registry,
                                    name="serving_offload",
                                    label="event")
        self.offload_promote = self.registry.histogram(
            "serving_offload_promote_s")
        self.offload_store: Optional[OffloadStore] = None
        if self.kv_offload:
            if self.prefix_cache is None:
                raise ValueError(
                    "enable_kv_offload requires the prefix cache "
                    "(enable_prefix_cache=True) — the offload tiers "
                    "extend its radix index")
            self.offload_store = OffloadStore(
                host_bytes=kv_offload_host_bytes,
                spill_dir=kv_offload_dir,
                counters=self.offload)
            # export/import closures resolve the cache-home engine at
            # CALL time: under disagg the prefill pool is the cache
            # home, and chaos wrappers installed post-construction
            # (server.engine = ChaosEngine(...)) must intercept
            self.prefix_cache.attach_offload(
                self.offload_store,
                lambda ids: (self.prefill_engine if self.disagg
                             else self.engine).export_blocks(
                                 ids, per_block_crc=True),
                lambda ids, payload: self.kv_transport.send(
                    "offload",
                    {"op": "promote",
                     "blocks": [int(b) for b in ids]},
                    payload),
                counters=self.offload,
                promote_hist=self.offload_promote,
                clock=clock)
        # journey correlation plane (docs/observability.md, "Request
        # journeys & exemplars"; OFF by default): one JourneyLog per
        # server, labeled with this replica's name and wired to the
        # injected iteration counter + clock — hop ordering rides the
        # traveling JourneyContext, never wall clocks.  The
        # APEX_TPU_JOURNEYS env twin arms it fleet-wide; a PROVIDED
        # kwarg wins (None = defer to env).  Disabled keeps the
        # zero-allocation NULL log (tests/L0/test_journey.py pins it
        # with tracemalloc).
        if enable_journeys is None:
            enable_journeys = os.environ.get(JOURNEYS_ENV)
        self.journeys = (
            JourneyLog(replica=journey_replica,
                       iter_source=lambda: self._iter, clock=clock)
            if resolve_journeys(enable_journeys)
            else NULL_JOURNEY_LOG)
        self.scheduler = Scheduler(
            self.engine.allocator,
            max_batch_size=self.engine.max_batch_size,
            block_size=self.engine.block_size,
            max_context=self.engine.max_context,
            max_waiting=None if self.disagg else max_waiting,
            counters=self.failures,
            prefix_cache=None if self.disagg else self.prefix_cache,
            chunk_size=self.prefill_chunk,
            overload=self.overload_policy,
            tracer=self.tracer, journeys=self.journeys,
            ring_rows=self.engine.ring_rows or None)
        if self.disagg:
            self.prefill_scheduler = Scheduler(
                self.prefill_engine.allocator,
                max_batch_size=int(prefill_max_concurrent),
                block_size=self.engine.block_size,
                max_context=self.engine.max_context,
                max_waiting=max_waiting,
                counters=self.failures,
                prefix_cache=self.prefix_cache,
                chunk_size=self.prefill_chunk,
                overload=self.overload_policy,
                tracer=self.tracer, journeys=self.journeys)
            # ONE terminal ledger across both pools: a request finishes
            # exactly once, wherever it is, and every consumer of
            # scheduler.finished (finalize, soaks, benches) sees it
            self.prefill_scheduler.finished = self.scheduler.finished
            self._handoff = deque()
        self.handoffs = CounterMeter(registry=self.registry,
                                     name="serving_handoff",
                                     label="event")
        self.handoff_pending = GaugeMeter(registry=self.registry,
                                          name="serving_handoff_pending")
        # per-class request accounting for stats()["sampling"]
        # (greedy / temperature / top_k / top_p / top_k_top_p)
        self.sampling_classes = CounterMeter(
            registry=self.registry, name="serving_sampling_requests",
            label="class")
        self.clock = clock
        self.draft_source = (draft_source if draft_source is not None
                             else NgramDraft())
        self.speculating = bool(enable_speculation)
        # pipelined serve loop (docs/serving.md, "Pipelined serve
        # loop"): the fused programs sample on device, so the host
        # never materializes logits
        self.pipelining = bool(enable_pipeline)
        self._inflight: Optional[_InflightStep] = None
        self._verify_compiled = set()   # _compile_verify_beside's kinds
        self._pending_produced = 0   # retired outside step() (submit)
        self.pipe = CounterMeter(registry=self.registry,
                                 name="serving_pipeline", label="event")
        self.spec = CounterMeter(registry=self.registry,
                                 name="serving_speculation",
                                 label="event")
        # per-verify-step draft/accept depth distributions — token
        # counts, not seconds, so they get a count-shaped ladder
        # (1..64 at 2x: buckets 0/1, 2, 4, 8, ... — exact at small K)
        self.spec_drafted_hist = self.registry.histogram(
            "serving_spec_drafted_tokens", low=1.0, high=64.0)
        self.spec_accepted_hist = self.registry.histogram(
            "serving_spec_accepted_tokens", low=1.0, high=64.0)
        self.breaker_events = CounterMeter(registry=self.registry,
                                           name="serving_breaker",
                                           label="event")
        self.breaker = (breaker if breaker is not None
                        else CircuitBreaker(clock=clock,
                                            counters=self.breaker_events))
        if self.breaker.counters is None:
            # a caller-built breaker without its own counters reports
            # through the server's registry, so stats() reconciles
            self.breaker.counters = self.breaker_events
        self.oom = CounterMeter(registry=self.registry,
                                name="serving_oom", label="site")
        self._draining = False
        self._closed = False
        self._final_stats: Optional[dict] = None
        self.queue_depth = GaugeMeter(registry=self.registry,
                                      name="serving_queue_depth")
        self.pressure_gauge = GaugeMeter(registry=self.registry,
                                         name="serving_pressure")
        self.occupancy = GaugeMeter(registry=self.registry,
                                    name="serving_batch_occupancy")
        self.chunk_iters = GaugeMeter(   # chunk prefills per iteration
            registry=self.registry, name="serving_chunk_iters")
        self.tokens = RateMeter()
        # latency distributions fed by the per-request timelines
        # (enqueue -> admit -> first token -> finish) and the step loop
        hist = self.registry.histogram
        self.ttft = hist("serving_ttft_s")
        self.queue_wait = hist("serving_queue_wait_s")
        self.decode_latency = hist("serving_decode_token_s")
        # per-token inter-token-latency gaps (the wall gap before each
        # token after a request's first) — the per-TOKEN tail the
        # disaggregation bench floors, vs decode_latency's per-request
        # average (docs/observability.md, "SLO & goodput")
        self.itl = hist("serving_itl_s")
        self.step_time = hist("serving_step_s")
        # per-step phase-composition counts for the flight record
        # (prefill tokens vs decode tokens vs verify columns) — bound
        # to a dict only while a recorder is on, so the disabled path
        # stays allocation-free
        self._phase: Optional[dict] = None
        # pipeline overlap split (stats()["pipeline"]): retire-wait is
        # the host blocked on device results (device-bound time); plan
        # is the host's scheduling+launch work, which the device
        # overlaps when pipelining is on (host-bound time).  A
        # well-overlapped step costs ~max of the two, a serial step
        # their sum.
        self.retire_wait = hist("serving_retire_wait_s")
        self.plan_time = hist("serving_plan_s")
        # per-priority-class queue-wait distributions, materialized as
        # classes are first seen (labeled series of the same metric)
        self._queue_wait_prio: Dict[int, object] = {}
        # memory observability (docs/observability.md, "Memory
        # accounting"): per-step occupancy/fragmentation gauges — the
        # current/peak/avg view behind stats()["memory"]; the flight
        # recorder carries the per-step time series
        self.mem_live = GaugeMeter(registry=self.registry,
                                   name="serving_kv_live_blocks")
        self.mem_free = GaugeMeter(registry=self.registry,
                                   name="serving_kv_free_blocks")
        self.mem_evictable = GaugeMeter(
            registry=self.registry, name="serving_kv_evictable_blocks")
        self.mem_frag = GaugeMeter(registry=self.registry,
                                   name="serving_kv_frag_slots")
        self._iter = 0              # scheduler iterations served
        self._finalized = 0         # scheduler.finished timeline cursor
        self._rec_cursor = 0        # flight-recorder finished cursor
        self._last_breaker_state = self.breaker.state
        # hang watchdog (docs/observability.md, "Ops plane &
        # watchdog"): the server owns the stall handler — thread
        # stacks + postmortem bundle + counter — and the thread's
        # lifecycle; step() feeds heartbeats behind an
        # `enabled` guard, so the disabled default costs nothing
        self.watchdog = watchdog if watchdog is not None \
            else NULL_WATCHDOG
        self._watchdog_stalls = self.registry.counter(
            "serving_watchdog_stalls")
        if self.watchdog.enabled:
            self.watchdog.on_stall = self._on_watchdog_stall
            self.watchdog.start()
        # streaming delivery (docs/serving.md, "Streaming &
        # cancellation"): the broker fans retired tokens out to
        # per-request bounded queues on ITS OWN lock — never the ops
        # lock — so a slow consumer can't stall step()
        self.stream_broker: Optional[StreamBroker] = (
            StreamBroker(queue_tokens=stream_queue_tokens)
            if enable_streaming else None)
        # embedded HTTP ops plane: resolved off unless a port is
        # given (kwarg wins over APEX_TPU_OPS_PORT; 0 = ephemeral).
        # While attached, step()/stats() serialize through its lock.
        if ops_port is None:
            env_port = os.environ.get(OPS_PORT_ENV)
            if env_port not in (None, ""):
                ops_port = int(env_port)
        self.ops_requests = CounterMeter(registry=self.registry,
                                         name="serving_ops_requests",
                                         label="endpoint")
        self.ops: Optional[OpsServer] = None
        self._ops_lock = None
        if ops_port is not None:
            self.ops = OpsServer(self, port=ops_port,
                                 counters=self.ops_requests)
            self._ops_lock = self.ops.lock
            self.ops.start()

    # -- request lifecycle ------------------------------------------------

    def submit(self, prompt: Sequence[int], max_new_tokens: int,
               eos_id: Optional[int] = None, *,
               priority: int = 0,
               deadline_iters: Optional[int] = None,
               deadline_s: Optional[float] = None,
               sampling: Optional[SamplingParams] = None,
               journey=None) -> Request:
        """Enqueue one request.

        ``max_new_tokens`` must be >= 1 and a prompt that leaves no
        room to generate within ``max_context`` is rejected with
        :class:`ValueError` (never silently capped to a <= 0 budget);
        a budget that merely overshoots the remaining context is capped
        down to fit.  ``priority`` is nice-style (0 = default
        foreground class; larger = lower priority, sheddable under
        overload — :mod:`serving.overload`).  Optional
        ``deadline_iters`` / ``deadline_s`` expire the request to
        ``finish_reason="timeout"``.

        ``sampling``: per-request :class:`SamplingParams`
        (temperature / top-k / top-p / seed; default greedy,
        bit-identical to the historical argmax path).  Stochastic
        requests keep BOTH fast paths — speculation and the pipelined
        loop — and are deterministic per (prompt, params, seed)
        thanks to counter-based keys (``docs/serving.md``,
        "Stochastic sampling").

        A request can come back already finished instead of enqueued
        — always with ``finished_at`` stamped at submission and never
        entering the admission-latency histograms:
        ``finish_reason="rejected"`` (bounded queue full, no
        lower-priority work to displace), ``"breaker_open"`` (circuit
        breaker tripped), or ``"draining"`` (after :meth:`drain` /
        :meth:`close` began).  Submitting to a closed server raises
        :class:`RuntimeError`.  A queue-full submission may instead
        displace a lower-priority queued request, which then finishes
        ``"shed"`` during this call.

        ``journey``: an existing :class:`JourneyContext` to continue —
        the router passes the fleet-level context here on placement,
        failover re-enqueue, and torn-hand-off fallback so the
        request's hops keep one rid across replicas.  None (the
        default) starts a fresh journey keyed by the request ``uid``
        when journeys are enabled, and carries nothing when they are
        off."""
        with (self._ops_lock or _NO_LOCK), \
                self.tracer.span("submit") as span:
            req = self._submit(prompt, max_new_tokens, eos_id,
                               priority=priority,
                               deadline_iters=deadline_iters,
                               deadline_s=deadline_s,
                               sampling=sampling, journey=journey)
            span.set(uid=req.uid, **_rid(req))
            return req

    def _submit(self, prompt, max_new_tokens, eos_id, *, priority,
                deadline_iters, deadline_s, sampling=None,
                journey=None) -> Request:
        """The :meth:`submit` body (runs under the ops lock when the
        HTTP ops plane is attached)."""
        if self._closed:
            raise RuntimeError(
                "InferenceServer is closed; no further submissions")
        # retire any launched-but-unretired step BEFORE the front
        # door decides anything: the breaker state, displacement
        # victims, and queue pressure must reflect the results of the
        # step the device already ran — the same state the synchronous
        # loop would show this submission (docs/serving.md,
        # "Pipelined serve loop")
        if self._inflight is not None:
            self._pending_produced += self._flush_window()
        prompt = [int(t) for t in prompt]
        if int(max_new_tokens) < 1:
            raise ValueError(
                f"max_new_tokens must be >= 1, got {max_new_tokens}")
        cap = self.engine.max_context - len(prompt)
        if cap <= 0:
            raise ValueError(
                f"prompt length {len(prompt)} leaves no room to "
                f"generate within max_context={self.engine.max_context}")
        if sampling is not None and not isinstance(sampling,
                                                   SamplingParams):
            raise TypeError(
                f"sampling must be a SamplingParams (or None for "
                f"greedy), got {type(sampling).__name__}")
        req = Request(prompt=prompt,
                      max_new_tokens=min(int(max_new_tokens), cap),
                      eos_id=eos_id,
                      priority=int(priority),
                      deadline_iters=deadline_iters,
                      deadline_s=deadline_s,
                      submit_iter=self._iter,
                      submitted_at=self.clock(),
                      sampling=sampling if sampling is not None
                      else SamplingParams())
        self.sampling_classes.incr(req.sampling.klass)
        if self.journeys.enabled:
            # continue the router's context when one travels in, else
            # open a fresh journey keyed by this request's uid (the
            # bare-server case); the front-door hop lands even for
            # submissions turned away below — their journey is just
            # enqueue -> finish
            req.journey = (journey if journey is not None
                           else self.journeys.start(req.uid))
            self.journeys.hop(req.journey, "enqueue", uid=req.uid,
                              prompt_tokens=len(prompt),
                              priority=req.priority)
        if self.tracer.enabled:
            if req.journey is not None:
                self.tracer.instant("request_enqueue", uid=req.uid,
                                    prompt_tokens=len(prompt),
                                    priority=req.priority,
                                    rid=req.journey.rid)
            else:
                self.tracer.instant("request_enqueue", uid=req.uid,
                                    prompt_tokens=len(prompt),
                                    priority=req.priority)
        if self._draining:
            return self._finish_at_submit(req, reasons.DRAINING)
        if not self.breaker.allow():
            return self._finish_at_submit(req, reasons.BREAKER_OPEN)
        # predictive admission: a wall-deadlined arrival that cannot
        # meet its deadline even at the fastest service ever observed
        # for its class is shed HERE, before any prefill is spent on
        # it (docs/resilience.md, "Overload policy & lifecycle")
        if self.admission is not None and self.admission.doomed(req):
            return self._finish_at_submit(req, reasons.SHED)
        try:
            # under disaggregation every request enters through the
            # prefill pool's queue; the decode pool only ever admits
            # via the hand-off
            (self.prefill_scheduler if self.disagg
             else self.scheduler).submit(req)
        except QueueFullError:
            return self._finish_at_submit(req, reasons.REJECTED)
        # a displaced victim may have finished "shed" inside
        # scheduler.submit: stamp its finished_at at submission time
        if self._finalized < len(self.scheduler.finished):
            self._finalize_finished()
        return req

    def _finish_at_submit(self, req: Request, reason: str) -> Request:
        """Finish ``req`` without ever enqueueing it (rejected /
        breaker_open / draining): terminal reason set, failure
        counted, and ``finished_at`` stamped NOW — submit-time
        rejections must not wait for the next step to close their
        timeline, and being never-admitted they stay out of the
        TTFT/queue-wait histograms."""
        req.finished = True
        req.finish_reason = reason
        self.scheduler.finished.append(req)
        self.failures.incr(f"requests_failed_{reason}")
        self._finalize_finished()
        return req

    def _expire_deadlines(self) -> None:
        """Fail every live request whose iteration or wall budget is
        spent — waiting requests too, so a queue stall cannot hold a
        request past its deadline (both pools under disaggregation)."""
        now = self.clock()
        for sched in self._schedulers():
            for req in (list(sched.waiting)
                        + list(sched.running.values())):
                if req.finished:
                    continue
                over_iters = (req.deadline_iters is not None and
                              self._iter - req.submit_iter
                              > req.deadline_iters)
                over_wall = (req.deadline_s is not None and
                             now - req.submitted_at >= req.deadline_s)
                if over_iters or over_wall:
                    sched.fail(req, reasons.TIMEOUT)

    def _schedulers(self):
        """Every live scheduler — ``(decode, prefill)`` under
        disaggregation, the single one otherwise."""
        if self.disagg:
            return (self.scheduler, self.prefill_scheduler)
        return (self.scheduler,)

    @property
    def has_work(self) -> bool:
        """Queued, running, launched-but-unretired, or
        pending-hand-off work anywhere on this server (both pools
        under disaggregation)."""
        if self.scheduler.has_work or self._inflight is not None:
            return True
        if self.disagg:
            return (self.prefill_scheduler.has_work
                    or bool(self._handoff))
        return False

    def pressure(self) -> float:
        """The server-level overload signal a router balances on: the
        max over this server's pools (``Scheduler.pressure``) — under
        disaggregation a saturated prefill pool reads as pressure even
        while the decode pool idles, and vice versa."""
        p = self.scheduler.pressure()
        if self.disagg:
            p = max(p, self.prefill_scheduler.pressure())
        return p

    def step(self) -> int:
        """One continuous-batching iteration: retire the previous
        iteration's launched decode/verify results (pipelined loop),
        expire deadlines, admit newly schedulable requests, advance
        ONE prefill chunk per prefilling request, then one decode step
        across the rest of the running batch — LAUNCHED without
        materialization when pipelining is on (its tokens retire at
        the start of the next step), sampled synchronously otherwise.
        Chunk prefills interleave with decode iterations, so a long
        prompt stalls running requests by at most one chunk — and a
        prefix-cache hit skips straight to its uncached tail.  Returns
        the number of tokens applied to requests this call (0 = idle,
        though chunk prefills may still have run; under pipelining a
        token counts when it is RETIRED, one step after its launch).
        Per-request failures (capacity / timeout / nonfinite / shed)
        finish the affected request alone, and a transient engine
        ``MemoryError`` skips the affected call for one iteration
        (retried bit-identically) — no exception escapes the step
        loop for them.

        Ops-plane integration (``docs/observability.md``, "Ops plane
        & watchdog"): an armed watchdog gets a heartbeat pair around
        every step — attribute stores, guarded out entirely when
        disabled — and, when the HTTP ops plane is attached, the step
        body runs under the ops lock so ``/statusz`` and the POST
        triggers read consistent state; a server without an ops plane
        takes no lock at all."""
        wd = self.watchdog
        if wd.enabled:
            wd.step_started()
        try:
            with (self._ops_lock or _NO_LOCK):
                if self.disagg:
                    return self._step_disagg()
                # the span tree (docs/observability.md, "What is
                # instrumented"): every statement of the step runs
                # under exactly one child of ``step``, so an idle gap
                # on the device can be put down to the phase that held
                # the host
                with self.tracer.span("step", iter=self._iter + 1):
                    return self._step()
        finally:
            if wd.enabled:
                wd.step_finished(self.scheduler.has_work)

    def _step(self) -> int:
        """The :meth:`step` body of the monolithic (single-pool)
        server, under its ``step`` span (see :meth:`step`)."""
        sched, engine, tr = self.scheduler, self.engine, self.tracer
        rec = self.recorder
        self._iter += 1
        produced, self._pending_produced = self._pending_produced, 0
        step_start = self.clock()
        self._phase = marks = None
        if rec.enabled:
            # pre-step marks for the flight record's per-step deltas
            # (plain int reads — the disabled path skips even these)
            with tr.span("account"):
                marks = (sched.preemption_count, sched.lookahead_granted,
                         sched.lookahead_rolled_back,
                         self.prefix.count("prefix_evicted_blocks"),
                         self.oom.total,
                         self.spec.count("drafted_tokens"),
                         self.spec.count("accepted_tokens"),
                         self._offload_marks())
                self._phase = self._new_phase()
        # RETIRE: consume the previous iteration's launched step before
        # any host decision — deadlines, shedding, admission, and
        # drafts below then see exactly the state the synchronous loop
        # would have had at this point (docs/serving.md, "Pipelined
        # serve loop")
        retired = self._flush_window()
        produced += retired
        plan_start = self.clock()
        with tr.span("plan"):
            self._expire_deadlines()

            # overload: record the pressure signal at its pre-shed
            # peak, then shed best-effort waiting work while the
            # policy says so
            self.pressure_gauge.update(sched.pressure())
            shed = sched.shed_overload()

            admitted = self._admit(sched, engine)

        chunks = 0
        pipelined = self.pipelining
        for req in [r for r in sched._admit_order if r.prefilling]:
            with self._launch_chunk(sched, engine, req) as (out, done):
                if out is None:
                    continue          # out of memory: replays next step
                chunks += 1
                if not done or not req.prefill_sample:
                    # mid-prefill, or resumed after preemption (the
                    # pending token continues instead of these logits)
                    continue
                # prefill sampling stays synchronous either way — the
                # sampled twin just shrinks the transfer to one id +
                # one flag; only decode/verify dispatch ahead (a
                # prefill's token gates whether the request joins THIS
                # iteration's decode launch, so deferring it would
                # change scheduling)
                with tr.span("prefill_read", uid=req.uid):
                    # the host waits for the device here
                    if pipelined:
                        ids, fin = out
                        finite = bool(np.asarray(fin)[0])
                        if finite:
                            tok = int(np.asarray(ids)[0])
                    else:
                        logits = np.asarray(out)
                        finite = np.all(np.isfinite(logits))
                        if finite:
                            tok = self._sample_prefill_host(req, logits)
                if not finite:
                    sched.fail(req, reasons.NONFINITE)
                    self.breaker.record_failure()
                    continue
                req.record_token(tok)
                self._note_first_token(req)
                produced += 1
                if req.finished:
                    sched.retire(req)
                    self.breaker.record_success()

        if sched.running:
            with tr.span("plan"):
                for req in list(sched.running.values()):
                    if req.running and not req.prefilling:
                        # an earlier pass may have preempted it; a
                        # False return means the request outgrew the
                        # pool with no victim left — it fails alone
                        # instead of raising into the batch
                        if not sched.ensure_decode_capacity(req):
                            sched.fail(req, reasons.CAPACITY)
                running = [r for r in sched.running.values()
                           if not r.prefilling]
            if running:
                with tr.span("draft"):
                    drafts = (self._propose_drafts(running)
                              if self.speculating else {})
                if pipelined:
                    # LAUNCH: enqueue the device step and stash the
                    # un-materialized result handles; its tokens
                    # retire at the start of the next step() (or at
                    # the next submit(), whichever comes first)
                    if drafts:
                        self._launch_verify(running, drafts)
                    else:
                        self._launch_decode(running)
                elif drafts:
                    produced += self._verify_step(running, drafts)
                else:
                    produced += self._decode_step(running)

        # everything below runs while the device works on the launch
        with tr.span("account"):
            self._account_step(produced, retired, chunks, admitted,
                               shed, plan_start, step_start, marks)
        return produced

    def _admit(self, sched, engine) -> List[Request]:
        """Admit what ``sched`` can take now: stamp ``admitted_at``,
        emit the ``request_admit`` instants, and launch the
        copy-on-write of whole-context cache hits on ``engine``.
        Returns the admitted requests."""
        tr = self.tracer
        with tr.span("admit"):
            admitted = sched.admit()
        if admitted:
            now = self.clock()
            for req in admitted:
                if req.admitted_at is None:
                    req.admitted_at = now
                if tr.enabled:
                    tr.instant("request_admit", uid=req.uid,
                               cached_tokens=req.cached_prefix_tokens)
        # whole-context cache hits first duplicate their final shared
        # block (copy-on-write) so the tail re-write stays private
        cows = [r for r in sched._admit_order if r.pending_cow]
        if cows:
            try:
                with tr.span("cow_copy", blocks=len(cows)):
                    engine.copy_blocks([r.pending_cow for r in cows])
            except MemoryError:
                # transient HBM burst: nothing was accounted, the same
                # copies re-launch next iteration bit-identically
                self._note_oom("copy_blocks")
            else:
                for req in cows:
                    sched.cow_done(req)
        return admitted

    @contextlib.contextmanager
    def _launch_chunk(self, sched, engine, req):
        """Launch ``req``'s next prefill chunk on ``engine``: plan it
        (``plan`` span), then, under the request's ``chunk_prefill``
        span, launch the sampled or the logits twin, count the phase
        and tell ``sched`` the chunk is in (``chunk_done``).  Yields
        ``(handles, done)`` INSIDE that span, so what the caller does
        with a finished prompt (``_step`` reads its token at once) is
        timed as the request's share of the step: the launch's
        un-materialized result (token id and finite flag, or logits
        in the synchronous loop) and whether the prompt is all in.
        ``handles`` is None where the launch ran out of memory:
        ``chunk_done`` was not called, so this exact chunk replays
        next iteration and generation stays bit-stable."""
        tr, pipelined = self.tracer, self.pipelining
        with tr.span("plan", uid=req.uid):
            tokens, start, is_last = sched.prefill_plan(req)
            # the per-request stochastic params ride the fused twin
            # only when this launch's token will actually be sampled
            # (final chunk of a fresh prefill) — mid-prefill chunks and
            # preemption re-prefills keep the greedy program
            samp = (sched.prefill_sampling(req)
                    if pipelined and is_last and req.prefill_sample
                    else None)
            # kwarg omitted when greedy so duck-typed engine wrappers
            # predating the stochastic twins keep working
            skw = {"sampling": samp} if samp is not None else {}
        with tr.span("chunk_prefill", uid=req.uid, tokens=len(tokens),
                     start=start, **_rid(req),
                     **self._kv_windows("chunk_prefill", [start],
                                        self.prefill_chunk)):
            # whose rings a model's window and state layers use
            ring = {"slot": req.slot} if engine.layers is not None else {}
            try:
                out = (engine.chunk_prefill_sampled(
                    tokens, start, req.block_table,
                    pad_to=self.prefill_chunk, **skw, **ring) if pipelined
                    else engine.chunk_prefill(
                        tokens, start, req.block_table,
                        pad_to=self.prefill_chunk, **ring))
            except MemoryError:
                self._note_oom("prefill")
                out, done = None, False
            else:
                if self._phase is not None:
                    self._phase["prefill_launches"] += 1
                    self._phase["prefill_tokens"] += len(tokens)
                done = sched.chunk_done(req, len(tokens))
            yield out, done

    def _account_step(self, produced, retired, chunks, admitted, shed,
                      plan_start, step_start, marks) -> None:
        """The step's meters, gauges, finished-request stamps and
        flight record (``marks``: the recorder's pre-step readings)."""
        sched, engine, rec = self.scheduler, self.engine, self.recorder
        self.chunk_iters.update(chunks)
        if chunks:
            self.prefix.incr("prefill_chunks", chunks)
        if self.pipelining:
            self.plan_time.record(self.clock() - plan_start)
        self.tokens.update(produced)
        self.queue_depth.update(sched.num_waiting)
        self.occupancy.update(sched.num_running
                              / self.engine.max_batch_size)
        step_s = self.clock() - step_start
        self.step_time.record(step_s)
        self._finalize_finished()
        # memory occupancy gauges (docs/observability.md, "Memory
        # accounting") — sampled once per step like queue depth
        alloc = engine.allocator
        self.mem_live.update(alloc.num_live)
        self.mem_free.update(alloc.num_free)
        self.mem_evictable.update(
            self.prefix_cache.num_evictable
            if self.prefix_cache is not None else 0)
        self.mem_frag.update(sched.frag_slots())
        if rec.enabled:
            (preempt0, lk_grant0, lk_roll0, evict0, oom0, drafted0,
             accepted0, off0) = marks
            fin = sched.finished
            new_fin = fin[self._rec_cursor:]
            finished_now = [
                {"uid": r.uid, "reason": r.finish_reason,
                 "tokens": len(r.generated)}
                for r in new_fin]
            self._rec_cursor = len(fin)
            step_rec = {
                "iter": self._iter,
                "produced": produced,
                "waiting": sched.num_waiting,
                "running": [r.uid for r in sched._admit_order],
                "prefilling": [r.uid for r in sched._admit_order
                               if r.prefilling],
                "admitted": [r.uid for r in admitted],
                "shed": [{"uid": r.uid, "priority": r.priority,
                          "debt_tokens":
                          OverloadPolicy.slo_debt_tokens(r)}
                         for r in shed],
                "finished": finished_now,
                "preemptions": sched.preemption_count - preempt0,
                "evicted_blocks":
                    self.prefix.count("prefix_evicted_blocks") - evict0,
                "oom": self.oom.total - oom0,
                "spec": {
                    "drafted":
                        self.spec.count("drafted_tokens") - drafted0,
                    "accepted":
                        self.spec.count("accepted_tokens") - accepted0,
                },
                "pressure": round(self.pressure_gauge.val, 4),
                "breaker": self.breaker.state,
                "memory": {
                    "free": alloc.num_free,
                    "live": alloc.num_live,
                    "evictable": (self.prefix_cache.num_evictable
                                  if self.prefix_cache is not None
                                  else 0),
                    "frag_slots": sched.frag_slots(),
                    "lookahead_granted":
                        sched.lookahead_granted - lk_grant0,
                    "lookahead_rolled_back":
                        sched.lookahead_rolled_back - lk_roll0,
                },
                "pipeline": {
                    "pending": 1 if self._inflight is not None else 0,
                    "retired_tokens": retired,
                },
                "offload": self._offload_delta(off0),
                "phase": self._phase,
                "step_s": step_s,
            }
            if self.journeys.enabled:
                # journey correlation: uid -> rid for every request
                # this step touched (admitted or finished), so a
                # flight record joins onto journeys/traces without a
                # per-uid search.  Conditional — journey-less flight
                # records keep the legacy shape byte-for-byte.
                step_rec["rids"] = {
                    str(r.uid): r.journey.rid
                    for r in list(admitted) + new_fin
                    if r.journey is not None}
            rec.record(step_rec)
            self._phase = None
        # breaker-open transition: the moment worth a black box — dump
        # a bundle while the ring still holds the steps leading up
        state = self.breaker.state
        if state != self._last_breaker_state:
            self._last_breaker_state = state
            if state == "open":
                self._auto_postmortem("breaker_open")

    def _sample_prefill_host(self, req, logits) -> int:
        """Sample one request's prefill token from materialized
        ``(V,)`` logits — the synchronous loop's half of the sampling
        contract.  Greedy requests take the host argmax
        (:func:`greedy_sample`); stochastic requests draw through the
        SAME
        jitted :func:`ops.sample_tokens` the fused programs use, with
        the same counter key (the token's sequence index ==
        ``num_cached`` after the final chunk accounted), so the two
        loops emit identical streams."""
        if req.sampling.is_greedy:
            return int(greedy_sample(logits))
        samp = self.scheduler.prefill_sampling(req)
        counter = np.asarray([req.num_cached], np.int32)
        ids, _fin = sample_tokens_host(logits[None], *samp, counter)
        return int(np.asarray(ids)[0])

    @staticmethod
    def _new_phase() -> dict:
        """A fresh per-step phase-composition record (the flight
        record's ``phase`` block): launches issued per program family
        this step and the tokens/columns each fed — the direct
        interference view (prefill tokens vs decode tokens vs verify
        columns per step) that ``tools/postmortem.py`` renders and
        ``--assert-complete`` reconciles against
        ``stats()["programs"]``."""
        return {"prefill_launches": 0, "prefill_tokens": 0,
                "decode_launches": 0, "decode_tokens": 0,
                "verify_launches": 0, "verify_columns": 0,
                "handoff_blocks": 0}

    # per-step offload deltas for the flight record (docs/serving.md,
    # "Hierarchical KV offload") — the tier-crossing view per
    # iteration, same mark/delta pattern as evicted_blocks/oom above
    _OFFLOAD_EVENTS = ("demotes", "promotes_host", "promotes_disk",
                       "spills", "crc_rejects")

    def _offload_marks(self) -> tuple:
        c = self.offload.count
        return tuple(c(k) for k in self._OFFLOAD_EVENTS)

    def _offload_delta(self, marks: tuple) -> dict:
        c = self.offload.count
        return {k: c(k) - m
                for k, m in zip(self._OFFLOAD_EVENTS, marks)}

    def _kv_windows(self, program, starts, rows) -> dict:
        """A launch's ``kv_windows`` and ``kv_windows_table``, only
        while the tracer records and where ``program`` attends through
        the block table: the windows the page loop of the layers that
        keep every token visits, summed over slots and row tiles, and
        those a grid over the whole table would have run
        (``ops.decode_attention.page_windows``)."""
        engine = self.engine
        if not self.tracer.enabled or getattr(
                engine, "attention_paths", {}).get(program) != "table":
            return {}
        row = engine.row
        visited, table = page_windows(
            starts, rows, engine.blocks_per_seq,
            block_size=engine.block_size, heads=row.heads,
            heads_per_group=row.heads_per_group,
            latent=row.kind == "latent")
        return {"kv_windows": visited, "kv_windows_table": table}

    def _decode_inputs(self, running):
        """The decode launch arrays — (tokens, positions, tables),
        inactive slots zeroed — shared by the synchronous and
        pipelined paths."""
        engine = self.engine
        b, mb = engine.max_batch_size, engine.blocks_per_seq
        tokens = np.zeros((b,), np.int32)
        positions = np.zeros((b,), np.int32)
        tables = np.zeros((b, mb), np.int32)
        for req in running:
            tokens[req.slot] = req.next_input
            positions[req.slot] = req.num_cached
            tables[req.slot, :len(req.block_table)] = req.block_table
        return tokens, positions, tables

    def _decode_step(self, running) -> int:
        """One batched single-token decode over ``running``,
        materialized and applied in the same call (the synchronous
        loop).  Returns tokens produced."""
        engine, tr = self.engine, self.tracer
        with tr.span("inputs", program="decode"):
            tokens, positions, tables = self._decode_inputs(running)
        try:
            with tr.span("decode", batch=len(running)):
                logits = np.asarray(
                    engine.decode(tokens, positions, tables))
        except MemoryError:
            # transient HBM burst: no request state moved, the
            # identical decode re-runs next iteration
            self._note_oom("decode")
            return 0
        with tr.span("apply", program="decode"):
            self.spec.incr("decode_steps")
            if self._phase is not None:
                self._phase["decode_launches"] += 1
                self._phase["decode_tokens"] += len(running)
            finite = np.all(np.isfinite(logits), axis=-1)
            samp = self.scheduler.sampling_inputs(running)
            if samp is None:
                toks = greedy_sample(logits)
            else:
                # the synchronous stochastic path: the SAME jitted
                # sampler as the fused twin, fed the same counter keys
                # (each slot's next sequence index), so sync and
                # pipelined streams agree byte-for-byte
                counters = np.zeros((logits.shape[0],), np.int32)
                for req in running:
                    counters[req.slot] = req.num_cached + 1
                toks = np.asarray(sample_tokens_host(
                    logits, *samp, counters)[0])
            return self._apply_decode_results(running, toks, finite)

    def _launch_decode(self, running) -> bool:
        """The pipelined decode launch: enqueue the fused sampled
        program and stash its un-materialized (ids, finite) handles as
        the in-flight window — the host returns immediately and the
        results retire next step.  False = the launch OOMed (skipped
        and retried bit-identically, exactly like the synchronous
        path)."""
        sched, engine, tr = self.scheduler, self.engine, self.tracer
        with tr.span("inputs", program="decode"):
            tokens, positions, tables = self._decode_inputs(running)
            samp = sched.sampling_inputs(running)
            # the kwarg is omitted on all-greedy launches so duck-typed
            # engine wrappers (chaos injection, tests) predating the
            # stochastic twins keep working unchanged
            kw = {"sampling": samp} if samp is not None else {}
        try:
            with tr.span("launch", program="decode",
                         batch=len(running),
                         **self._kv_windows("decode", positions, 1)):
                ids, fin = engine.decode_sampled(tokens, positions,
                                                 tables, **kw)
                self._compile_verify_beside(kw)
        except MemoryError:
            self._note_oom("decode")
            return False
        with tr.span("account"):
            self.spec.incr("decode_steps")
            if self._phase is not None:
                self._phase["decode_launches"] += 1
                self._phase["decode_tokens"] += len(running)
            self._inflight = _InflightStep(
                "decode", list(running), ids, fin, self.clock())
            sched.hold_inflight(running)
            self.pipe.incr("launches")
        return True

    def _compile_verify_beside(self, kw) -> None:
        """On an accelerator, the first decode launch of a kind
        (greedy or stochastic: ``kw``) is followed by one launch of
        the verify program of that kind with every row idle, so that
        the verify program is compiled beside the decode program and
        not when the first draft turns up: that first draft may come
        minutes into serving (a model that does not repeat itself gives
        the n-gram drafter nothing to match), and its compile, 15 to 50
        s at the benchmark's sizes, would stall every stream.  The idle
        rows write to the garbage block and count nothing.  The CPU
        backend, where programs are tests' and compile lazily, skips
        it, as it skips donation for the sampled programs."""
        kind = "stoch" if kw else "sampled"
        if kind in self._verify_compiled:
            return
        self._verify_compiled.add(kind)
        if not self.speculating or jax.default_backend() == "cpu":
            return
        engine = self.engine
        b, k = engine.max_batch_size, self.spec_tokens + 1
        idle = np.zeros((b,), np.int32)
        with self.tracer.span("compile_verify", kind=kind):
            engine.verify_sampled(
                np.zeros((b, k), np.int32), idle, idle,
                np.zeros((b, engine.blocks_per_seq), np.int32), **kw)

    def _apply_decode_results(self, running, toks, finite,
                              now: Optional[float] = None) -> int:
        """Apply one decode step's sampled results to ``running`` —
        the retire half shared by both loops.  ``toks``/``finite`` are
        (B,) host arrays; ``now`` backdates breaker failures to the
        launch time (pipelined retire observes them a step late).
        Returns tokens produced.

        Step guard: a False ``finite`` flag means that row's logits
        went non-finite — the request is evicted before its garbage
        token enters termination logic; every finite row proceeds
        normally."""
        sched = self.scheduler
        produced = 0
        for req in running:
            if req.finished or not req.running:
                continue      # failed between launch and retire
            if not finite[req.slot]:
                sched.fail(req, reasons.NONFINITE)
                self.breaker.record_failure(now)
                continue
            req.num_cached += 1
            req.record_token(int(toks[req.slot]))
            self._note_first_token(req)
            produced += 1
            if req.finished:
                sched.retire(req)
                self.breaker.record_success()
            else:
                # index any block this token just filled so a later
                # shared-prefix request can match it
                sched.register_progress(req)
        self.spec.incr("decode_tokens", produced)
        return produced

    # -- speculative decoding (docs/serving.md) ---------------------------

    def _propose_drafts(self, running) -> Dict[int, List[int]]:
        """uid -> drafted tokens for this iteration: the draft
        source's guesses, capped by the request's remaining token
        budget (drafting past ``max_new_tokens`` is wasted verify
        width) and by the lookahead blocks the scheduler can grant
        without preempting anyone.  The draft source's index of each
        history lives on the request (``Request.draft_index``)."""
        sched = self.scheduler
        drafts: Dict[int, List[int]] = {}
        calls = rebuilds = 0
        for req in running:
            budget = min(self.spec_tokens,
                         req.max_new_tokens - len(req.generated) - 1)
            if budget < 1:
                continue
            d, index = self.draft_source.propose_indexed(
                req.prompt, req.generated, budget, req.draft_index)
            calls += 1
            if index is not req.draft_index:
                req.draft_index = index
                rebuilds += 1
            d = d[:budget]
            # a draft is a hint from arbitrary user code: truncate at
            # the first out-of-vocab id rather than feeding it to the
            # embedding gather
            for i, t in enumerate(d):
                if not 0 <= int(t) < self.engine.cfg.vocab_size:
                    d = d[:i]
                    break
            if not d:
                continue
            fit = sched.lookahead_capacity(req, 1 + len(d))
            d = d[:fit - 1]
            if d:
                drafts[req.uid] = d
        self.spec.incr("draft_calls", calls)
        self.spec.incr("draft_index_rebuilds", rebuilds)
        return drafts

    def _verify_inputs(self, running, drafts):
        """The verify launch arrays — (tokens, lengths, positions,
        tables): every slot's pending token plus its drafts (none = a
        plain one-token column), zero-padded — shared by the
        synchronous and pipelined paths."""
        engine = self.engine
        kw = self.spec_tokens + 1
        b, mb = engine.max_batch_size, engine.blocks_per_seq
        tokens = np.zeros((b, kw), np.int32)
        lengths = np.zeros((b,), np.int32)
        positions = np.zeros((b,), np.int32)
        tables = np.zeros((b, mb), np.int32)
        for req in running:
            d = drafts.get(req.uid, ())
            n = 1 + len(d)
            tokens[req.slot, 0] = req.next_input
            if d:
                tokens[req.slot, 1:n] = d
            lengths[req.slot] = n
            positions[req.slot] = req.num_cached
            tables[req.slot, :len(req.block_table)] = req.block_table
        return tokens, lengths, positions, tables

    def _verify_step(self, running, drafts) -> int:
        """One speculative verify step over ``running``, materialized
        and applied in the same call (the synchronous loop): every
        slot feeds its pending token plus its drafts through the
        fixed-width verify program, and greedy acceptance keeps, per
        slot, the longest draft prefix matching the model's own argmax
        plus the model's next token — so the emitted tokens are
        exactly what one-token decode would have produced, just
        several of them per engine step.  Rejected suffix K/V is
        rolled back (``Scheduler.rollback_lookahead``).  Returns
        tokens produced."""
        sched, engine, tr = self.scheduler, self.engine, self.tracer
        with tr.span("inputs", program="verify"):
            tokens, lengths, positions, tables = self._verify_inputs(
                running, drafts)
        try:
            with tr.span("verify", batch=len(running),
                         drafted=sum(len(v) for v in drafts.values())):
                logits = np.asarray(engine.verify(
                    tokens, lengths, positions, tables))
        except MemoryError:
            # skip-and-retry: no request state moved, and drafts are
            # pure functions of request history — the retry next
            # iteration recomputes them bit-identically.  Lookahead
            # blocks grown for this verify are returned so the skipped
            # iteration holds no extra pool space.
            self._note_oom("verify")
            for req in running:
                if req.running:
                    sched.rollback_lookahead(req)
            return 0
        with tr.span("apply", program="verify"):
            self.spec.incr("verify_steps")
            if self._phase is not None:
                self._phase["verify_launches"] += 1
                self._phase["verify_columns"] += (
                    len(running) + sum(len(d) for d in drafts.values()))
            finite = np.all(np.isfinite(logits), axis=-1)      # (B, K)
            samp = self.scheduler.sampling_inputs(running)
            if samp is None:
                row_toks = greedy_sample(logits)               # (B, K)
            else:
                # every verify column sampled with its own positional
                # counter key — acceptance below compares drafts to
                # these samples, which IS rejection sampling (the
                # Gumbel-max coupling, ops.sample_tokens) and keeps
                # the stream identical to plain decode
                b, kw = logits.shape[:2]
                counters = (positions[:, None].astype(np.int32) + 1
                            + np.arange(kw, dtype=np.int32)[None, :])
                samp2 = tuple(np.broadcast_to(a[:, None], (b, kw))
                              for a in samp)
                row_toks = np.asarray(sample_tokens_host(
                    logits, *samp2, counters)[0])
            return self._apply_verify_results(running, drafts, lengths,
                                              row_toks, finite)

    def _launch_verify(self, running, drafts) -> bool:
        """The pipelined verify launch: enqueue the fused sampled
        program (every row's argmax + finite flag on device) and
        stash the un-materialized handles plus the draft map as the
        in-flight window; greedy acceptance runs at retire, next step.
        False = the launch OOMed — lookahead blocks grown for it are
        rolled back and the identical verify (drafts are deterministic
        functions of request history) retries next iteration."""
        sched, engine, tr = self.scheduler, self.engine, self.tracer
        with tr.span("inputs", program="verify"):
            tokens, lengths, positions, tables = self._verify_inputs(
                running, drafts)
            samp = sched.sampling_inputs(running)
            kw = {"sampling": samp} if samp is not None else {}
        try:
            with tr.span("launch", program="verify",
                         batch=len(running),
                         drafted=sum(len(v) for v in drafts.values()),
                         **self._kv_windows("verify", positions,
                                            self.spec_tokens + 1)):
                ids, fin = engine.verify_sampled(tokens, lengths,
                                                 positions, tables,
                                                 **kw)
        except MemoryError:
            self._note_oom("verify")
            for req in running:
                if req.running:
                    sched.rollback_lookahead(req)
            return False
        with tr.span("account"):
            self.spec.incr("verify_steps")
            if self._phase is not None:
                self._phase["verify_launches"] += 1
                # columns fed = each slot's pending token + its drafts
                # (host ints — lengths mirrors exactly this)
                self._phase["verify_columns"] += (
                    len(running) + sum(len(d) for d in drafts.values()))
            self._inflight = _InflightStep(
                "verify", list(running), ids, fin, self.clock(),
                drafts=drafts, lengths=lengths)
            sched.hold_inflight(running)
            self.pipe.incr("launches")
        return True

    def _apply_verify_results(self, running, drafts, lengths,
                              row_toks, finite,
                              now: Optional[float] = None) -> int:
        """Greedy acceptance over one verify step's sampled results —
        the retire half shared by both loops.  ``row_toks``/``finite``
        are (B, K) host arrays (the model's argmax and finite flag at
        every fed position); ``now`` backdates breaker failures to
        launch time.  Accepts, per slot, the longest draft prefix
        matching the model's own argmax plus the model's next token,
        then rolls back rejected-suffix K/V blocks.  Returns tokens
        produced."""
        sched = self.scheduler
        produced = 0
        for req in running:
            if req.finished or not req.running:
                continue      # failed between launch and retire
            n = int(lengths[req.slot])
            if not np.all(finite[req.slot, :n]):
                sched.fail(req, reasons.NONFINITE)
                self.breaker.record_failure(now)
                continue
            toks = row_toks[req.slot]                      # (K,)
            d = drafts.get(req.uid, ())
            req.num_cached += 1        # the pending token's K/V landed
            accepted = 0
            for j, guess in enumerate(d):
                if int(guess) != int(toks[j]):
                    break              # model disagrees: reject the
                    #                    rest of the draft
                req.record_token(int(guess))
                self._note_first_token(req)
                produced += 1
                req.num_cached += 1    # its verify-written K/V is valid
                accepted += 1
                if req.finished:
                    break
            resampled = False
            if not req.finished:
                # the model's own next token — the sample after the
                # last accepted token, exactly what a one-token decode
                # would draw there (its K/V is NOT yet written; it
                # becomes the pending token, same as decode).  Under
                # greedy this is the argmax correction; under
                # stochastic sampling a draft rejection makes it the
                # residual resample of rejection sampling (the
                # Gumbel-max coupling: the column's own sample, which
                # conditional on differing from the draft is exactly
                # the normalized-residual draw)
                resampled = accepted < len(d)
                req.record_token(int(toks[accepted]))
                self._note_first_token(req)
                produced += 1
            if d:
                req.spec_drafted += len(d)
                req.spec_accepted += accepted
                self.spec.incr("drafted_tokens", len(d))
                self.spec.incr("accepted_tokens", accepted)
                self.spec_drafted_hist.record(len(d))
                self.spec_accepted_hist.record(accepted)
                if not req.sampling.is_greedy:
                    # the stats()["sampling"]["rejection"] block:
                    # stochastic drafts accepted with prob p(draft),
                    # each rejection emitting one residual resample
                    self.spec.incr("stoch_drafted_tokens", len(d))
                    self.spec.incr("stoch_accepted_tokens", accepted)
                    if resampled:
                        self.spec.incr("stoch_resamples")
            if req.finished:
                sched.retire(req)
                self.breaker.record_success()
            else:
                # index any blocks the accepted tokens just filled,
                # then release lookahead blocks holding only
                # rejected-suffix positions (KV rollback)
                sched.register_progress(req)
                sched.rollback_lookahead(req)
        self.spec.incr("decode_tokens", produced)
        return produced

    def _flush_window(self) -> int:
        """RETIRE: materialize and apply the in-flight launched step
        (no-op when the window is empty).  Blocks until the device
        finishes it — which, one step after launch, it usually already
        has; the measured wait is the device-bound share of the step
        (``stats()["pipeline"]["host_stall_ms"]``).  Returns tokens
        produced."""
        inf = self._inflight
        if inf is None:
            return 0
        self._inflight = None
        t0 = self.clock()
        with self.tracer.span("retire", program=inf.kind,
                              batch=len(inf.running)):
            toks = np.asarray(inf.ids)
            finite = np.asarray(inf.finite)
        with self.tracer.span("apply", program=inf.kind):
            self.retire_wait.record(self.clock() - t0)
            # the device step is fully consumed: its K/V writes landed,
            # so the window's block pin lifts before any request state
            # moves
            self.scheduler.release_inflight()
            self.pipe.incr("retired_behind")
            if inf.kind == "decode":
                return self._apply_decode_results(
                    inf.running, toks, finite, now=inf.launched_at)
            return self._apply_verify_results(
                inf.running, inf.drafts, inf.lengths, toks, finite,
                now=inf.launched_at)

    # -- disaggregated prefill/decode pools (docs/serving.md) --------------

    def _step_disagg(self) -> int:
        """One disaggregated iteration (``enable_disagg=True``): the
        DECODE pool retires, plans, and launches a pure decode/verify
        step — never a prefill — and the PREFILL pool then advances up
        to ``prefill_max_concurrent`` chunk launches whose device
        compute overlaps the already-in-flight decode (the two pools
        share no array, so nothing serializes them).  Finished
        prefills hand their blocks to the decode pool through the
        fixed-shape cross-pool block copy at the START of the next
        step; greedy output is bit-exact vs the monolithic loop by
        construction (same programs, same per-request context, the
        copy is byte-preserving)."""
        sched = self.scheduler
        psched = self.prefill_scheduler
        rec = self.recorder
        self._iter += 1
        produced, self._pending_produced = self._pending_produced, 0
        step_start = self.clock()
        self._phase = None
        if rec.enabled:
            preempt0 = (sched.preemption_count
                        + psched.preemption_count)
            lk_grant0 = sched.lookahead_granted
            lk_roll0 = sched.lookahead_rolled_back
            evict0 = self.prefix.count("prefix_evicted_blocks")
            oom0 = self.oom.total
            drafted0 = self.spec.count("drafted_tokens")
            accepted0 = self.spec.count("accepted_tokens")
            off0 = self._offload_marks()
            self._phase = self._new_phase()
        # RETIRE the decode pool's in-flight step first — this is the
        # inter-token edge disaggregation protects
        retired = self._flush_window()
        produced += retired
        plan_start = self.clock()
        self._expire_deadlines()
        self.pressure_gauge.update(self.pressure())
        shed = psched.shed_overload()
        # HAND-OFF: prefills that finished in an earlier step
        # materialize their first token and move pools (the copy and
        # this step's decode of the moved request share the decode
        # pool's data dependency, so ordering is automatic)
        produced += self._process_handoffs()
        # DECODE pool: pure decode/verify over its running batch
        if sched.running:
            for req in list(sched.running.values()):
                if req.running and not req.prefilling:
                    if not sched.ensure_decode_capacity(req):
                        sched.fail(req, reasons.CAPACITY)
            # a decode-pool preemption victim must re-prefill: it
            # re-enters through the PREFILL pool's queue front,
            # keeping its seniority (recompute is bit-stable — the
            # pending token continues, exactly as monolithic)
            while sched.waiting:
                psched.waiting.appendleft(sched.waiting.pop())
            running = [r for r in sched.running.values()
                       if not r.prefilling]
            if running:
                drafts = (self._propose_drafts(running)
                          if self.speculating else {})
                if self.pipelining:
                    if drafts:
                        self._launch_verify(running, drafts)
                    else:
                        self._launch_decode(running)
                elif drafts:
                    produced += self._verify_step(running, drafts)
                else:
                    produced += self._decode_step(running)
        # PREFILL pool: admission + one chunk per prefilling request,
        # launched AFTER the decode launch so its compute runs under
        # the in-flight decode instead of in front of it
        chunks, pf_produced, admitted = self._prefill_slice()
        produced += pf_produced
        self.chunk_iters.update(chunks)
        if chunks:
            self.prefix.incr("prefill_chunks", chunks)

        if self.pipelining:
            self.plan_time.record(self.clock() - plan_start)
        self.tokens.update(produced)
        self.queue_depth.update(psched.num_waiting)
        self.occupancy.update(sched.num_running
                              / self.engine.max_batch_size)
        step_s = self.clock() - step_start
        self.step_time.record(step_s)
        self._finalize_finished()
        alloc = self.engine.allocator
        palloc = self.prefill_engine.allocator
        self.mem_live.update(alloc.num_live)
        self.mem_free.update(alloc.num_free)
        self.mem_evictable.update(
            self.prefix_cache.num_evictable
            if self.prefix_cache is not None else 0)
        self.mem_frag.update(sched.frag_slots() + psched.frag_slots())
        self.handoff_pending.update(len(self._handoff))
        if rec.enabled:
            fin = sched.finished
            new_fin = fin[self._rec_cursor:]
            finished_now = [
                {"uid": r.uid, "reason": r.finish_reason,
                 "tokens": len(r.generated)}
                for r in new_fin]
            self._rec_cursor = len(fin)
            step_rec = {
                "iter": self._iter,
                "produced": produced,
                "waiting": psched.num_waiting,
                "running": [r.uid for r in sched._admit_order]
                + [r.uid for r in psched._admit_order],
                "prefilling": [r.uid for r in psched._admit_order
                               if r.prefilling],
                "admitted": [r.uid for r in admitted],
                "shed": [{"uid": r.uid, "priority": r.priority,
                          "debt_tokens":
                          OverloadPolicy.slo_debt_tokens(r)}
                         for r in shed],
                "finished": finished_now,
                "preemptions": (sched.preemption_count
                                + psched.preemption_count) - preempt0,
                "evicted_blocks":
                    self.prefix.count("prefix_evicted_blocks") - evict0,
                "oom": self.oom.total - oom0,
                "spec": {
                    "drafted":
                        self.spec.count("drafted_tokens") - drafted0,
                    "accepted":
                        self.spec.count("accepted_tokens") - accepted0,
                },
                "pressure": round(self.pressure_gauge.val, 4),
                "breaker": self.breaker.state,
                "memory": {
                    "free": alloc.num_free,
                    "live": alloc.num_live,
                    "evictable": (self.prefix_cache.num_evictable
                                  if self.prefix_cache is not None
                                  else 0),
                    "frag_slots": (sched.frag_slots()
                                   + psched.frag_slots()),
                    "lookahead_granted":
                        sched.lookahead_granted - lk_grant0,
                    "lookahead_rolled_back":
                        sched.lookahead_rolled_back - lk_roll0,
                },
                "pipeline": {
                    "pending": 1 if self._inflight is not None else 0,
                    "retired_tokens": retired,
                },
                "offload": self._offload_delta(off0),
                "phase": self._phase,
                "disagg": {
                    "handoff_pending": len(self._handoff),
                    "prefill_free": palloc.num_free,
                    "prefill_live": palloc.num_live,
                },
                "step_s": step_s,
            }
            if self.journeys.enabled:
                # same conditional uid -> rid join as the monolithic
                # step record
                step_rec["rids"] = {
                    str(r.uid): r.journey.rid
                    for r in list(admitted) + new_fin
                    if r.journey is not None}
            rec.record(step_rec)
            self._phase = None
        state = self.breaker.state
        if state != self._last_breaker_state:
            self._last_breaker_state = state
            if state == "open":
                self._auto_postmortem("breaker_open")
        return produced

    def _prefill_slice(self):
        """The prefill pool's share of one disaggregated step: shed /
        admit / COW / one chunk per prefilling slot, all against the
        PREFILL engine and scheduler.  Chunk launches are asynchronous
        (mid-chunk results are never materialized, and the final
        chunk's sampled token is stashed as un-materialized handles
        under pipelining), so the slice costs the host little more
        than dispatch.  Returns ``(chunk launches, tokens produced,
        admitted requests)``."""
        psched, engine = self.prefill_scheduler, self.prefill_engine
        pipelined = self.pipelining
        admitted = self._admit(psched, engine)
        chunks = 0
        produced = 0
        for req in [r for r in psched._admit_order if r.prefilling]:
            # the launch alone is the chunk's span here: a finished
            # prompt waits for its hand-off, nothing is read
            with self._launch_chunk(psched, engine, req) as (out, done):
                pass
            if out is None:
                continue              # out of memory: replays next step
            chunks += 1
            if not done:
                continue
            if not req.prefill_sample:
                # resumed after preemption: the pending token
                # continues — nothing to sample, straight to hand-off
                self._handoff.append(_Handoff(req))
                continue
            if pipelined:
                # the sampled token stays un-materialized until the
                # hand-off processes next step (its compute will long
                # be done) — the prefill slice never blocks on device
                self._handoff.append(_Handoff(req, handles=out))
                continue
            # synchronous path: materialize now, exactly like the
            # monolithic loop's prefill sampling
            logits = np.asarray(out)
            if not np.all(np.isfinite(logits)):
                psched.fail(req, reasons.NONFINITE)
                self.breaker.record_failure()
                continue
            tok = self._sample_prefill_host(req, logits)
            req.record_token(tok)
            self._note_first_token(req)
            produced += 1
            if req.finished:
                psched.retire(req)
                self.breaker.record_success()
                continue
            self._handoff.append(_Handoff(req))
        return chunks, produced, admitted

    def _process_handoffs(self) -> int:
        """Drain the hand-off queue (FIFO): materialize each finished
        prefill's first token (pipelined launches stashed handles a
        step ago), then move its blocks into the decode pool via the
        cross-pool block copy — or ship them to another replica
        through ``handoff_sink``.  A hand-off that cannot place yet
        (no decode slot / blocks, or a transient copy failure) stays
        queued, blocks intact on the prefill side, and retries next
        step — delayed, never torn: the copy is idempotent over whole
        tables, so a partial transfer is simply re-copied.  Returns
        tokens produced (hand-off-time first tokens)."""
        sched, psched = self.scheduler, self.prefill_scheduler
        q = self._handoff
        produced = 0
        while q:
            ent = q[0]
            req = ent.req
            if req.finished or not req.running:
                # expired / evacuated / failed while queued
                q.popleft()
                continue
            if ent.handles is not None:
                ids, fin = ent.handles
                ent.handles = None
                if not bool(np.asarray(fin)[0]):
                    psched.fail(req, reasons.NONFINITE)
                    self.breaker.record_failure()
                    q.popleft()
                    continue
                req.record_token(int(np.asarray(ids)[0]))
                self._note_first_token(req)
                produced += 1
                if req.finished:
                    psched.retire(req)
                    self.breaker.record_success()
                    self.handoffs.incr("finished_at_prefill")
                    q.popleft()
                    continue
            if self.handoff_sink is not None:
                # cross-replica: export the blocks (+ scale sidecars)
                # as a checksummed host payload and let the router
                # place the decode half; True = ownership moved
                payload = self.prefill_engine.export_blocks(
                    req.block_table)
                if self.handoff_sink(req, payload):
                    # a cancel() racing the sink call may have
                    # terminalized req already (freeing its prefill
                    # blocks on the standard fail path) — failing it
                    # AGAIN would double-free; the sink side handles
                    # the orphaned ingest
                    if not req.finished:
                        psched.register_progress(req)
                        psched.fail(req, reasons.HANDOFF)
                    self.handoffs.incr("sink_delivered")
                    q.popleft()
                    continue
                # nobody could take it: fall back to the LOCAL decode
                # pool below — monolithic placement on this replica
                self.handoffs.incr("sink_local_fallback")
                if req.finished:
                    # cancelled mid-sink and the sink declined: its
                    # blocks are already freed — nothing to place
                    q.popleft()
                    continue
            n = len(req.block_table)
            if not sched.has_free_slot:
                self.handoffs.incr("deferred")
                break
            dst = sched._try_alloc(n)
            if dst is None:
                self.handoffs.incr("deferred")
                break
            try:
                with self.tracer.span("handoff", uid=req.uid,
                                      blocks=n):
                    self.engine.copy_blocks_from(
                        self.prefill_engine,
                        list(zip(req.block_table, dst)))
            except MemoryError:
                # transient (or chaos-torn) transfer: return the
                # destination blocks and retry the WHOLE copy next
                # step — re-copying every block makes a torn transfer
                # indistinguishable from a delayed one
                sched.allocator.free(dst)
                self._note_oom("handoff")
                break
            if self._phase is not None:
                self._phase["handoff_blocks"] += n
            psched.release_handoff(req)
            sched.admit_handoff(req, dst)
            self.handoffs.incr("requests")
            self.handoffs.incr("blocks", n)
            q.popleft()
        return produced

    def ingest_handoff(self, prompt: Sequence[int],
                       generated: Sequence[int], payload: dict, *,
                       max_new_tokens: int,
                       num_cached: int,
                       eos_id: Optional[int] = None,
                       priority: int = 0,
                       deadline_iters: Optional[int] = None,
                       deadline_s: Optional[float] = None,
                       sampling: Optional[SamplingParams] = None,
                       submitted_at: Optional[float] = None,
                       first_token_at: Optional[float] = None,
                       journey=None) -> Optional[Request]:
        """The decode half of a CROSS-REPLICA hand-off: import an
        :meth:`DecodeEngine.export_blocks` payload into this server's
        (decode) pool and admit the request straight into the decode
        batch at its carried position — no prefill here, ever.

        Returns the admitted :class:`Request`, or ``None`` when this
        replica cannot take it right now (draining, no free decode
        slot, or no blocks) — the router then falls back to monolithic
        placement.  Raises :class:`ValueError` on a torn payload
        (checksum mismatch): nothing was imported, the caller must
        fall back to a fresh prefill (which is bit-identical)."""
        with (self._ops_lock or _NO_LOCK):
            if self._closed:
                raise RuntimeError(
                    "InferenceServer is closed; no further submissions")
            if self._draining:
                return None
            if self._inflight is not None:
                self._pending_produced += self._flush_window()
            generated = [int(t) for t in generated]
            if not generated:
                raise ValueError(
                    "ingest_handoff needs >= 1 generated token (the "
                    "prefill side samples the first token before "
                    "handing off)")
            sched = self.scheduler
            if not sched.has_free_slot:
                return None
            n = int(payload.get("num_blocks", 0))
            blocks = sched._try_alloc(n)
            if blocks is None:
                return None
            try:
                self.engine.import_blocks(blocks, payload)
            except ValueError:
                sched.allocator.free(blocks)
                raise
            except MemoryError:
                sched.allocator.free(blocks)
                return None
            req = Request(prompt=[int(t) for t in prompt],
                          max_new_tokens=int(max_new_tokens),
                          eos_id=eos_id,
                          priority=int(priority),
                          deadline_iters=deadline_iters,
                          deadline_s=deadline_s,
                          submit_iter=self._iter,
                          submitted_at=(submitted_at
                                        if submitted_at is not None
                                        else self.clock()),
                          sampling=sampling if sampling is not None
                          else SamplingParams())
            req.generated = generated
            req.next_input = generated[-1]
            req.num_cached = int(num_cached)
            req.admitted_at = self.clock()
            req.first_token_at = (first_token_at
                                  if first_token_at is not None
                                  else req.admitted_at)
            self.sampling_classes.incr(req.sampling.klass)
            if self.journeys.enabled and journey is not None:
                # the hand-off carries the journey context across
                # replicas: ingest hop here, then admit_handoff's
                # handoff=True admit hop — one rid, causal order
                req.journey = journey
                self.journeys.hop(journey, "handoff_ingest",
                                  uid=req.uid, blocks=n,
                                  carried_tokens=req.num_cached)
            sched.admit_handoff(req, blocks)
            self.handoffs.incr("ingested")
            self.handoffs.incr("blocks", n)
            return req

    def _offload_ingest(self, meta: dict, payload: dict) -> dict:
        """Receiver half of the offload-promote transfer: import the
        checksummed payload into the blocks the sender reserved.  The
        cache-home engine is resolved at call time (prefill pool under
        disagg, else the monolithic engine) so the handler survives a
        server reconfiguration.  A torn payload raises
        :class:`ValueError` natively — the transport reports it to the
        sender un-retried and caches nothing."""
        eng = self.prefill_engine if self.disagg else self.engine
        blocks = [int(b) for b in meta["blocks"]]
        eng.import_blocks(blocks, payload)
        return {"blocks": len(blocks)}

    def _note_oom(self, site: str) -> None:
        """Account one transient engine ``MemoryError``: the affected
        call was skipped (nothing mutated) and will retry next
        iteration; the circuit breaker counts it as a failure so a
        sustained OOM burst trips fast rejection at the front door."""
        self.oom.incr(site)
        self.breaker.record_failure()
        if self.tracer.enabled:
            self.tracer.instant("engine_oom", site=site)

    # -- per-request timelines --------------------------------------------

    def _note_first_token(self, req: Request) -> None:
        """Stamp the first-token edge of the request timeline (the
        TTFT numerator) the moment its first token is sampled, and —
        for every later token — the inter-token gap since the previous
        one (the ITL distribution behind
        ``stats()["latency"]["itl_ms"]`` and the per-request p99 the
        SLO tracker bounds).  Tokens accepted together in one verify
        step record one real gap plus near-zero followers — exactly
        the arrival pattern a streaming consumer sees."""
        now = self.clock()
        if req.first_token_at is None and req.generated:
            req.first_token_at = now
            if self.tracer.enabled:
                self.tracer.instant("request_first_token", uid=req.uid)
            if self.journeys.enabled and req.journey is not None:
                self.journeys.hop(req.journey, "first_token",
                                  uid=req.uid,
                                  ttft_s=now - req.submitted_at)
        elif req.last_token_at is not None:
            gap = now - req.last_token_at
            req.itl_gaps.append(gap)
            self.itl.record(gap)
            if self.journeys.enabled and req.journey is not None:
                # ITL exemplar: the worst gap per histogram bucket
                # remembers which rid produced it, so an SLO-miss
                # bucket resolves to a renderable journey
                self.journeys.exemplar("itl",
                                       self.itl.bucket_index(gap),
                                       gap, req.journey.rid)
        req.last_token_at = now
        # streaming fan-out rides the same edge: every applied token
        # funnels through here, so this is THE retire-time publish
        # point (docs/serving.md, "Streaming & cancellation")
        if self.stream_broker is not None:
            self.stream_broker.publish(req.uid, len(req.generated) - 1,
                                       req.generated[-1])

    def _finalize_finished(self) -> None:
        """Stamp ``finished_at`` on every request that finished since
        the last call (any path: retire, fail, rejected-at-submit) and
        feed the latency histograms from its timeline.  Cursor-based
        over ``scheduler.finished`` so each request is accounted
        exactly once."""
        fin = self.scheduler.finished
        while self._finalized < len(fin):
            req = fin[self._finalized]
            self._finalized += 1
            if req.finished_at is None:
                req.finished_at = self.clock()
            if self.tracer.enabled:
                self.tracer.instant("request_finish", uid=req.uid,
                                    reason=req.finish_reason or "",
                                    tokens=len(req.generated))
            # never-admitted requests (rejected / shed-from-queue /
            # breaker_open / draining / queued timeout) have no
            # admitted_at, so timeline() emits no queue_wait_s/ttft_s
            # — admission latency never mixes in requests that were
            # turned away at the front door
            tl = req.timeline()
            if "queue_wait_s" in tl:
                self.queue_wait.record(tl["queue_wait_s"])
                self._queue_wait_for(req.priority).record(
                    tl["queue_wait_s"])
            if "ttft_s" in tl:
                self.ttft.record(tl["ttft_s"])
                if self.journeys.enabled and req.journey is not None:
                    # TTFT exemplar: worst observation per bucket
                    # keeps its rid (the SLO-miss -> journey link)
                    self.journeys.exemplar(
                        "ttft", self.ttft.bucket_index(tl["ttft_s"]),
                        tl["ttft_s"], req.journey.rid)
            if "decode_token_s" in tl:
                self.decode_latency.record(tl["decode_token_s"])
            if (self.journeys.enabled and req.journey is not None
                    and req.finish_reason != reasons.HANDOFF):
                # HANDOFF is not a journey terminal: ownership moved
                # to the ingesting replica, which records the real
                # finish — a hop here would double-finish the journey
                self.journeys.hop(req.journey, "finish", uid=req.uid,
                                  reason=req.finish_reason or "",
                                  tokens=len(req.generated))
            # SLO/goodput classification (docs/observability.md,
            # "SLO & goodput"): served terminals count toward
            # attainment, shed work toward the debt counters
            self.slo.observe(req)
            if self.admission is not None:
                self.admission.observe(req)
            # terminal stream event: delivery backfills any tokens the
            # bounded queue never carried, so the consumer's stream is
            # complete the moment it sees the finish_reason
            if self.stream_broker is not None:
                self.stream_broker.finish(req.uid,
                                          req.finish_reason or "")

    def _queue_wait_for(self, priority: int):
        """The per-priority-class queue-wait histogram (a labeled
        series of ``serving_queue_wait_s``), created on first use."""
        h = self._queue_wait_prio.get(priority)
        if h is None:
            h = self.registry.histogram("serving_queue_wait_s",
                                        priority=str(priority))
            self._queue_wait_prio[priority] = h
        return h

    # -- postmortems (docs/observability.md) -------------------------------

    def dump_postmortem(self, path: str, *, reason: str = "on_demand",
                        extra: Optional[dict] = None) -> dict:
        """Write a postmortem bundle into ``path`` — the flight ring
        as JSONL, the full metrics snapshot, the tracer's Chrome
        trace, and a manifest — and return the manifest.  Meaningful
        whenever the flight recorder is on (``flight_recorder=`` /
        ``postmortem_dir=`` / ``APEX_TPU_POSTMORTEM``); with the null
        recorder the bundle still writes but its flight log is empty.
        Render/inspect with ``tools/postmortem.py``."""
        merged = {"iter": self._iter,
                  "engine": self.engine.memory_info()}
        if extra:
            merged.update(extra)
        return write_postmortem(path, recorder=self.recorder,
                                registry=self.registry,
                                tracer=self.tracer, reason=reason,
                                extra=merged,
                                journeys=dump_journeys([self.journeys])
                                if self.journeys.enabled else None)

    def journey(self, rid: int) -> Optional[dict]:
        """One merged journey by rid (``Journey.as_dict()`` shape), or
        None when unknown / journeys disabled — the programmatic twin
        of ``GET /debug/journey/<rid>`` (``tools/journey.py`` renders
        the bundle-side view)."""
        j = merge_journeys([self.journeys], rid=int(rid)).get(int(rid))
        return j.as_dict() if j is not None else None

    def _auto_postmortem(self, reason: str,
                         extra: Optional[dict] = None) -> Optional[str]:
        """Dump a bundle under ``postmortem_dir`` (when configured,
        with a live recorder) named ``<reason>_iter<N>``; returns the
        bundle path or None when auto-capture is off."""
        if not (self.recorder.enabled and self._postmortem_dir):
            return None
        path = os.path.join(self._postmortem_dir,
                            f"{reason}_iter{self._iter}")
        self.dump_postmortem(path, reason=reason, extra=extra)
        return path

    # apexlint: disable=lock-discipline — documented lock-free: runs on the watchdog thread while the serve thread is wedged, possibly holding the ops lock; taking it here would deadlock the black box
    def _on_watchdog_stall(self, info: dict) -> Optional[str]:
        """The armed watchdog's stall handler — runs ON THE WATCHDOG
        THREAD while the serve thread is still stuck, so it takes no
        locks: count the stall, then (when ``postmortem_dir`` is
        configured) capture every thread's stack via
        :mod:`faulthandler` — the wedged serve thread's frames are
        the payload — alongside a postmortem bundle whose manifest
        names the stall and the stack attachment
        (``tools/postmortem.py`` renders and gates both).  Returns
        the bundle path, or None when capture is off."""
        self._watchdog_stalls.incr()
        if self.tracer.enabled:
            self.tracer.instant("watchdog_stall", **info)
        if not self._postmortem_dir:
            return None
        path = os.path.join(self._postmortem_dir,
                            f"watchdog_stall_iter{self._iter}")
        os.makedirs(path, exist_ok=True)
        threads_name = "threads.txt"
        with open(os.path.join(path, threads_name), "w") as f:
            faulthandler.dump_traceback(file=f, all_threads=True)
        self.dump_postmortem(path, reason="watchdog_stall",
                             extra={"stall": info,
                                    "thread_stacks": threads_name})
        return path

    def audit(self) -> None:
        """The scheduler/allocator/prefix-cache invariant audit, with
        postmortem capture: an :class:`AssertionError` auto-dumps a
        bundle (when ``postmortem_dir`` + recorder are configured)
        before re-raising, so the steps leading up to the violated
        invariant are preserved, not just the assertion text.  Under
        disaggregation both pools' schedulers are audited."""
        try:
            for sched in self._schedulers():
                sched.audit()
        except AssertionError as e:
            self._auto_postmortem("audit_failure",
                                  extra={"error": str(e)})
            raise

    # -- front door -------------------------------------------------------

    def generate(self, prompts: Sequence[Sequence[int]],
                 max_new_tokens: int,
                 eos_id: Optional[int] = None, *,
                 priority: int = 0,
                 deadline_iters: Optional[int] = None,
                 deadline_s: Optional[float] = None,
                 sampling: Union[SamplingParams,
                                 Sequence[Optional[SamplingParams]],
                                 None] = None,
                 return_requests: bool = False):
        """Generate completions for ``prompts`` (token-id lists) and
        return the generated ids per prompt, in input order.

        ``sampling``: one :class:`SamplingParams` for every prompt, or
        a per-prompt sequence (None entries = greedy) — the batch
        twin of :meth:`submit`'s ``sampling``.

        A request that fails (capacity / timeout / rejected / shed /
        nonfinite) contributes whatever it generated before failing —
        inspect ``finish_reason`` via ``return_requests=True`` to tell
        a clean completion from an isolated failure."""
        if sampling is None or isinstance(sampling, SamplingParams):
            per_prompt = [sampling] * len(prompts)
        else:
            per_prompt = list(sampling)
            if len(per_prompt) != len(prompts):
                raise ValueError(
                    f"sampling sequence length {len(per_prompt)} != "
                    f"{len(prompts)} prompts")
        reqs = [self.submit(p, max_new_tokens, eos_id,
                            priority=priority,
                            deadline_iters=deadline_iters,
                            deadline_s=deadline_s,
                            sampling=s)
                for p, s in zip(prompts, per_prompt)]
        while self.has_work:
            self.step()
        if return_requests:
            return reqs
        return [list(r.generated) for r in reqs]

    # -- graceful lifecycle -----------------------------------------------

    @property
    def draining(self) -> bool:
        return self._draining

    @property
    def closed(self) -> bool:
        return self._closed

    def begin_drain(self) -> None:
        """The NON-BLOCKING half of :meth:`drain`: stop admissions
        (subsequent submits finish ``"draining"``) but leave running
        the work off to the caller's step loop.  This is the rolling-
        restart shape the multi-replica router needs
        (``serving.router``): the router keeps stepping a draining
        replica alongside the healthy ones until its in-flight work
        reaches terminal states, instead of blocking the whole fleet
        inside one replica's synchronous :meth:`drain`.  Idempotent;
        in-flight generation is bit-identical either way (the same
        scheduler/engine steps run on the same state)."""
        self._draining = True

    def end_drain(self) -> None:
        """Reopen admissions after :meth:`begin_drain` WITHOUT
        replacing the server — the in-place weight-rollout shape
        (``serving/elastic``): a drained server keeps its compiled
        programs and swaps params in place, so "restart" is just
        flipping admissions back on.  Idempotent on a non-draining
        server; a CLOSED server cannot reopen (close released its
        pools)."""
        if self._closed:
            raise RuntimeError("cannot end_drain a closed server")
        self._draining = False

    def drain(self) -> dict:
        """Graceful shutdown, phase one: stop admissions (subsequent
        submits finish immediately with ``finish_reason="draining"``)
        and run every in-flight request to a terminal state.  Draining
        changes nothing about how in-flight work computes — the same
        scheduler/engine steps run on the same state — so a request's
        tokens are bit-identical whether or not a drain begins
        mid-generation (pinned by ``tests/L0/test_overload.py``).
        Idempotent; returns the flushed :meth:`stats` snapshot."""
        self.begin_drain()
        while self.has_work:
            self.step()
        self._account_pending_produced()
        self._finalize_finished()
        return self.stats()

    def withdraw_queued(self) -> List[Request]:
        """Remove and return every WAITING request without finishing
        it — the router's drain-time re-enqueue source
        (``serving.router``): queued work has generated nothing, so it
        restarts bit-identically on another replica instead of waiting
        behind this one's drain.  Flushes the pipelined window first
        so the withdrawal sees post-retire queue state."""
        if self._inflight is not None:
            self._pending_produced += self._flush_window()
        moved = self.scheduler.withdraw_waiting()
        if self.disagg:
            moved += self.prefill_scheduler.withdraw_waiting()
        self._finalize_finished()
        return moved

    # -- streaming & cancellation (docs/serving.md) ------------------------

    def _find_request(self, uid: int) -> Optional[Request]:
        """The live-or-finished request with ``uid``, or None.
        ``scheduler.running`` is keyed by SLOT, so uid lookups scan
        values; the finished list is shared across pools."""
        for sched in self._schedulers():
            for req in sched.running.values():
                if req.uid == uid:
                    return req
            for req in sched.waiting:
                if req.uid == uid:
                    return req
        for req in self.scheduler.finished:
            if req.uid == uid:
                return req
        return None

    def stream(self, req_or_uid, callback: Optional[Callable] = None
               ) -> TokenStream:
        """The per-token delivery stream for a submitted request
        (docs/serving.md, "Streaming & cancellation").

        Iterate it (``for tok in server.stream(req.uid)``), poll it
        (``drain()`` / ``take(timeout=)``), or pass ``callback`` to
        get ``callback("token", tok)`` at each retire plus one
        ``callback("end", finish_reason)``.  Opening late is fine —
        the stream backfills everything already generated.  Requires
        ``enable_streaming``; unknown uids raise ``KeyError``."""
        with (self._ops_lock or _NO_LOCK):
            if self.stream_broker is None:
                raise RuntimeError(
                    "streaming is disabled (enable_streaming=False)")
            if isinstance(req_or_uid, Request):
                req = req_or_uid
            else:
                req = self._find_request(int(req_or_uid))
                if req is None:
                    raise KeyError(f"no request with uid "
                                   f"{req_or_uid} on this server")
            if self.journeys.enabled and req.journey is not None \
                    and not req.finished:
                self.journeys.hop(req.journey, "stream_open",
                                  uid=req.uid,
                                  backfill=len(req.generated))
            return self.stream_broker.open(req.uid, req, callback)

    def cancel(self, uid: int) -> bool:
        """Cancel one request by uid — the client hung up (the SSE
        front door calls this on a broken socket) or explicitly
        abandoned it.  Frees its blocks / lookahead / in-flight holds
        immediately with ``finish_reason="cancelled"``; a queued
        request simply leaves the queue.  Returns True if a live
        request was cancelled, False if the uid is unknown or already
        terminal (double-cancel is an idempotent no-op).

        Safe mid-pipeline: the launched-but-unretired window is
        flushed FIRST (the ``submit()`` write-safety idiom), so the
        device step that may still reference the request's blocks has
        fully retired before ``fail()`` releases them; a cancel
        arriving between a later launch and its retire is handled by
        the apply-side discard guards (``req.finished`` requests'
        retired tokens are dropped)."""
        with (self._ops_lock or _NO_LOCK):
            return self._cancel(uid)

    def _cancel(self, uid: int) -> bool:
        # the flush can retire final tokens and FINISH requests —
        # possibly the victim itself (the lost-race path) — so the
        # finalize below must run even when nothing is failed
        if self._inflight is not None:
            self._pending_produced += self._flush_window()
        cancelled = False
        for sched in self._schedulers():
            for req in (list(sched.running.values())
                        + list(sched.waiting)):
                if req.uid == uid and not req.finished:
                    sched.fail(req, reasons.CANCELLED)
                    cancelled = True
                    break
            if cancelled:
                break
        self._finalize_finished()
        return cancelled

    def _stream_stats(self) -> dict:
        """The ``stats()["streams"]`` block — broker counters plus the
        cancellation tally (meaningful even with streaming off)."""
        st = {"enabled": self.stream_broker is not None,
              "cancelled":
                  self.failures.count("requests_failed_cancelled")}
        if self.stream_broker is not None:
            st.update(self.stream_broker.stats())
            # bounded per-stream rows (``ops_probe --streams``)
            st["per_stream"] = self.stream_broker.snapshot()
        return st

    def evacuate(self, reason: str = reasons.REPLICA_FAILED) -> tuple:
        """Failover surgery for a server whose ENGINE is presumed dead
        (the router's circuit breaker tripped on repeated step
        failures — ``serving.router``).  Returns
        ``(requeueable, failed)``:

        - the launched-but-unretired window (if any) is dropped
          unconsumed — its device step belongs to a dead engine;
        - every admitted request that has not sampled a token yet
          (prefilling or pending its first decode) is preempted back
          to the queue — its K/V here is abandoned, and a fresh
          prefill elsewhere is bit-identical — then withdrawn along
          with the ordinary queued work as ``requeueable``;
        - every mid-stream request (tokens already emitted) fails
          with ``finish_reason=reason`` — its cache cannot move, and
          silently re-decoding it elsewhere would emit duplicate
          tokens to whoever is consuming the stream.  Its partial
          output stays on the request (the chaos oracle prefix-checks
          it).

        Host bookkeeping (scheduler/allocator/prefix cache) is purely
        host-side, so it stays audit-clean even when the engine is
        wedged — the pool is left consistent for a later recovery."""
        self._inflight = None
        self.scheduler.release_inflight()
        if self.disagg:
            # queued hand-offs' requests still live in the prefill
            # scheduler; the pool sweep below disposes of them, so the
            # queue entries just drop
            self._handoff.clear()
        failed = []
        for sched in self._schedulers():
            for req in list(sched.running.values()):
                if req.generated:
                    sched.fail(req, reason)
                    failed.append(req)
                else:
                    sched.preempt(req)
        requeueable = []
        for sched in self._schedulers():
            requeueable += sched.withdraw_waiting()
        self._finalize_finished()
        return requeueable, failed

    def _account_pending_produced(self) -> None:
        """Feed the token meter any production retired OUTSIDE a step
        (a ``submit()``-time window flush whose tokens no later step
        picked up — e.g. the submission was turned away and the
        server went idle)."""
        if self._pending_produced:
            self.tokens.update(self._pending_produced)
            self._pending_produced = 0

    def close(self) -> dict:
        """Graceful shutdown, phase two: :meth:`drain`, then refuse
        all further submissions (:class:`RuntimeError`).  Exactly-once:
        the drain runs on the first call only; repeated calls return
        the same final stats snapshot without re-running anything.
        An armed watchdog and an attached ops plane are stopped AFTER
        the drain completes, so ``/healthz`` reports ``draining``
        through the drain and the final scrape still answers."""
        if self._closed:
            return self._final_stats
        self._final_stats = self.drain()
        self._closed = True
        if self.watchdog.enabled:
            self.watchdog.stop()
        if self.ops is not None:
            self.ops.stop()
        self.kv_transport.close()
        return self._final_stats

    def reset_meters(self) -> None:
        """Zero the counters (after compile warmup, before a timed
        window) — a completed :meth:`generate` already returns every
        slot and block, so the server itself needs no reset."""
        self.tokens.reset()
        self.queue_depth.reset()
        self.pressure_gauge.reset()
        self.occupancy.reset()
        self.chunk_iters.reset()
        self.mem_live.reset()
        self.mem_free.reset()
        self.mem_evictable.reset()
        self.mem_frag.reset()
        self.ttft.reset()
        self.queue_wait.reset()
        for h in self._queue_wait_prio.values():
            h.reset()
        self.decode_latency.reset()
        self.itl.reset()
        self.handoff_pending.reset()
        self.step_time.reset()
        self.retire_wait.reset()
        self.plan_time.reset()
        self.spec_drafted_hist.reset()
        self.spec_accepted_hist.reset()
        self.offload_promote.reset()
        # journeys reset with the latency histograms their exemplars
        # index into — a bucket index only means anything within one
        # measurement window
        self.journeys.clear()
        self.scheduler.finished.clear()
        self._finalized = 0
        self._rec_cursor = 0
        # the flight ring resets with the step histograms — a bundle's
        # step accounting must reconcile against serving_step_s
        # (tools/postmortem.py --assert-complete), so their windows
        # have to start together
        self.recorder.clear()

    def _memory_stats(self) -> dict:
        """The ``stats()["memory"]`` block: live/free/evictable block
        occupancy with high-watermarks, the fragmentation gauge
        (allocated-but-unwritten token slots), and the speculation
        lookahead grant/rollback tallies.  Current values are read
        straight off the allocator/cache; the flight recorder carries
        the per-step time series behind them."""
        alloc = self.engine.allocator
        sched = self.scheduler
        usable = alloc.cfg.num_blocks - 1
        live = alloc.num_live
        frag = sched.frag_slots()
        info = self.engine.memory_info()
        # under disaggregation the prefix cache's evictable holds live
        # in the PREFILL pool — the decode pool's free/live/evictable
        # partition stays exact with evictable 0 here, and the
        # prefill pool's own partition rides in stats()["disagg"]
        cache_here = (self.prefix_cache
                      if self.prefix_cache is not None
                      and not self.disagg else None)
        out = {
            "blocks_usable": usable,
            "blocks_free": alloc.num_free,
            "blocks_live": live,
            "blocks_live_peak": alloc.live_peak,
            "blocks_evictable": (cache_here.num_evictable
                                 if cache_here is not None
                                 else 0),
            "blocks_evictable_peak": (cache_here.evictable_peak
                                      if cache_here is not None
                                      else 0),
            # the evictable holds PRICED in pool bytes (same
            # bytes_per_block math as pool_bytes, scale sidecars
            # included): the warm-but-reclaimable capacity an offload
            # sizing decision trades against host_bytes
            "evictable_bytes": (cache_here.num_evictable
                                if cache_here is not None else 0)
            * info["bytes_per_block"],
            "occupancy": round(live / usable, 3),
            "occupancy_peak": round(alloc.live_peak / usable, 3),
            "frag_slots": frag,
            "frag_frac": round(
                frag / (live * self.engine.block_size), 3)
            if live else 0.0,
            "lookahead_granted_blocks": sched.lookahead_granted,
            "lookahead_rolled_back_blocks": sched.lookahead_rolled_back,
            "pool_bytes": info["pool_bytes"],
            # the ACTUAL per-chip HBM cost, from the live arrays'
            # shard shape/dtype — equals pool_bytes unsharded, and
            # pool_bytes/tp under tensor parallelism; under
            # quantization both include the fp32 scale sidecar
            "pool_bytes_per_device": info["pool_bytes_per_device"],
            "bytes_per_block": info["bytes_per_block"],
            # what a token keeps a layer, as its family says
            # (models/family.py): "kv" every head's K|V pair, "latent"
            # one compressed row for all heads, and its bytes as stored
            "cache_kind": info["cache_kind"],
            "row_bytes_per_token_layer": info["row_bytes_per_token_layer"],
            "cache_dtype": info["cache_dtype"],
            # quantized KV pool (docs/serving.md, "Quantized KV
            # cache"): storage mode + the compute dtype values widen
            # to at read (None / == cache_dtype when off)
            "quantize": info["quantize"],
            "compute_dtype": info["compute_dtype"],
            # the pool by kind of layer (``models/family.py`` (d), (e)):
            # blocks of the layers that keep every token; and, where
            # the model has window or state layers, their rings
            "by_kind": {"full": {**info["by_kind"]["full"],
                                 "blocks_live": live}},
        }
        if "window" in info["by_kind"]:
            ring = self.engine.ring_rows
            cached = [r.num_cached for r in sched.running.values()]
            out["by_kind"]["window"] = {
                **info["by_kind"]["window"],
                # a layer's ring rows that hold a cached token's row
                "rows_live": sum(min(n, ring) for n in cached),
                # cached tokens' rows a layer's rings have let go:
                # of the running requests, and of every request since
                # the start
                "rows_let_go_live": sum(max(0, n - ring) for n in cached),
                "rows_let_go": sched.ring_rows_let_go + sum(
                    max(0, n - ring) for n in cached),
            }
        if "state" in info["by_kind"]:
            out["by_kind"]["state"] = {
                **info["by_kind"]["state"],
                # the slots whose rings hold a running request's state
                "slots_live": len(sched.running)}
        return out

    def _expert_stats(self) -> dict:
        """The ``stats()["experts"]`` block: the rows each expert of
        each expert layer was given since the start (or the last
        ``reset_cache``), as the programs counted them on the device in
        an array carried beside the pool.  It is read here and nowhere
        else: a step copies nothing of it to the host.  ``{"enabled":
        False}`` for a family without expert layers."""
        engines = [e for e in (self.engine, self.prefill_engine)
                   if e is not None and "routed" in e.cache]
        if not engines:
            return {"enabled": False}
        routed = sum(np.asarray(e.cache["routed"], np.int64)
                     for e in engines)
        mean = routed.mean(axis=1)
        return {
            "enabled": True,
            "layers": int(routed.shape[0]),
            "experts_held": int(routed.shape[1]),
            "rows_routed": int(routed.sum()),
            "routed": routed.tolist(),
            "max_over_mean": [round(float(m), 3) for m in
                              routed.max(axis=1) / np.maximum(mean, 1e-9)],
        }

    def _disagg_stats(self) -> dict:
        """The pinned ``stats()["disagg"]`` block: hand-off counters
        plus the PREFILL pool's memory partition (the decode pool owns
        ``stats()["memory"]``)."""
        if not self.disagg:
            return {"enabled": False}
        palloc = self.prefill_engine.allocator
        usable = palloc.cfg.num_blocks - 1
        return {
            "enabled": True,
            "prefill_max_concurrent":
                self.prefill_scheduler.max_batch_size,
            "prefill_blocks_usable": usable,
            "prefill_blocks_free": palloc.num_free,
            "prefill_blocks_live": palloc.num_live,
            "prefill_blocks_live_peak": palloc.live_peak,
            "prefill_blocks_evictable": (
                self.prefix_cache.num_evictable
                if self.prefix_cache is not None else 0),
            "prefill_evictable_bytes": (
                self.prefix_cache.num_evictable
                if self.prefix_cache is not None else 0)
            * self.prefill_engine.memory_info()["bytes_per_block"],
            "prefill_pool_bytes":
                self.prefill_engine.memory_info()["pool_bytes"],
            "prefill_backlog_blocks":
                self.prefill_scheduler.prefill_backlog_blocks(),
            "handoff": {
                "pending": len(self._handoff),
                "pending_peak": int(self.handoff_pending.peak),
                **self.handoffs.as_dict(),
            },
            "sink_attached": self.handoff_sink is not None,
        }

    def _offload_stats(self) -> dict:
        """The pinned ``stats()["offload"]`` block (docs/serving.md,
        "Hierarchical KV offload"): demote/promote/spill/reject
        counters from the ``serving_offload`` meter, the store's tier
        occupancy, and the promote-latency histogram.  Counter keys
        are present (zero) even before the first event — and with
        offload disabled — so dashboards and the flight recorder
        never key-miss."""
        c = self.offload.count
        store = self.offload_store
        return {
            "enabled": self.kv_offload,
            "demotes": c("demotes"),
            "demote_failed": c("demote_failed"),
            "promotes_host": c("promotes_host"),
            "promotes_disk": c("promotes_disk"),
            "spills": c("spills"),
            "crc_rejects": c("crc_rejects"),
            "disk_torn": c("disk_torn"),
            "capacity_skips": c("capacity_skips"),
            "transport_skips": c("transport_skips"),
            "host_dropped": c("host_dropped"),
            "host_entries": (store.host_entries
                             if store is not None else 0),
            "host_bytes": (store.host_used_bytes
                           if store is not None else 0),
            "host_bytes_cap": (store.host_bytes
                               if store is not None else 0),
            "disk_entries": (store.disk_entries
                             if store is not None else 0),
            "spill_dir": (store.spill_dir
                          if store is not None else None),
            "promote_ms": _hist_ms(self.offload_promote),
        }

    def _program_stats(self) -> dict:
        """The ``stats()["programs"]`` block: the per-compiled-program
        table (call count, host wall time, compile count/time,
        steady-state per-call ms per program/shape key) plus the
        totals and ``attention``, which way each serving program family was
        built to attend: ``"table"`` (the pool read in place through
        the block table) or ``"gathered"``, fixed when the engine
        built its programs."""
        table = self.programs.table()
        return {
            "enabled": self.programs.enabled,
            "by_program": table,
            "attention": dict(self.engine.attention_paths),
            "total_wall_ms": round(
                sum(r["wall_ms"] for r in table.values()), 3),
            "total_compile_ms": round(
                sum(r["compile_ms"] for r in table.values()), 3),
        }

    def stats(self) -> dict:
        """Serving counters for logs and the bench harness.

        Prefix-cache keys: ``prefix_hit_rate`` is hit tokens over all
        admitted context tokens; ``kv_blocks_cached`` counts indexed
        blocks (shared or evictable), ``kv_blocks_free`` only the
        truly-free list — reclaimable capacity is their sum plus
        evictable holds.

        Telemetry keys (``docs/observability.md``):
        ``tokens_per_s_recent`` is the trailing-window rate (recent
        throughput, vs the lifetime-average ``tokens_per_s``);
        ``latency`` carries p50/p90/p99 from the TTFT / queue-wait /
        per-token-decode / step-time histograms fed by the per-request
        timelines; ``slo`` is per-priority-class attainment +
        goodput-vs-throughput + shed debt; ``memory`` is the KV-pool
        occupancy/high-watermark/fragmentation breakdown;
        ``trace_dropped_events`` / ``flight`` surface ring-buffer
        loss so a truncated trace or flight log is never mistaken for
        the full run.  ``programs`` is the per-compiled-program
        call/wall/compile table, ``watchdog`` the hang detector's
        state, and ``ops`` the embedded HTTP endpoint's
        (``docs/observability.md``, "Ops plane & watchdog").  Every
        pre-telemetry key is preserved unchanged (asserted in
        ``tests/L0/test_serving_engine.py``)."""
        with (self._ops_lock or _NO_LOCK):
            return self._stats()

    def _stats(self) -> dict:
        """The :meth:`stats` body (runs under the ops lock when the
        HTTP ops plane is attached — ``/statusz`` serves this)."""
        self._account_pending_produced()
        self._finalize_finished()
        pre, dec = self.engine.compile_counts()
        out = {
            "tokens_generated": self.tokens.total,
            "tokens_per_s": round(self.tokens.rate, 1),
            "tokens_per_s_recent": round(
                self.tokens.rate_over(RECENT_RATE_WINDOW_S), 1),
            "queue_depth_peak": self.queue_depth.peak,
            "batch_occupancy_avg": round(self.occupancy.avg, 3),
            "prefill_compiles": pre,
            "decode_compiles": dec,
            "kv_blocks_free": self.engine.allocator.num_free,
            "requests_finished": len(self.scheduler.finished),
            "preemptions": sum(r.preemptions
                               for r in self.scheduler.finished),
            "requests_failed": self.failures.as_dict(),
            "requests_failed_total": self.failures.total,
            "prefill_chunks": self.prefix.count("prefill_chunks"),
            "chunk_iters_peak": self.chunk_iters.peak,
            # overload / lifecycle telemetry (docs/resilience.md,
            # "Overload policy & lifecycle")
            "pressure": round(self.pressure_gauge.val, 3),
            "pressure_peak": round(self.pressure_gauge.peak, 3),
            "breaker_state": self.breaker.state,
            "breaker_events": self.breaker_events.as_dict(),
            "oom_events": self.oom.total,
            "draining": self._draining,
            # speculative decoding (docs/serving.md): acceptance-rate
            # counters, engine-step accounting, and the per-verify
            # drafted/accepted depth histograms.  decode_tokens /
            # decode_steps only count the decode phase (prefill-sampled
            # first tokens excluded), so tokens_per_engine_step is the
            # speculation speedup axis the bench floors.
            "speculation": {
                "enabled": self.speculating,
                "spec_tokens": self.spec_tokens,
                "drafted_tokens": self.spec.count("drafted_tokens"),
                "accepted_tokens": self.spec.count("accepted_tokens"),
                "acceptance_rate": round(self.spec.ratio(
                    "accepted_tokens", "drafted_tokens"), 3),
                "verify_steps": self.spec.count("verify_steps"),
                "decode_steps": self.spec.count("decode_steps"),
                "decode_tokens": self.spec.count("decode_tokens"),
                "tokens_per_engine_step": round(
                    self.spec.count("decode_tokens")
                    / max(1, self.spec.count("verify_steps")
                          + self.spec.count("decode_steps")), 3),
                "verify_compiles": self.engine.verify_compiles(),
                "drafted_per_step": _hist_counts(self.spec_drafted_hist),
                "accepted_per_step": _hist_counts(
                    self.spec_accepted_hist),
                # the draft source's calls, and the per-request indexes
                # it built from scratch (a request's first call, a
                # history it had not indexed, a compaction)
                "draft_calls": self.spec.count("draft_calls"),
                "draft_index_rebuilds": self.spec.count(
                    "draft_index_rebuilds"),
            },
            # stochastic sampling (docs/serving.md, "Stochastic
            # sampling"): per-class request traffic and the
            # rejection-sampling accounting — stochastic drafts
            # accept with prob p(draft) under the Gumbel-max
            # coupling, each first rejection emitting one residual
            # resample
            "sampling": {
                "requests": self.sampling_classes.as_dict(),
                "rejection": {
                    "drafted_tokens":
                        self.spec.count("stoch_drafted_tokens"),
                    "accepted_tokens":
                        self.spec.count("stoch_accepted_tokens"),
                    "acceptance_rate": round(self.spec.ratio(
                        "stoch_accepted_tokens",
                        "stoch_drafted_tokens"), 3),
                    "resamples": self.spec.count("stoch_resamples"),
                },
            },
            # pipelined serve loop (docs/serving.md, "Pipelined serve
            # loop"): dispatch-ahead depth and the host-stall /
            # device-stall split — host_stall_ms is the retire-time
            # wait on device results (device-bound share),
            # host_plan_ms the host scheduling+launch work the device
            # overlaps (host-bound share); a well-overlapped step
            # costs ~max of the two, a serial one their sum.
            "pipeline": {
                "enabled": self.pipelining,
                "depth": 1 if self.pipelining else 0,
                "launches": self.pipe.count("launches"),
                "retired_behind": self.pipe.count("retired_behind"),
                "pending": 1 if self._inflight is not None else 0,
                "host_stall_ms": _hist_ms(self.retire_wait),
                "host_plan_ms": _hist_ms(self.plan_time),
            },
            "latency": {
                "ttft_ms": _hist_ms(self.ttft),
                "queue_wait_ms": _hist_ms(self.queue_wait),
                "decode_token_ms": _hist_ms(self.decode_latency),
                # per-TOKEN inter-token gaps (vs decode_token_ms's
                # per-request average): the tail the disaggregation
                # bench floors (docs/serving.md)
                "itl_ms": _hist_ms(self.itl),
                "step_ms": _hist_ms(self.step_time),
                "queue_wait_by_priority_ms": {
                    p: _hist_ms(h) for p, h in
                    sorted(self._queue_wait_prio.items())},
            },
            # per-compiled-program accounting (docs/observability.md,
            # "Ops plane & watchdog"): where does the step go, per
            # program and shape key — steady_ms excludes compile calls
            "programs": self._program_stats(),
            # hang watchdog: armed state, latched stall flag (what
            # /healthz keys on), and the exactly-once stall count
            "watchdog": {
                "enabled": self.watchdog.enabled,
                "stalled": self.watchdog.stalled,
                "stalls": self.watchdog.stalls,
                "deadline_s": self.watchdog.deadline_s,
            },
            # embedded HTTP ops plane: bound port + served requests
            "ops": {
                "enabled": self.ops is not None,
                "port": self.ops.port if self.ops is not None else None,
                "requests": self.ops_requests.total,
            },
            # streaming delivery (docs/serving.md, "Streaming &
            # cancellation"): broker fan-out counters + cancellations
            "streams": self._stream_stats(),
            # disaggregated prefill/decode pools (docs/serving.md,
            # "Disaggregated prefill/decode"): the prefill pool's own
            # free/live/evictable partition plus the hand-off
            # counters; {enabled: False} on a monolithic server
            "disagg": self._disagg_stats(),
            # hierarchical KV offload (docs/serving.md, "Hierarchical
            # KV offload"): tier-crossing counters (demote / promote
            # by hit tier / spill / integrity rejects), store
            # occupancy, and the promote-latency histogram;
            # {"enabled": False} with zeroed counters when off —
            # shape-stable either way
            "offload": self._offload_stats(),
            # KV transport (docs/serving.md, "KV transport"): the
            # retry/deadline/breaker envelope every cross-pool block
            # movement rides — totals plus per-peer counters and
            # breaker state; shape-stable, backend-tagged
            "transport": self.kv_transport.stats(),
            # tensor-parallel serving (docs/serving.md,
            # "Tensor-parallel serving"): mesh geometry, tp degree,
            # per-shard KV bytes, and the mesh-lowered program count —
            # pinned like the blocks above; {enabled: False, tp: 1}
            # on a single-chip server
            "sharding": self.engine.sharding_info(),
            # SLO attainment + goodput-vs-throughput
            # (docs/observability.md, "SLO & goodput")
            "slo": self.slo.as_stats(),
            # predictive admission (docs/resilience.md): learned
            # per-class service floors + submit-time shed tally;
            # {enabled: False} unless the policy armed it
            "admission": (self.admission.as_stats()
                          if self.admission is not None
                          else {"enabled": False}),
            # KV memory occupancy, high-watermarks, fragmentation
            # (docs/observability.md, "Memory accounting")
            "memory": self._memory_stats(),
            # routed experts (docs/observability.md, "Expert load"):
            # rows given to each expert of each expert layer, counted
            # on the device and read only here
            "experts": self._expert_stats(),
            # ring-buffer loss accounting: a saturated tracer or
            # recorder silently truncates history — surface it
            "trace_dropped_events": self.tracer.dropped,
            "flight": {
                "enabled": self.recorder.enabled,
                "steps_recorded": self.recorder.steps_recorded,
                "dropped": self.recorder.dropped,
            },
            # journey correlation plane (docs/observability.md,
            # "Request journeys & exemplars"): pinned census —
            # shape-stable enabled or not, like flight/offload
            "journeys": self.journeys.census(),
        }
        if self.prefix_cache is not None:
            out.update({
                "prefix_hit_tokens":
                    self.prefix.count("prefix_hit_tokens"),
                "prefix_miss_tokens":
                    self.prefix.count("prefix_miss_tokens"),
                "prefix_hit_requests":
                    self.prefix.count("prefix_hit_requests"),
                "prefix_hit_rate": round(self.prefix.ratio(
                    "prefix_hit_tokens",
                    "prefix_hit_tokens", "prefix_miss_tokens"), 3),
                "prefix_evicted_blocks":
                    self.prefix.count("prefix_evicted_blocks"),
                "prefix_cow_blocks":
                    self.prefix.count("prefix_cow_blocks"),
                "kv_blocks_cached": self.prefix_cache.num_cached_blocks,
                "kv_blocks_evictable": self.prefix_cache.num_evictable,
            })
        return out
