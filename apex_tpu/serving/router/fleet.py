"""`RouterFleet` — N in-process replicas behind one front door.

The driver half of the router subsystem: builds the replicas (one
:class:`~serving.InferenceServer` each, optionally each carrying its
own ``mesh=``/``tp=`` slice — the replicas-of-shards topology), wires
them into a :class:`~serving.router.router.ReplicaRouter`, and
exposes the same four-call surface as one server:

- ``submit()`` — routed by pressure/affinity/health
  (:mod:`serving.router.policy`);
- ``step()`` — one round-robin pass over the replicas (each replica
  advances one continuous-batching iteration; the rotation point
  moves every fleet step so no replica systematically retires first).
  ``threaded=True`` steps the replicas concurrently on a private
  thread pool — each replica's device step is independent, so on a
  multi-core host (or N real device sets) the fleet step costs ~the
  slowest replica, not the sum.  Breaker bookkeeping and failover
  stay serial either way (``ReplicaRouter.absorb_step``), so the two
  modes make identical routing decisions;
- ``drain()`` — fleet-wide graceful shutdown (every replica stops
  admitting, in-flight work runs to terminal states);
  ``drain_replica()`` / ``revive()`` are the rolling-restart pair;
- ``stats()`` — fleet aggregates plus the pinned ``stats()["router"]``
  block (per-replica pressure/live/finished, affinity
  hit/spill/re-enqueue counters, per-replica breaker snapshots).

Router × TP (``docs/serving.md``, "Multi-replica routing"): pass
``tp=K`` and each replica gets its OWN ``jax.sharding.Mesh`` over a
disjoint ``K``-device slice — ``replicas * tp`` devices total — so
request-level data parallelism composes with tensor-parallel decode
exactly as it would across real hosts.

An optional aggregate ops plane (``ops_port=``) serves the fleet the
same way a single server's does: ``/healthz`` answers for the fleet
(ok / draining / closed) with the router's pressure gauge,
``/statusz`` is the fleet ``stats()``, ``/metrics`` the router
registry, and ``/debug/requests/<uid>`` finds a request on whichever
replica holds it.
"""

from __future__ import annotations

import contextlib
import dataclasses
import os
import time
from concurrent.futures import ThreadPoolExecutor
from typing import Callable, List, Optional, Sequence

from apex_tpu.observability import (
    JOURNEYS_ENV,
    NULL_FLIGHT_RECORDER,
    NULL_JOURNEY_LOG,
    NULL_WATCHDOG,
    JourneyLog,
    MetricsRegistry,
    OpsServer,
    dump_journeys,
    fleet_prometheus_text,
    get_tracer,
    journeys_census,
    merge_journeys,
    resolve_journeys,
    write_postmortem,
)
from apex_tpu.resilience.breaker import CircuitBreaker
from apex_tpu.serving import reasons
from apex_tpu.serving.api import InferenceServer
from apex_tpu.serving.elastic import Autoscaler, AutoscalerConfig
from apex_tpu.serving.elastic.rollout import rollout_fleet
from apex_tpu.serving.router.policy import RouterPolicy
from apex_tpu.serving.router.replica import Replica
from apex_tpu.serving.router.router import ReplicaRouter, RouterRequest
from apex_tpu.serving.scheduler import Request
from apex_tpu.serving.streaming import StreamBroker, TokenStream
from apex_tpu.serving.transport import (
    InProcessTransport,
    KVTransport,
    TransportError,
    TransportPolicy,
)
from apex_tpu.utils import GaugeMeter

__all__ = ["RouterFleet"]

_NO_LOCK = contextlib.nullcontext()


class _FleetSchedView:
    """Duck-typed aggregate ``scheduler`` for the ops plane: the
    endpoints only read ``waiting`` / ``running`` / ``finished`` /
    ``has_work``, so the view concatenates the replicas' live state
    on access (``running`` keyed by uid — what ``/debug/requests``
    actually looks up)."""

    def __init__(self, fleet: "RouterFleet"):
        self._fleet = fleet

    @property
    def waiting(self):
        return [r for rep in self._fleet.replicas
                for r in rep.server.scheduler.waiting]

    @property
    def running(self):
        return {r.uid: r for rep in self._fleet.replicas
                for r in rep.server.scheduler.running.values()}

    @property
    def finished(self):
        return [r for rep in self._fleet.replicas
                for r in rep.server.scheduler.finished]

    @property
    def has_work(self):
        return self._fleet.has_work


class RouterFleet:
    """N routed replicas with one ``submit/step/drain/stats`` door.

    Args:
      cfg, params: the model every replica serves (shared host-side;
        each replica holds its own device arrays and compiled
        programs — that is the point of a replica).
      replicas: fleet size (>= 1).
      policy: the :class:`RouterPolicy`; default stock affinity with
        ``affinity_block`` snapped to the replicas' KV block size so
        router-side matches predict replica-side cache hits.
      make_server: optional ``make_server(i) -> InferenceServer``
        factory overriding replica construction entirely (mutually
        exclusive with ``tp=``); the default builds
        ``InferenceServer(cfg, params, clock=clock, **server_kwargs)``
        per replica — each with its OWN private registry, so
        per-replica counters never alias.
      tp: tensor-parallel degree PER REPLICA — each replica gets a
        disjoint ``tp``-device mesh slice (Router × TP; needs
        ``replicas * tp`` visible devices).
      tp_axis: the mesh axis name (default ``"model"``).
      breaker_factory: ``(i) -> CircuitBreaker`` for the router-side
        per-replica breakers (default: 3-failure threshold on
        ``clock``).
      threaded: step replicas concurrently on a private thread pool
        (identical routing decisions either way; see module
        docstring).
      clock / registry / tracer: the fleet's time source, metrics
        registry (router counters + per-replica pressure gauges), and
        span tracer.
      ops_port: serve the aggregate ops plane on this loopback port
        (0 = ephemeral), mirroring ``InferenceServer(ops_port=)``.
      **server_kwargs: passed to every default-built replica
        (``max_batch_size``, ``block_size``, ``cache_dtype``, ...).
    """

    def __init__(self, cfg, params, *, replicas: int = 2,
                 policy: Optional[RouterPolicy] = None,
                 make_server: Optional[Callable] = None,
                 names: Optional[Sequence[str]] = None,
                 tp: Optional[int] = None, tp_axis: str = "model",
                 breaker_factory: Optional[Callable] = None,
                 threaded: bool = False,
                 clock: Callable[[], float] = time.monotonic,
                 registry: Optional[MetricsRegistry] = None,
                 tracer=None,
                 ops_port: Optional[int] = None,
                 disagg_prefill: int = 0,
                 disagg_prefill_threshold: Optional[int] = None,
                 enable_streaming: bool = True,
                 stream_queue_tokens: int = 256,
                 enable_elastic: bool = False,
                 elastic: Optional[AutoscalerConfig] = None,
                 enable_journeys: Optional[bool] = None,
                 kv_transport: Optional[KVTransport] = None,
                 **server_kwargs):
        if replicas < 1:
            raise ValueError(f"replicas must be >= 1, got {replicas}")
        if make_server is not None and tp:
            raise ValueError(
                "pass either make_server= or tp= — a custom factory "
                "owns its replicas' meshes")
        if disagg_prefill and not 0 < disagg_prefill < replicas:
            raise ValueError(
                f"disagg_prefill={disagg_prefill} must leave at least "
                f"one decode-capable replica (replicas={replicas})")
        self.registry = registry if registry is not None \
            else MetricsRegistry()
        self.tracer = tracer if tracer is not None else get_tracer()
        self.clock = clock
        # journey correlation plane (docs/observability.md, "Request
        # journeys & exemplars"; OFF by default): the fleet arms one
        # log per replica (replica=<name>) plus a router-level one
        # (replica="router") for route/failover/hand-off hops, and
        # journey(rid) merges them causally by hop seq
        if enable_journeys is None:
            enable_journeys = os.environ.get(JOURNEYS_ENV)
        self._enable_journeys = resolve_journeys(enable_journeys)
        self.journeys = (
            JourneyLog(replica="router",
                       iter_source=lambda: self._iter, clock=clock)
            if self._enable_journeys else NULL_JOURNEY_LOG)
        self._journey_name_next: Optional[str] = None
        # the fleet keeps its construction recipe: scale-up builds
        # new replicas from the same factory/kwargs, and rollout
        # rebinds self.params so post-rollout scale-ups serve the
        # NEW weights (serving/elastic)
        self.cfg = cfg
        self.params = params
        self._server_kwargs = dict(server_kwargs)
        self._breaker_factory = breaker_factory
        self._weights_version: Optional[str] = None
        self._last_rollout: Optional[dict] = None
        self._rollout_active = False
        self.retired_replicas: List[Replica] = []
        meshes: List = [None] * replicas
        if tp:
            import jax
            import numpy as np
            from jax.sharding import Mesh

            devs = jax.devices()
            need = tp * replicas
            if len(devs) < need:
                raise ValueError(
                    f"Router x TP needs replicas*tp = {need} devices "
                    f"for {replicas} replicas of tp={tp}, have "
                    f"{len(devs)}")
            meshes = [Mesh(np.asarray(devs[i * tp:(i + 1) * tp]),
                           (tp_axis,)) for i in range(replicas)]

        def default_server(i: int) -> InferenceServer:
            kw = dict(server_kwargs)
            # scaled-up replicas (i beyond the construction-time
            # fleet) are meshless "any"-role; reading self.params
            # (not the closure arg) keeps them on the rolled-out
            # weight version
            if i < len(meshes) and meshes[i] is not None:
                kw.setdefault("mesh", meshes[i])
                kw.setdefault("tp_axis", tp_axis)
            if i < disagg_prefill:
                # a prefill-role replica runs its server DISAGGREGATED
                # so every prefill lands in the dedicated prefill pool
                # and finished KV ships through the hand-off sink
                # (wired below); its own decode pool stays the
                # last-resort local fallback
                kw.setdefault("enable_disagg", True)
            if self._enable_journeys:
                # each replica's log is labeled with its fleet name so
                # merged journeys read replica0 -> replica2, not
                # server/server (scale-ups pass their serial name via
                # _journey_name_next)
                kw.setdefault("enable_journeys", True)
                kw.setdefault(
                    "journey_replica",
                    self._journey_name_next
                    or (names[i] if names and i < len(names)
                        else f"replica{i}"))
            return InferenceServer(cfg, self.params, clock=clock,
                                   **kw)

        build = make_server or default_server
        self._build = build
        self.replicas: List[Replica] = []
        for i in range(replicas):
            srv = build(i)
            breaker = (breaker_factory(i) if breaker_factory is not None
                       else CircuitBreaker(failure_threshold=3,
                                           clock=clock))
            name = names[i] if names else None
            self.replicas.append(
                Replica(i, srv, name=name, breaker=breaker,
                        role="prefill" if i < disagg_prefill
                        else "any"))
        if policy is None:
            policy = RouterPolicy(
                affinity_block=self.replicas[0].server.engine.block_size,
                disagg_prefill_threshold=(
                    disagg_prefill_threshold if disagg_prefill
                    else None))
        # cross-replica KV transport (docs/serving.md, "KV
        # transport"): hand-off and warm payloads ride this backend;
        # the router registers every replica as a peer (elastic
        # scale-ups included) and the in-process default is
        # behavior-identical to the historical direct calls
        self.kv_transport = kv_transport if kv_transport is not None \
            else InProcessTransport(policy=TransportPolicy(clock=clock))
        self.router = ReplicaRouter(self.replicas, policy=policy,
                                    clock=clock,
                                    registry=self.registry,
                                    tracer=self.tracer,
                                    journeys=self.journeys,
                                    transport=self.kv_transport)
        # wire each prefill-role replica's hand-off sink to the router
        # (the server exports the blocks; the router places the decode
        # half — docs/serving.md, "Disaggregated prefill/decode")
        for rep in self.replicas:
            if rep.role == "prefill" and rep.server.disagg:
                rep.server.handoff_sink = \
                    self.router.handoff_sink_for(rep)
        if disagg_prefill and \
                self.router.policy.disagg_prefill_threshold is None:
            # default: prompts spanning >= 4 KV blocks are worth the
            # cross-replica transfer; shorter ones stay monolithic
            self.router.policy = dataclasses.replace(
                self.router.policy,
                disagg_prefill_threshold=(
                    4 * self.replicas[0].server.engine.block_size))
        self.threaded = bool(threaded)
        self._pool = (ThreadPoolExecutor(
            max_workers=replicas,
            thread_name_prefix="apex-tpu-router")
            if self.threaded and replicas > 1 else None)
        self._iter = 0
        self._draining = False
        self._closed = False
        self._final_stats: Optional[dict] = None
        # fleet-level pressure (max over alive replicas) — the ops
        # plane's /healthz pressure field, and the router's own
        # saturation signal
        self.pressure_gauge = GaugeMeter(registry=self.registry,
                                         name="router_pressure")
        self._replica_pressure = [
            GaugeMeter(registry=self.registry,
                       name="router_replica_pressure",
                       replica=rep.name)
            for rep in self.replicas]
        # ops-plane duck-type surface (the aggregate view): the fleet
        # has no single flight ring / watchdog / submit breaker — the
        # per-replica ones live behind each replica's own ops plane
        self.watchdog = NULL_WATCHDOG
        self.recorder = NULL_FLIGHT_RECORDER
        self.breaker = None
        self.scheduler = _FleetSchedView(self)
        self._postmortem_dir = None
        # fleet-level streaming front door (docs/serving.md,
        # "Streaming & cancellation"): streams key on the STABLE
        # ``rid`` and read through the RouterRequest proxy, so a
        # stream survives failover re-enqueue and hand-off rebinds;
        # the cursor pump republishes from the proxy's token list and
        # the broker's index dedup drops anything already delivered
        self.stream_broker: Optional[StreamBroker] = (
            StreamBroker(queue_tokens=stream_queue_tokens)
            if enable_streaming else None)
        self._stream_reqs: dict = {}     # rid -> RouterRequest
        self._stream_cursors: dict = {}  # rid -> publish high-water
        # elastic control loop (docs/serving.md, "Elastic fleet"):
        # OFF by default — a fleet without it is byte-identical to
        # the pre-elastic fleet.  Scaled-up replicas take serial
        # names (replicaN, N ever-increasing) so a retire + regrow
        # never aliases stats rows.
        self._replica_serial = replicas
        self.autoscaler: Optional[Autoscaler] = (
            Autoscaler(self, elastic, clock=clock)
            if enable_elastic else None)
        self.ops: Optional[OpsServer] = None
        self._ops_lock = None
        if ops_port is not None:
            self.ops = OpsServer(self, port=ops_port)
            self._ops_lock = self.ops.lock
            self.ops.start()

    # -- the one-door surface ----------------------------------------------

    def submit(self, prompt: Sequence[int], max_new_tokens: int,
               eos_id: Optional[int] = None, *,
               priority: int = 0,
               deadline_iters: Optional[int] = None,
               deadline_s: Optional[float] = None) -> RouterRequest:
        """Route one request (see :meth:`ReplicaRouter.submit`)."""
        with (self._ops_lock or _NO_LOCK):
            if self._closed:
                raise RuntimeError(
                    "RouterFleet is closed; no further submissions")
            if self._draining:
                # fleet-level drain: finish at the front door exactly
                # like a draining single server would — without
                # consuming a placement
                now = self.clock()
                inner = Request(prompt=[int(t) for t in prompt],
                                max_new_tokens=int(max_new_tokens),
                                eos_id=eos_id,
                                priority=int(priority),
                                submitted_at=now)
                inner.finished = True
                inner.finish_reason = reasons.DRAINING
                inner.finished_at = now
                rr = RouterRequest(inner, None)
                self.router.requests.append(rr)
                return rr
            return self.router.submit(
                prompt, max_new_tokens, eos_id, priority=priority,
                deadline_iters=deadline_iters, deadline_s=deadline_s)

    def step(self) -> int:
        """One fleet iteration: every non-open replica advances one
        continuous-batching step (rotating the start point for
        fairness), then breaker bookkeeping and any failover run
        serially.  Returns tokens produced across the fleet."""
        with (self._ops_lock or _NO_LOCK):
            return self._step()

    def _step(self) -> int:
        self._iter += 1
        n = len(self.replicas)
        k = self._iter % n
        order = self.replicas[k:] + self.replicas[:k]
        router = self.router
        if self._pool is not None:
            futures = {rep: self._pool.submit(router.try_step, rep)
                       for rep in order}
            results = {rep: f.result() for rep, f in futures.items()}
        else:
            results = {rep: router.try_step(rep) for rep in order}
        produced = 0
        for rep in order:
            produced += router.absorb_step(rep, results[rep])
        peak = 0.0
        for rep, gauge in zip(self.replicas, self._replica_pressure):
            p = rep.pressure()
            gauge.update(p)
            if rep.alive and p > peak:
                peak = p
        self.pressure_gauge.update(peak)
        self._pump_streams()
        # the control loop ticks last, on this step's fresh gauges;
        # it stands down while a drain or rollout owns the replica
        # list (one lifecycle driver at a time)
        if self.autoscaler is not None and not self._draining \
                and not self._rollout_active:
            self.autoscaler.observe()
        return produced

    # -- elastic fleet (docs/serving.md, "Elastic fleet") ------------------

    def shed_debt_tokens(self) -> int:
        """Cumulative SLO debt (shed tokens) across the fleet —
        retired replicas included, so the autoscaler's trend signal
        never jumps backwards on a scale-down."""
        return sum(
            rep.server.slo.as_stats()["debt"]["shed_tokens"]
            for rep in self.replicas + self.retired_replicas)

    def add_replica(self, *, warm_blocks: int = 0) -> Replica:
        """Grow the fleet by one replica built from the construction
        recipe (factory or default kwargs), optionally warming its
        prefix cache from a donor.  Manual actuator — the autoscaler
        calls the unlocked body."""
        with (self._ops_lock or _NO_LOCK):
            rep, _ = self._add_replica(warm_blocks=warm_blocks)
            return rep

    def _add_replica(self, *, warm_blocks: int = 0):
        i = len(self.replicas)
        name = f"replica{self._replica_serial}"
        self._replica_serial += 1
        # the default factory reads the serial name for its journey
        # log label (the positional default would alias a retired
        # replica's rows after a scale-down + regrow)
        self._journey_name_next = name
        try:
            srv = self._build(i)
        finally:
            self._journey_name_next = None
        breaker = (self._breaker_factory(i)
                   if self._breaker_factory is not None
                   else CircuitBreaker(failure_threshold=3,
                                       clock=self.clock))
        rep = Replica(i, srv, name=name, breaker=breaker, role="any")
        rep.weights_version = self._weights_version
        # append-at-end ONLY: the affinity index stores positional
        # replica indices, so any other insertion point would remap
        # every existing entry under the router's feet
        self.replicas.append(rep)
        self.router.add_replica(rep)
        self._replica_pressure.append(
            GaugeMeter(registry=self.registry,
                       name="router_replica_pressure",
                       replica=rep.name))
        warmed = self._warm_replica(rep, warm_blocks) \
            if warm_blocks > 0 else 0
        return rep, warmed

    def _warm_replica(self, rep: Replica, max_blocks: int) -> int:
        """Seed the new replica's prefix cache from the best donor
        over the checksummed block-transfer path.  Best-effort: any
        failure (no donor, no spare blocks, torn payload) leaves the
        replica cold, never broken."""
        dst_srv = rep.server
        dst_pc = dst_srv.prefix_cache
        if dst_pc is None:
            return 0
        donor, best = None, 0
        for cand in self.replicas:
            if cand is rep or not cand.alive or cand.draining:
                continue
            pc = cand.server.prefix_cache
            if pc is not None and pc.num_cached_blocks > best:
                best = pc.num_cached_blocks
                donor = cand
        if donor is None:
            return 0
        src_srv = donor.server
        nodes = src_srv.prefix_cache.export_nodes(max_blocks)
        if not nodes:
            return 0
        # the engines that OWN the prefix pool (the prefill pool
        # under disaggregation)
        src_eng = src_srv.prefill_engine or src_srv.engine
        dst_eng = dst_srv.prefill_engine or dst_srv.engine
        # warm only into genuinely spare capacity: the new replica
        # must still admit a full-context request immediately
        spare = dst_eng.allocator.num_free - dst_eng.blocks_per_seq
        n = min(len(nodes), max(0, spare))
        if n <= 0:
            return 0
        nodes = nodes[:n]
        src_ids = [blk for _, _, blk in nodes]
        try:
            payload = src_eng.export_blocks(src_ids)
        except Exception:
            return 0
        # the bulk KV bytes ride the transport (alloc + import happen
        # in the peer handler — the receiver owns its pool); the
        # control plane (donor choice, spare-capacity read, radix
        # seeding below) stays in-process
        try:
            ack = self.kv_transport.send(rep.name, {"op": "warm"},
                                         payload)
        except (ValueError, MemoryError, TransportError):
            # torn transfer (checksum rejected whole), receiver OOM,
            # or an exhausted envelope: the handler freed its staging
            # blocks — start cold, never broken
            return 0
        dst_ids = ack.get("blocks")
        if not dst_ids:
            return 0
        return dst_pc.seed_nodes(nodes, dict(zip(src_ids, dst_ids)))

    def remove_replica(self) -> Replica:
        """Retire the LAST replica (it must already be drained dry —
        ``drain_replica`` + stepping first).  The server closes; the
        replica moves to ``retired_replicas`` so its finished ledger
        keeps counting in fleet aggregates."""
        with (self._ops_lock or _NO_LOCK):
            return self._remove_replica()

    def _remove_replica(self) -> Replica:
        rep = self.replicas[-1]
        if not (rep.draining and not rep.server.has_work):
            raise RuntimeError(
                f"{rep.name} still has work or is not draining; "
                f"drain it dry before remove_replica()")
        self.replicas.pop()
        self.router.remove_replica(rep)
        gauge = self._replica_pressure.pop()
        gauge.update(0.0)
        rep.server.close()
        self.retired_replicas.append(rep)
        return rep

    def _probe_server(self, params) -> InferenceServer:
        """A standalone (never-routed) server for the rollout parity
        audit — same model kwargs as a default replica, its own
        private registry, NO entry in any fleet ledger, so probe
        traffic can never pollute the soaks' exactly-once
        accounting."""
        return InferenceServer(self.cfg, params, clock=self.clock,
                               **self._server_kwargs)

    def rollout(self, checkpoint_dir: str, **kwargs) -> dict:
        """Zero-downtime weight rollout of the newest checkpoint
        under ``checkpoint_dir`` (``serving/elastic/rollout.py``:
        per-replica drain -> swap -> verify -> revive behind an A/B
        output-parity gate; halt + rollback on any failure).  Runs
        UNLOCKED like :meth:`drain` — every fleet call it makes
        self-locks, and holding the ops lock across a multi-step
        drain would starve the handlers."""
        return rollout_fleet(self, checkpoint_dir, **kwargs)

    # -- streaming & cancellation (docs/serving.md) ------------------------

    def _pump_streams(self) -> None:
        """Fan this fleet step's tokens out to open streams.  Reads go
        through the RouterRequest proxy, so a rebind (failover
        re-enqueue, hand-off, monolithic fallback) is transparent:
        the moved request regenerates its stream bit-identically, the
        publish cursor only ever advances, and the broker's index
        dedup discards the already-delivered prefix."""
        b = self.stream_broker
        if b is None or not self._stream_reqs:
            return
        for rid, rr in list(self._stream_reqs.items()):
            gen = rr.generated
            cur = self._stream_cursors.get(rid, 0)
            for i in range(cur, len(gen)):
                b.publish(rid, i, gen[i])
            if len(gen) > cur:
                self._stream_cursors[rid] = len(gen)
            if rr.finished:
                b.finish(rid, rr.finish_reason or "")
                self._stream_reqs.pop(rid, None)
                self._stream_cursors.pop(rid, None)

    def _resolve_request(self, which) -> Optional[RouterRequest]:
        """The RouterRequest for a proxy or rid (None if unknown)."""
        if isinstance(which, RouterRequest):
            return which
        rid = int(which)
        for rr in self.router.requests:
            if rr.rid == rid:
                return rr
        return None

    def stream(self, req_or_rid, callback: Optional[Callable] = None
               ) -> TokenStream:
        """The per-token stream for a routed request — the fleet
        front door's delivery surface (same contract as
        :meth:`InferenceServer.stream`, keyed by the stable ``rid``).
        Opening late backfills; the stream survives failover and
        hand-off and ends with a terminal event carrying the
        ``finish_reason``."""
        with (self._ops_lock or _NO_LOCK):
            if self.stream_broker is None:
                raise RuntimeError(
                    "streaming is disabled (enable_streaming=False)")
            rr = self._resolve_request(req_or_rid)
            if rr is None:
                raise KeyError(
                    f"no routed request with rid {req_or_rid}")
            s = self.stream_broker.open(rr.rid, rr, callback)
            if not rr.finished:
                self._stream_reqs[rr.rid] = rr
                self._pump_streams()
            return s

    def cancel(self, req_or_rid) -> bool:
        """Cancel a routed request wherever it currently lives (the
        SSE front door's disconnect hook).  Scans the replicas by the
        CURRENT inner uid, so a request that moved since submission is
        still found; idempotent — False for unknown/terminal."""
        with (self._ops_lock or _NO_LOCK):
            rr = self._resolve_request(req_or_rid)
            if rr is None or rr.finished:
                return False
            uid = rr.inner.uid
            for rep in self.replicas:
                if rep.server.cancel(uid):
                    self._pump_streams()
                    return True
            return False

    def _stream_stats(self) -> dict:
        """The fleet ``stats()["streams"]`` block: front-door broker
        counters + fleet-wide cancellation tally."""
        cancelled = sum(
            rep.server.failures.count("requests_failed_cancelled")
            for rep in self.replicas)
        st = {"enabled": self.stream_broker is not None,
              "cancelled": cancelled}
        if self.stream_broker is not None:
            st.update(self.stream_broker.stats())
            # bounded per-stream rows (``ops_probe --streams``)
            st["per_stream"] = self.stream_broker.snapshot()
        return st

    @property
    def has_work(self) -> bool:
        """Any live (non-open) replica still holding queued, running,
        or launched-but-unretired work.  Open replicas never count:
        failover already evacuated them."""
        return any(rep.server.has_work for rep in self.replicas
                   if rep.breaker.state != "open")

    def generate(self, prompts: Sequence[Sequence[int]],
                 max_new_tokens: int,
                 eos_id: Optional[int] = None, *,
                 priority: int = 0,
                 return_requests: bool = False):
        """Batch-synchronous front door, fleet edition: route all
        prompts, run the fleet to completion, return the generated
        ids per prompt in input order (or the proxies with
        ``return_requests=True``)."""
        reqs = [self.submit(p, max_new_tokens, eos_id,
                            priority=priority) for p in prompts]
        while self.has_work:
            self.step()
        if return_requests:
            return reqs
        return [list(r.generated) for r in reqs]

    # -- lifecycle ---------------------------------------------------------

    @property
    def draining(self) -> bool:
        return self._draining

    @property
    def closed(self) -> bool:
        return self._closed

    def drain_replica(self, which) -> int:
        """Rolling-restart drain of one replica (index or name):
        placement stops, queued work moves to the survivors, in-flight
        work finishes in place over normal stepping.  Returns requests
        moved."""
        with (self._ops_lock or _NO_LOCK):
            return self.router.drain_replica(self._resolve(which))

    def replica_drained(self, which) -> bool:
        """True once a draining replica has run all its work off —
        safe to swap (:meth:`revive`)."""
        rep = self._resolve(which)
        return rep.draining and not rep.server.has_work

    def revive(self, which, server=None) -> None:
        """Return a replica to the rotation, optionally swapping in a
        fresh server (the rolling-restart second half)."""
        with (self._ops_lock or _NO_LOCK):
            self.router.revive(self._resolve(which), server)

    def _resolve(self, which) -> Replica:
        if isinstance(which, Replica):
            return which
        if isinstance(which, str):
            for rep in self.replicas:
                if rep.name == which:
                    return rep
            raise KeyError(f"no replica named {which!r}")
        return self.replicas[int(which)]

    def drain(self) -> dict:
        """Fleet-wide graceful shutdown: every replica stops
        admitting, then the fleet steps until all in-flight work
        reaches terminal states.  Idempotent; returns the final
        :meth:`stats`."""
        # admissions stop atomically w.r.t. concurrent submit()/step()
        # holders of the ops lock (apexlint lock-discipline: the flag
        # write used to race the handler threads)
        with (self._ops_lock or _NO_LOCK):
            self._draining = True
            for rep in self.replicas:
                rep.server.begin_drain()
        # the convergence loop runs unlocked on purpose: step()
        # re-locks per iteration, and holding across it would starve
        # ops handlers; a stale has_work read only costs one extra step
        # apexlint: disable=lock-discipline — convergence loop; step() self-locks per iteration
        while self.has_work:
            self.step()
        return self.stats()

    def close(self) -> dict:
        """Drain, then close every replica, stop the thread pool and
        the ops plane, and refuse further submissions.  Exactly-once;
        repeated calls return the same final stats."""
        with (self._ops_lock or _NO_LOCK):
            if self._closed:
                return self._final_stats
        final = self.drain()
        with (self._ops_lock or _NO_LOCK):
            if self._closed:       # lost a concurrent close(): keep
                return self._final_stats        # the first result
            self._final_stats = final
            self._closed = True
            replicas = list(self.replicas)
            pool, ops = self._pool, self.ops
        for rep in replicas:
            srv = rep.server
            if not srv.closed and not srv.has_work:
                srv.close()
        # teardown after the flag flip, unlocked: joining the ops
        # thread while holding its own lock would deadlock any
        # handler blocked on that lock
        if pool is not None:
            pool.shutdown(wait=True)
        if ops is not None:
            ops.stop()
        # the transport join rides the same unlocked teardown: the
        # _closed flag already fenced new sends, and the socket
        # backend's server thread synchronizes on the TRANSPORT lock,
        # not the fleet ops lock — joining it under _ops_lock would
        # only stall late ops handlers for the join timeout
        # apexlint: disable=lock-discipline
        self.kv_transport.close()
        return final

    # -- observability -----------------------------------------------------

    def _journey_logs(self) -> list:
        """Every journey log in the fleet: the router's own (route /
        failover / hand-off hops) plus each replica's — retired
        replicas included, so a journey that finished on a since-
        removed replica still merges complete."""
        return [self.journeys] + [
            rep.server.journeys
            for rep in self.replicas + self.retired_replicas]

    def journey(self, rid: int) -> Optional[dict]:
        """One request's merged cross-replica journey (None if the
        rid never opened one).  Hops from every replica it touched
        — submit/route at the router, enqueue/admit/first-token/
        finish on the servers, evacuate/reenqueue and hand-off hops
        wherever they fired — causally ordered by the hop sequence
        the traveling context issued, never by wall clock."""
        with (self._ops_lock or _NO_LOCK):
            j = merge_journeys(self._journey_logs(),
                               rid=int(rid)).get(int(rid))
            return j.as_dict() if j is not None else None

    def fleet_metrics_text(self) -> str:
        """Fleet-wide Prometheus exposition: the router registry's
        series as-is plus every replica's private registry with a
        ``replica=<name>`` label — one HELP/TYPE per family across
        the whole fleet (``GET /metrics/fleet``).  Lock-free like
        ``/metrics``: registries serialize internally."""
        sources = [({}, self.registry)]
        sources += [({"replica": rep.name}, rep.server.registry)
                    for rep in self.replicas + self.retired_replicas]
        return fleet_prometheus_text(sources)

    def dump_postmortem(self, path: str, *, reason: str = "on_demand",
                        extra: Optional[dict] = None) -> dict:
        """The aggregate ops plane's postmortem hook: the router
        registry snapshot + trace + a manifest carrying the router
        block (per-replica flight rings live behind each replica's
        own ops plane), plus the merged journeys member when the
        correlation plane is armed."""
        merged = {"iter": self._iter,
                  "router": self.router.router_stats()}
        if extra:
            merged.update(extra)
        return write_postmortem(path, recorder=self.recorder,
                                registry=self.registry,
                                tracer=self.tracer, reason=reason,
                                extra=merged,
                                journeys=(
                                    dump_journeys(self._journey_logs())
                                    if self.journeys.enabled else None))

    def stats(self) -> dict:
        """Fleet aggregates + the pinned ``stats()["router"]`` block
        (``docs/serving.md``, "Multi-replica routing").  Aggregate
        prefix-cache counters sum the replicas' — the fleet-level
        hit rate is what the affinity policy exists to raise."""
        with (self._ops_lock or _NO_LOCK):
            return self._stats()

    def _elastic_stats(self) -> dict:
        """The pinned ``stats()["elastic"]`` block: the autoscaler's
        decision table when the control loop is on, the minimal
        shape otherwise — plus the rollout/version fields either
        way (rollout works on non-autoscaled fleets too)."""
        st = (self.autoscaler.stats() if self.autoscaler is not None
              else {"enabled": False})
        census: dict = {}
        for rep in self.replicas:
            v = rep.weights_version or "initial"
            census[v] = census.get(v, 0) + 1
        st["weights_versions"] = census
        st["last_rollout"] = self._last_rollout
        return st

    def _stats(self) -> dict:
        router = self.router.router_stats()
        router["steps"] = self._iter
        router["threaded"] = self.threaded
        hit = miss = finished = tokens = 0
        # retired replicas stay in the ledger: a scale-down must not
        # make finished work or generated tokens vanish from the
        # fleet's aggregates (the soak reconciles on these)
        for rep in self.replicas + self.retired_replicas:
            srv = rep.server
            hit += srv.prefix.count("prefix_hit_tokens")
            miss += srv.prefix.count("prefix_miss_tokens")
            finished += len(srv.scheduler.finished)
            tokens += srv.tokens.total
        return {
            "router": router,
            "requests_finished": finished,
            "requests_unplaced": router["unplaced"],
            "tokens_generated": tokens,
            "prefix_hit_tokens": hit,
            "prefix_miss_tokens": miss,
            "prefix_hit_rate": round(hit / (hit + miss), 3)
            if hit + miss else 0.0,
            "pressure": round(self.pressure_gauge.val, 3),
            "pressure_peak": round(self.pressure_gauge.peak, 3),
            "draining": self._draining,
            "streams": self._stream_stats(),
            "elastic": self._elastic_stats(),
            "journeys": journeys_census(self._journey_logs()),
            # cross-replica KV transport (docs/serving.md, "KV
            # transport"): envelope totals + per-peer counters and
            # breaker state for hand-off / warm transfers
            "transport": self.kv_transport.stats(),
        }
