"""apex_tpu.serving — batched inference: KV-cache + continuous batching.

The training stack (amp, optimizers, parallel, models) answers "how
fast can we learn"; this package answers "how much traffic can we
serve".  Three layers, bottom-up:

- :mod:`serving.kv_cache` — a preallocated, block-table-indexed KV
  pool (vLLM's PagedAttention memory model, fixed-shape for
  jit-stability; dtype from the amp half policy) with a host-side
  free-list allocator;
- :mod:`serving.engine` — the jitted device steps: fixed-width chunk
  prefill, a single-token batched decode and the speculative verify
  step, each attending the pool through the block table;
- :mod:`serving.prefix_cache` — a block-level prefix index
  (RadixAttention-style, keyed on full-block token chunks chained by
  physical parent id) over the allocator's refcounts: shared-prefix
  traffic maps its longest cached prefix onto shared blocks and only
  prefills the tail, idle cached blocks evict LRU under pool
  pressure, and whole-context hits duplicate their last block
  copy-on-write;
- :mod:`serving.scheduler` / :mod:`serving.api` — Orca-style
  iteration-level continuous batching (admit-on-slot-free, per-request
  EOS/max-token termination, preempt-youngest on memory pressure) with
  Sarathi-style CHUNKED PREFILL (one fixed-size chunk per prefilling
  request per iteration, interleaved with decode, so long prompts
  stall running requests by at most one chunk) and the synchronous
  :class:`InferenceServer` front door, with failure isolation: one
  pathological request finishes alone (``finish_reason`` ``capacity``
  / ``timeout`` / ``rejected`` / ``nonfinite``) instead of raising
  into the batch (``docs/resilience.md``);
- :mod:`serving.speculation` — speculative decoding with BIT-EXACT
  greedy acceptance (on by default, ``enable_speculation=False`` opts
  out): zero-weight n-gram/prompt-lookup drafts from each request's
  own history (a small-model drafter plugs in via
  :class:`~serving.speculation.DraftSource`) are scored K-at-a-time by
  the engine's fixed-width verify program
  (``ops.chunk_cached_attention`` over the live block-table cache);
  the accepted tokens are exactly the drafts matching the model's own
  argmax plus the model's next token, so output is bit-identical to
  one-token decode while repetitive traffic decodes several tokens
  per engine step;
- on-device stochastic sampling (``docs/serving.md``, "Stochastic
  sampling"): per-request :class:`~apex_tpu.ops.sampling.SamplingParams`
  (temperature / top-k / top-p / seed; default greedy, bit-identical
  to the historical argmax path) sample INSIDE the fused programs
  with counter-based PRNG keys — streams are pure functions of
  (prompt, params, seed), so same-seed replay, preemption resume,
  and the chaos oracle stay byte-exact — and speculation generalizes
  to stochastic drafts via rejection sampling (Gumbel-max coupling:
  accept a draft iff it equals the column's own sample), so sampled
  traffic keeps BOTH fast paths instead of falling back to the
  synchronous logits path;
- tensor-parallel sharded serving (``docs/serving.md``,
  "Tensor-parallel serving"): pass ``mesh=`` (+ optional
  ``tp_rules=``) and the engine lowers every compiled program through
  GSPMD over a device mesh — params split Megatron-style
  (``parallel.gpt_tp_rules``), the KV pool shards its heads dim while
  block tables stay replicated host state, and the fused sampling
  twins take the vocab-parallel argmax path
  (``ops.vocab_parallel_sample``) so logits never gather; greedy
  output is bit-identical to the unsharded engine
  (``tests/L0/test_serving_tp.py``);
- quantized int8 KV cache (``docs/serving.md``, "Quantized KV
  cache"): ``kv_quant="int8"`` (env twin ``APEX_TPU_KV_QUANT``)
  stores the pool int8 with a per-slot per-head fp32 absmax scale
  sidecar — quantization fused into every write program,
  dequantization fused into every read (in-kernel on the Pallas
  decode path), ~1.9x concurrent live blocks per HBM byte net of the
  sidecar at head_dim 64; quant-on output is held to a decode-parity
  tolerance budget vs the full-width pool and is BIT-STABLE across
  COW / preemption / eviction / chunking / speculation / pipeline /
  tensor parallelism (``tests/L0/test_kv_quant.py``);
- :mod:`serving.overload` + the lifecycle layer — priority-aware load
  shedding (``finish_reason="shed"``) under queue/pool pressure, a
  circuit breaker in front of ``submit``
  (``finish_reason="breaker_open"``), and graceful ``drain()`` /
  ``close()`` with bit-identical in-flight completions
  (``docs/resilience.md``, "Overload policy & lifecycle");
- :mod:`serving.router` — the multi-replica front door
  (``docs/serving.md``, "Multi-replica routing"):
  :class:`~serving.router.RouterFleet` fronts N in-process replicas
  with one ``submit()/step()/drain()/stats()`` surface —
  least-pressure placement on the scheduler's ``pressure()`` signal,
  prefix AFFINITY via a router-side radix index (shared-prefix
  sessions land on the replica already holding their cached blocks,
  spilling under pressure), per-replica circuit breakers with
  exactly-once failover (queued work re-enqueues onto survivors
  bit-identically), rolling-restart ``drain_replica()``/``revive()``,
  and Router x TP composition (each replica on its own disjoint
  device mesh);
- disaggregated prefill/decode (``docs/serving.md``, "Disaggregated
  prefill/decode"): ``enable_disagg=True`` splits the server into
  phase-separated execution pools — a dedicated prefill pool (its own
  engine, KV pool, scheduler, and the prefix cache's home) runs every
  chunked prefill and hands finished KV blocks to a PURE-decode pool
  through the fixed-shape cross-pool block copy, so a 10x long-prompt
  burst queues against prefill capacity instead of inflating the
  decode inter-token tail; output is bit-exact vs the monolithic
  engine, and ``RouterFleet(disagg_prefill=k)`` extends the hand-off
  cross-replica (checksummed block payloads via
  ``DecodeEngine.export_blocks`` / ``InferenceServer.ingest_handoff``,
  torn transfers detected whole, failover back to monolithic
  placement);
- hierarchical KV offload (``docs/serving.md``, "Hierarchical KV
  offload"): ``enable_kv_offload=True`` (env twin
  ``APEX_TPU_KV_OFFLOAD``) backs the prefix cache with a bounded
  host-RAM tier and an optional checksummed disk spill tier
  (:class:`~serving.offload.OffloadStore`) — cold evictable blocks
  DEMOTE (``DecodeEngine.export_blocks``) instead of dying, and
  admission-time radix hits PROMOTE them back through the
  checksummed ``import_blocks`` path into fresh device blocks, so a
  cache hit spans device -> host -> disk at fixed HBM; every
  integrity/capacity failure on the offload path falls back to cold
  prefill bit-identically;
- :mod:`serving.transport` — the KV transport layer
  (``docs/serving.md``, "KV transport"): every cross-pool block
  movement above (disagg hand-off, elastic prefix warm, offload
  promote) rides a :class:`~serving.transport.KVTransport` backend —
  :class:`~serving.transport.InProcessTransport` (the direct copy,
  default, behavior-identical) or
  :class:`~serving.transport.SocketTransport` (crc-framed payloads
  over loopback TCP) — under one
  :class:`~serving.transport.TransportPolicy` robustness envelope:
  per-transfer deadline, bounded retry with decorrelated jitter,
  per-peer circuit breaker fast-failing into each consumer's existing
  degradation path, and exactly-once ingest via monotonic transfer
  ids + a bounded receiver dedup ledger.

Quick start::

    from apex_tpu.serving import InferenceServer
    server = InferenceServer(gpt_cfg, params, max_batch_size=8)
    completions = server.generate(prompts, max_new_tokens=64,
                                  eos_id=eos)

See ``docs/serving.md`` for cache-sizing math; ``benchmarks/run.py``
measures the server on the chip (``PERF.md``).
"""

from apex_tpu.ops.sampling import SamplingParams
from apex_tpu.serving.api import InferenceServer, greedy_sample
from apex_tpu.serving.engine import DecodeEngine
from apex_tpu.serving.kv_cache import (
    BlockAllocator,
    KVCacheConfig,
    dequantize_kv,
    init_kv_cache,
    quantize_kv,
    resolve_cache_dtype,
    resolve_kv_quant,
)
from apex_tpu.serving.offload import OffloadStore, resolve_kv_offload
from apex_tpu.serving.overload import OverloadPolicy
from apex_tpu.serving.prefix_cache import PrefixCache
from apex_tpu.serving.router import (
    ReplicaRouter,
    RouterFleet,
    RouterPolicy,
    RouterRequest,
)
from apex_tpu.serving.scheduler import QueueFullError, Request, Scheduler
from apex_tpu.serving.speculation import DraftSource, NgramDraft
from apex_tpu.serving.transport import (
    InProcessTransport,
    KVTransport,
    SocketTransport,
    TransportError,
    TransportPolicy,
)

__all__ = [
    "BlockAllocator",
    "DecodeEngine",
    "DraftSource",
    "InProcessTransport",
    "InferenceServer",
    "KVCacheConfig",
    "KVTransport",
    "NgramDraft",
    "OffloadStore",
    "OverloadPolicy",
    "PrefixCache",
    "QueueFullError",
    "ReplicaRouter",
    "Request",
    "RouterFleet",
    "RouterPolicy",
    "RouterRequest",
    "SamplingParams",
    "Scheduler",
    "SocketTransport",
    "TransportError",
    "TransportPolicy",
    "dequantize_kv",
    "greedy_sample",
    "init_kv_cache",
    "quantize_kv",
    "resolve_cache_dtype",
    "resolve_kv_offload",
    "resolve_kv_quant",
]
