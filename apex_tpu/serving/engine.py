"""Jit-compiled chunk prefill + single-token decode steps over the KV cache.

Compiled programs, all fixed-shape so the continuous-batching loop
never recompiles in steady state:

- **chunk prefill** (one request, one fixed-width chunk at a carried
  KV position): the one way a prompt, or a prefix-cached prompt's
  tail, gets into the pool — each layer writes the chunk's rows (a
  head's K and V, or a latent row: what the model's family keeps,
  ``models/family.py``) at its block-offset slots and the chunk
  attends the request's already-cached context through its block
  table plus itself causally.  One trace a chunk width, so a fixed
  chunk size means ONE trace however long prompts get.
- **decode** (the whole running batch, always ``max_batch_size``
  wide): the model runs on one token per slot at its own position;
  each layer writes the token's K/V into the pool and attends through
  the block table.  Returns next-token logits.  Compiled exactly once.
- **verify** (the whole batch, ``max_batch_size`` x a fixed token
  width): the speculative-decoding scoring step — every slot feeds its
  pending token plus its drafted guesses at carried positions; each
  layer writes all fed tokens' K/V and they attend their cached
  context plus themselves causally (the same body as chunk prefill,
  batched and returning EVERY row's logits).  Greedy acceptance happens
  on the host (``serving.api``); rejected suffix positions hold garbage
  K/V that sits beyond the accepted length — never at or before a
  valid row's position, and overwritten before the request ever
  advances past it.  One trace per verify width, so a fixed
  speculation depth compiles exactly once.
- **block copy** (fixed-width (src, dst) id batch): whole-block
  duplication inside the pool — the device half of the prefix cache's
  copy-on-write.  Compiled exactly once.
- **sampled variants** (``chunk_prefill_sampled`` /
  ``decode_sampled`` / ``verify_sampled``): the same programs with
  greedy argmax and the non-finite row guard fused in
  (:func:`ops.greedy_argmax` / :func:`ops.finite_rows`), returning
  token ids + per-row finite flags instead of logits.  The per-step
  device→host transfer shrinks from a ``(B, V)`` float block to a
  ``(B,)`` int32 vector, and — because the host never has to
  materialize logits to sample — the pipelined serve loop
  (``serving.api``, ``enable_pipeline``) can leave the returned arrays
  as futures and let JAX async dispatch run the device a full
  iteration ahead of host scheduling.  Bit-exact against the host
  path by construction: ``jnp.argmax`` and ``np.argmax`` share the
  lowest-index tie rule (pinned by ``tests/L0/test_pipeline.py``).

Empty slots ride along as no-ops by construction: position 0 masks
the whole context, the zeroed block table routes the KV write into
the reserved garbage block, and the caller ignores their logits.

How a program attends is fixed when the engine is built
(``DecodeEngine.attention_paths``, reported under
``stats()["programs"]["attention"]``), from what can be seen then:

- ``"table"`` — *attend through the table*: an unquantized pool on one
  TPU device whose geometry the kernel tiles (a group of the row —
  a head's ``K | V`` pair of ``2 * head_dim`` values, or a latent row —
  a multiple of 128 lanes, a latent row's value too, ``block_size`` of
  whole sublane tiles).
  Decode, verify and chunk prefill write a layer's rows, then
  ``ops.decode_attention.paged_attention`` takes the pool itself with
  the block tables and lengths as prefetched scalars and visits only
  the windows of pages up to each slot's last row: no
  ``(B, max_context)`` copy of keys and values, no bias row, no
  concatenation, no pad.  (The chunk
  program took the kernel by measurement: 13.0 ms a launch at GPT-2 XL
  against 20.0 ms gathering its one request's context; my chip run,
  PR 25.)
- ``"gathered"``: each layer's context is gathered block by block into
  the ops of the gathered form (``ops.cached_attention`` /
  ``ops.chunk_cached_attention``, the parity oracle of the kernel).
  An int8 pool (its kernel front works on gathered input), a mesh (a
  GSPMD-sharded jit cannot hold a Mosaic kernel) and the CPU backend
  take it for every program.  Results are the same either way.

The cache pytree is donated through every step, and the donation
holds: the pool's layout (``serving.kv_cache``) lets every write, block
copy and read compile to an update or a slice of the donated buffer, so
no program holds a second copy of the pool or any pool-sized temporary
(``memory_info()["decode_temp_bytes"]``;
``tests/L0/test_serving_programs_compiled.py`` reads the compiled
programs at GPT-2 XL's size).  (XLA on CPU ignores donation; the
warning is filtered.)
"""

from __future__ import annotations

import dataclasses
import functools
import warnings
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding, PartitionSpec as P

from apex_tpu.models.family import layer_states, layer_windows
from apex_tpu.observability import (NULL_PROGRAM_ACCOUNTING, NULL_TRACER,
                                    device_scope)
from apex_tpu.ops.decode_attention import paged_attention_fits
from apex_tpu.ops.pallas_utils import LANES, on_tpu, pallas_auto_gate
from apex_tpu.ops.sampling import finite_rows, greedy_argmax, sample_tokens
from apex_tpu.ops.vocab_parallel import (
    vocab_parallel_sample,
    vocab_parallel_sample_tokens,
)
from apex_tpu.serving.kv_cache import (
    STATE_LEAF,
    WINDOW_LEAF,
    BlockAllocator,
    CacheView,
    KVCacheConfig,
    copy_blocks,
    copy_blocks_across,
    init_kv_cache,
    pool_leaves,
    pool_specs,
    read_blocks,
    resolve_kv_quant,
    ring_rows,
    slot_index,
    state_rows,
    write_blocks,
)

# CPU backends can't honor donation; the fallback copy is exactly the
# pre-donation behavior, so the warning is noise off-TPU
warnings.filterwarnings(
    "ignore", message="Some donated buffers were not usable")


# padded width of one copy_blocks launch: COW duplicates arrive one or
# two at a time, so a single fixed shape keeps the program count at 1
_COPY_WIDTH = 8


class DecodeEngine:
    """The device half of the serving stack: owns the cache pool, the
    compiled chunk-prefill/decode programs, and nothing else — admission,
    batching composition, and termination live in
    ``serving.scheduler``/``serving.api``.

    Args:
      cfg: the model's configuration object, of any family that tells
        the engine what it needs (``models/family.py``): how to build
        the model (``cfg.build_model``), what one token keeps in one
        layer of the pool (``cfg.cache_row()``: every head's ``K | V``
        pair for ``models.GPTConfig``, one latent row shared by all
        heads for ``models.DeepseekV3Config``, 8 key-value heads'
        pairs read by 8 query heads each for
        ``models.ExaoneMoeConfig``), which tokens each layer keeps
        (``models.family.layer_windows``: a window layer's rows live in
        a ring its slot owns, a pool leaf of their own), which layers
        keep a fixed state instead (``models.family.layer_states``: a
        ring of a few rows a slot, ``models.Lfm2MoeConfig``'s short
        convolutions), and
        ``vocab_size``, ``num_hidden_layers``,
        ``max_position_embeddings``.  The family is told from the
        object; there is no switch.  What a row that several query
        heads share does not do yet (``kv_quant="int8"``, ``mesh``)
        raises here with its reason.
      params: the model's ``{"params": ...}["params"]`` pytree (pass
        amp-cast params to serve in half).
      max_batch_size: decode batch width (running-request slots).
      max_context: per-request token capacity; default
        ``cfg.max_position_embeddings``.
      num_blocks: physical blocks in the pool (incl. the reserved
        garbage block 0); default sizes the pool for
        ``max_batch_size`` full-context requests plus slack.
      block_size: tokens per block.
      cache_dtype: KV COMPUTE dtype; None = amp policy
        (:func:`serving.kv_cache.resolve_cache_dtype`).
      kv_quant: ``"int8"`` stores the pool quantized — int8 payload
        plus a per-slot per-head fp32 scale sidecar sharded with its
        heads — with quantization fused into every write program and
        dequantization fused into every read (``docs/serving.md``,
        "Quantized KV cache").  ``cache_dtype`` keeps naming the
        compute dtype the values widen to.  Default ``None`` (the
        historical full-width pool, byte-identical programs).
      tracer: optional :class:`apex_tpu.observability.SpanTracer`;
        when enabled, every first-compile of a chunk/decode/verify/
        copy program emits a ``compile`` instant event (recompiles in
        steady state are exactly what the trace is for catching).
      programs: optional
        :class:`apex_tpu.observability.ProgramAccounting` — every
        host-API launch is tallied per program key
        (``chunk_prefill[<width>]`` / ``decode`` /
        ``verify[<width>]`` / sampled twins /
        ``copy_blocks``): call count, host wall time, compile count,
        compile time.  Default: the zero-overhead disabled instance
        (``InferenceServer`` passes a registry-backed one).
      mesh: optional :class:`jax.sharding.Mesh` — tensor-parallel
        serving (``docs/serving.md``, "Tensor-parallel serving").
        Params place per ``tp_rules`` (Megatron column/row split), the
        KV pool shards its HEADS dim over ``tp_axis`` (each device
        holds ``num_heads/tp`` heads of EVERY block, so block tables,
        the allocator, and the whole scheduler stay replicated
        host-side state), and all compiled programs lower through
        GSPMD with sharded in/out placements — XLA inserts the
        attention all-reduce and the lm-head all-gather; the sampled
        twins take the fused :func:`ops.vocab_parallel_sample` path
        (per-shard argmax, one (B,)-shaped cross-shard reduction)
        instead of ever gathering logits.  Greedy token streams are
        bit-exact vs the unsharded engine
        (``tests/L0/test_serving_tp.py``).
      tp_rules: the ``(regex, PartitionSpec)`` param-sharding rules
        for ``mesh`` (default :func:`parallel.gpt_tp_rules` on
        ``tp_axis``).
      tp_axis: the mesh axis tensor parallelism shards over
        (default ``"model"``).
      verify_rows: the most rows a verify launch feeds a sequence
        (``InferenceServer`` passes ``spec_tokens + 1``); a state
        layer's ring is sized to hold them beside the rows a launch
        reads before its first.  Default 1: no verify launch wider than
        a decode step.
    """

    def __init__(self, cfg, params, *,
                 max_batch_size: int = 8,
                 max_context: Optional[int] = None,
                 num_blocks: Optional[int] = None,
                 block_size: int = 16,
                 cache_dtype=None,
                 kv_quant: Optional[str] = None,
                 tracer=None,
                 programs=None,
                 mesh=None,
                 tp_rules=None,
                 tp_axis: str = "model",
                 verify_rows: int = 1):
        self.cfg = cfg
        self.row = row = cfg.cache_row()
        self.tracer = tracer if tracer is not None else NULL_TRACER
        self.programs = (programs if programs is not None
                         else NULL_PROGRAM_ACCOUNTING)
        self.mesh = mesh
        self.tp_axis = tp_axis if mesh is not None else None
        self.tp = 1
        self.kv_quant = resolve_kv_quant(kv_quant)
        self.quantized = self.kv_quant is not None
        self._repl = None         # replicated placement for launch args
        self._pool_shard = None   # the pool's head-sharded placement
        self._scale_shard = None  # the scale sidecar's (heads last)
        if row.shared and (self.quantized or mesh is not None):
            # both split or scale the row by query heads, and a group
            # that several of them share is not one head's
            raise NotImplementedError(
                f"a {row.kind!r} pool keeps {row.groups} group(s) of "
                f"{row.used} values for {row.heads} query heads: "
                + ("kv_quant='int8' stores one scale a head for a K|V "
                   "pair of its own" if self.quantized else
                   "mesh=... shards the pool's row by whole heads, each "
                   "with a K|V pair of its own")
                + ", and this row's groups are shared.  Open work: "
                "ROADMAP.md Reach.")
        if mesh is not None:
            if tp_axis not in mesh.shape:
                raise ValueError(
                    f"tp_axis {tp_axis!r} is not an axis of the mesh "
                    f"(axes: {tuple(mesh.shape)})")
            self.tp = int(mesh.shape[tp_axis])
            if mesh.size > 1 and on_tpu():
                # the decode programs lower through GSPMD, which
                # cannot partition the Mosaic kernels in them
                # (cached_attention, FusedLayerNorm):
                # refuse here rather than fail in the first launch or
                # serve from the jnp references unannounced
                raise NotImplementedError(
                    f"InferenceServer/DecodeEngine(mesh=...) over "
                    f"{mesh.size} TPU devices: GSPMD cannot partition "
                    "the Pallas kernels of the decode path (\"Mosaic "
                    "kernels cannot be automatically partitioned\"), "
                    "and they are not yet wrapped in a shard_map over "
                    "the mesh.  Open work: CHANGES.md PR 21, ROADMAP.md "
                    "Speed item 10.  One process can instead serve one "
                    "unsharded replica on each chip.")
            if row.groups % self.tp:
                raise ValueError(
                    f"num_attention_heads={row.groups} "
                    f"must divide the {tp_axis!r} axis ({self.tp}) — "
                    "the KV pool shards its heads dim, so every "
                    "device must hold a whole number of heads")
            from apex_tpu.parallel.tensor_parallel import (
                gpt_tp_rules,
                shard_params,
            )
            if tp_rules is None:
                tp_rules = gpt_tp_rules(tp_axis)
            params = shard_params(params, mesh, tp_rules)
            self._tp_rules = tp_rules
            self._repl = NamedSharding(mesh, P())
            # the pool and its scale sidecar shard their heads, each
            # leaf as kv_cache lays it out
            specs = pool_specs(tp_axis)
            self._pool_shard = NamedSharding(mesh, specs["kv"])
            self._scale_shard = NamedSharding(mesh, specs["k_scale"])
        self.params = params
        self.max_batch_size = int(max_batch_size)
        self.max_context = int(max_context
                               or cfg.max_position_embeddings)
        if self.max_context > cfg.max_position_embeddings:
            raise ValueError(
                f"max_context={self.max_context} exceeds the model's "
                f"max_position_embeddings={cfg.max_position_embeddings}")
        self.block_size = int(block_size)
        self.blocks_per_seq = -(-self.max_context // self.block_size)
        if num_blocks is None:
            # every slot can hold a full-context request, +1 garbage
            num_blocks = self.max_batch_size * self.blocks_per_seq + 1
        # which tokens each layer keeps: the layers that keep every one
        # share the leaf the block tables address, a window layer's
        # rows live in its slot's ring (kv_cache, "kv_window"), and a
        # layer that keeps a state keeps it in a ring of its own
        # (kv_cache, "conv_state")
        windows, states = layer_windows(cfg), layer_states(cfg)
        fixed = [i for i, st in enumerate(states) if st is not None]
        kept = [i for i, w in enumerate(windows)
                if w is None and states[i] is None]
        slide = [i for i, w in enumerate(windows) if w is not None]
        self.window = windows[slide[0]] if slide else None
        if slide and (len(set(windows)) > 2 or not kept or fixed
                      or row.kind != "kv"):
            raise NotImplementedError(
                f"window layers of one width beside layers that keep "
                f"every token, over a 'kv' row, is what the pool lays "
                f"out; got windows {sorted(set(windows), key=str)} over "
                f"a {row.kind!r} row"
                + (" beside layers that keep a state" if fixed else ""))
        self.state = states[fixed[0]] if fixed else None
        if fixed and (len({states[i] for i in fixed}) > 1 or not kept):
            raise NotImplementedError(
                f"state layers of one shape beside layers that keep "
                f"every token is what the pool lays out; got "
                f"{sorted({states[i] for i in fixed})}")
        self.layers = tuple(
            (WINDOW_LEAF, slide.index(i), w) if w is not None
            else (STATE_LEAF, fixed.index(i), None) if i in fixed
            else ("kv", kept.index(i), None)
            for i, w in enumerate(windows)) if slide or fixed else None
        self.cache_cfg = KVCacheConfig(
            num_layers=len(kept),
            num_heads=row.groups,
            head_dim=row.group_width // 2,
            num_blocks=int(num_blocks),
            block_size=self.block_size,
            dtype=cache_dtype,
            quantize=self.kv_quant)
        self.allocator = BlockAllocator(self.cache_cfg)
        # the window layers' leaf: a ring a slot and garbage block 0
        self.ring_rows = (ring_rows(self.window, self.block_size)
                          if slide else 0)
        self.window_cfg = dataclasses.replace(
            self.cache_cfg, num_layers=len(slide),
            num_blocks=self.max_batch_size * self.ring_rows
            // self.block_size + 1) if slide else None
        # the state layers' leaf: a ring a slot that holds what a launch
        # reads before its first row and a verify launch's fed rows
        self.state_rows = (state_rows(self.state[0], int(verify_rows),
                                      self.block_size) if fixed else 0)
        self._state_shape = ((len(fixed), self.max_batch_size,
                              self.state_rows, self.state[1])
                             if fixed else None)
        # which way each serving program attends, decided once from
        # what can be seen here: an unquantized pool on one TPU device
        # whose geometry the kernel tiles is read in place through the
        # block table; an int8 pool, a mesh (GSPMD cannot hold a
        # Mosaic kernel) and the CPU gather each layer's context into
        # the ops of the gathered form.
        in_place = (not self.quantized and mesh is None
                    and pallas_auto_gate()
                    and paged_attention_fits(
                        self.cache_cfg.head_dim, self.block_size,
                        self.cache_cfg.storage_dtype())
                    # a shared row's value is sliced from it by lanes
                    and not (row.shared and row.value[1] % LANES))
        self.attention_paths = dict.fromkeys(
            ("decode", "verify", "chunk_prefill"),
            "table" if in_place else "gathered")
        self.model = cfg.build_model(kv_quant=self.quantized)
        # what the family's programs carry beside the pool
        self._counters = dict(getattr(cfg, "serving_counters",
                                      dict)())
        self.cache = self._fresh_cache()

        # under a mesh every program pins its output placements so
        # GSPMD keeps the (donated) pool head-sharded and replicates
        # exactly what the host consumes (logits / token ids / flags);
        # without one the jits are byte-identical to the single-chip
        # engine
        def _jit(fn, donate, outs):
            if self.mesh is None:
                return jax.jit(fn, donate_argnums=donate)
            return jax.jit(fn, donate_argnums=donate,
                           out_shardings=outs)

        cache_sh = None
        repl = self._repl
        if self.mesh is not None:
            cache_sh = {"kv": self._pool_shard}
            if self.quantized:
                cache_sh["k_scale"] = self._scale_shard
                cache_sh["v_scale"] = self._scale_shard
        self._decode_jit = _jit(self._decode_impl, (1,),
                                (cache_sh, repl))
        self._chunk_jit = _jit(self._chunk_impl, (1,),
                               (cache_sh, repl))
        self._verify_jit = _jit(self._verify_impl, (1,),
                                (cache_sh, repl))
        self._copy_jit = _jit(self._copy_impl, (0,), cache_sh)
        # the cross-pool hand-off programs (docs/serving.md,
        # "Disaggregated prefill/decode").  Donation policy mirrors
        # the sampled twins: the hand-off copy sits in the decode
        # pool's step path, and a donated call executes synchronously
        # on the CPU backend (BENCH_NOTES r8) — which would stall the
        # very decode launch disaggregation exists to protect.
        xfer_donate = (0,) if jax.default_backend() != "cpu" else ()
        self._xfer_jit = _jit(self._xfer_impl, xfer_donate, cache_sh)
        self._import_jit = _jit(self._import_impl, xfer_donate,
                                cache_sh)
        self._export_jit = jax.jit(self._export_impl)
        self._decode_exe = None   # _decode_compiled's, made when asked
        # the fused on-device-sampling twins (docs/serving.md,
        # "Pipelined serve loop"): same bodies + argmax/finite-guard,
        # so a greedy server transfers token ids, never logits.
        # Donation policy differs from the logits programs: on TPU the
        # pool is the HBM hog and must be updated in place, but on the
        # CPU backend a donated call executes SYNCHRONOUSLY — which
        # would serialize host and device again and defeat the
        # pipelined loop's dispatch-ahead.  CPU pools are test-scale,
        # so trading the (already-copied-anyway) in-place update for
        # an async launch is the right side of the bargain there.
        sampled_cache = (1,) if jax.default_backend() != "cpu" else ()
        self._chunk_sampled_jit = _jit(self._chunk_sampled_impl,
                                       sampled_cache,
                                       (cache_sh, repl, repl))
        self._decode_sampled_jit = _jit(self._decode_sampled_impl,
                                        sampled_cache,
                                        (cache_sh, repl, repl))
        self._verify_sampled_jit = _jit(self._verify_sampled_impl,
                                        sampled_cache,
                                        (cache_sh, repl, repl))
        # the STOCHASTIC twins (docs/serving.md, "Stochastic
        # sampling"): the same bodies + in-trace temperature/top-k/
        # top-p sampling with per-request counter-based keys
        # (ops.sample_tokens; the vocab-parallel no-gather path under
        # a mesh).  Distinct traces from the greedy twins on purpose:
        # an all-greedy step keeps launching the argmax-only program
        # — zero selection/noise cost for the default traffic — and the
        # stochastic program only compiles once the first stochastic
        # request is actually batched.  Greedy rows INSIDE a
        # stochastic launch still take the bit-exact argmax lane.
        self._chunk_stoch_jit = _jit(self._chunk_stoch_impl,
                                     sampled_cache,
                                     (cache_sh, repl, repl))
        self._decode_stoch_jit = _jit(self._decode_stoch_impl,
                                      sampled_cache,
                                      (cache_sh, repl, repl))
        self._verify_stoch_jit = _jit(self._verify_stoch_impl,
                                      sampled_cache,
                                      (cache_sh, repl, repl))

    # -- compiled bodies --------------------------------------------------

    def _fresh_cache(self):
        """The zeroed pool and, beside it, the family's counters."""
        cache = init_kv_cache(self.cache_cfg, sharding=self._pool_shard,
                              scale_sharding=self._scale_shard)
        if self.window_cfg is not None:
            cache[WINDOW_LEAF] = init_kv_cache(self.window_cfg)["kv"]
        if self._state_shape is not None:
            cache[STATE_LEAF] = jnp.zeros(self._state_shape, jnp.float32)
        for name, shape in self._counters.items():
            cache[name] = jnp.zeros(shape, jnp.int32)
        return cache

    def _view(self, program, cache, tables, start, slots, ring=None):
        """The model's view of the pool for one launch of ``program``
        (``kv_cache.CacheView``).  ``ring`` (B,): whose rings each
        row's window and state layers use, where the model has such
        layers: the decode slot's, which is the row's own number in a
        launch of the whole batch (None)."""
        if self.layers is None:
            ring = None
        with device_scope("kv_write"):
            start = start.astype(jnp.int32)
        return CacheView(
            cache, tables, start, slots,
            block_size=self.block_size, row=self.row,
            table=self.attention_paths[program] == "table",
            ring=ring, layers=self.layers)

    @property
    def max_fed_rows(self) -> Optional[int]:
        """The most rows one launch may feed a sequence (a prefill
        chunk, a verify launch): what a window layer's ring holds
        beside its window.  None where no layer is one."""
        return self.ring_rows - self.window if self.window else None

    @property
    def max_verify_rows(self) -> Optional[int]:
        """The most rows a verify launch may feed a sequence: the window
        layers' bound, or what a state layer's ring holds beside the rows
        a launch reads before its first (a chunk is never rolled back,
        so it may feed any number).  None where no layer is either."""
        if self.state is None:
            return self.max_fed_rows
        return self.state_rows - self.state[0]

    def _chunk_impl(self, params, cache, ids, start, length, table,
                    slot=None):
        """One prefill CHUNK at a carried KV position: ids (1, Cb)
        zero-padded chunk tokens; start (1,) absolute position of
        ``ids[0]`` (== tokens already materialized through ``table``);
        length (1,) valid tokens in the chunk; table (1,
        blocks_per_seq).  Each layer attends the request's cached
        context (slots < start) plus the chunk causally and writes the
        chunk's K/V at its block-offset slots (:meth:`_fed_rows`).
        Returns (cache, last-valid-token logits (1, V)) — the logits
        only matter on the final chunk.  ``slot`` (1,): the request's
        decode slot, whose ring its window layers write (a model with
        such layers alone is given it)."""
        logits, cache = self._fed_rows("chunk_prefill", params, cache,
                                       ids, start, length, table, slot)
        with device_scope("head"):
            last = jnp.take_along_axis(
                logits, (length[:, None, None] - 1).astype(jnp.int32),
                axis=1)[:, 0]                              # (1, V)
        return cache, last

    def _verify_impl(self, params, cache, ids, start, length, tables):
        """The speculative verify step: ids (B, K) — each slot's
        pending token followed by its drafted guesses, zero-padded;
        start (B,) absolute position of ``ids[:, 0]`` (== tokens
        already materialized through that slot's table); length (B,)
        valid tokens per slot (0 = idle slot); tables (B,
        blocks_per_seq).

        Each slot's K tokens attend its cached context (slots < start)
        plus themselves causally — the batched generalization of
        ``_chunk_impl`` — and their K/V are written at block-offset
        slots (invalid columns sink into the garbage block).  Returns
        (cache, logits (B, K, V)): EVERY row's
        logits, because greedy acceptance needs the model's argmax at
        each drafted position, not just the last."""
        logits, cache = self._fed_rows("verify", params, cache, ids,
                                       start, length, tables)
        return cache, logits                               # (B, K, V)

    def _fed_rows(self, program, params, cache, ids, start, length,
                  tables, ring=None):
        """The body verify and chunk prefill share: ids (B, K) fed at
        positions ``start + 0..K-1``, the first ``length`` of each row
        valid.  Every layer attends through the launch's view of the
        pool and writes its K/V there (invalid columns sink into the
        garbage block).  Returns (logits (B, K, V), cache)."""
        with device_scope("kv_write"):
            off = jnp.arange(ids.shape[1], dtype=jnp.int32)[None, :]
            pos = start[:, None].astype(jnp.int32) + off   # (B, K)
            slots = jnp.where(off < length[:, None],
                              slot_index(tables, pos, self.block_size), 0)
        with device_scope("embed"):
            # padded columns can run past the embedding table; clamp
            # (their logits are ignored and their K/V writes
            # garbage-sunk)
            pos_emb = jnp.minimum(pos,
                                  self.cfg.max_position_embeddings - 1)
        logits, view = self.model.apply(
            {"params": params}, ids, positions=pos_emb,
            deterministic=True,
            cache_views=self._view(program, cache, tables, start, slots,
                                   ring),
            return_kv=True)
        return logits, view.cache

    def _copy_impl(self, cache, src, dst):
        """(_COPY_WIDTH,) src/dst block ids, (0, 0)-padded — the COW
        block duplication (``kv_cache.copy_blocks``)."""
        return copy_blocks(cache, src, dst, self.block_size)

    def _xfer_impl(self, dst_cache, src_cache, src, dst):
        """(_COPY_WIDTH,) src/dst block ids, (0, 0)-padded — the
        CROSS-POOL hand-off copy (``kv_cache.copy_blocks_across``):
        ``src`` indexes another engine's pool of identical geometry,
        ``dst`` this one's."""
        return copy_blocks_across(dst_cache, src_cache, src, dst,
                                  self.block_size)

    def _import_impl(self, cache, block_ids, leaves):
        """Write a host-shipped block payload into the pool:
        ``block_ids`` (W,) physical blocks (padding ids are the garbage
        block), ``leaves`` a dict matching the cache's leaf names with
        the blocks' per-slot rows along axis 1."""
        return write_blocks(cache, block_ids, leaves, self.block_size)

    def _export_impl(self, cache, block_ids):
        """Every leaf's rows of ``block_ids`` (n,), slots along axis
        1."""
        return read_blocks(cache, block_ids, self.block_size)

    def _decode_impl(self, params, cache, tokens, positions, tables):
        """tokens (B,) current input token per slot; positions (B,)
        its position (== cached context length); tables (B,
        blocks_per_seq).  Returns (cache, logits (B, V))."""
        slots = slot_index(tables, positions, self.block_size)
        with device_scope("embed"):
            ids = tokens[:, None]
            pos = positions[:, None].astype(jnp.int32)
        logits, view = self.model.apply(
            {"params": params}, ids, positions=pos, deterministic=True,
            cache_views=self._view("decode", cache, tables, positions,
                                   slots[:, None]),
            return_kv=True)
        with device_scope("head"):
            logits = logits[:, 0]                     # (B, V)
        return view.cache, logits

    # -- fused on-device-sampling bodies ----------------------------------
    # Each composes its logits twin with greedy argmax + the finite-row
    # guard INSIDE the trace, so the (B, V) logits block never leaves
    # the device — only (B,) int32 ids and (B,) bool flags transfer,
    # and only when the caller eventually materializes them.

    def _sample(self, logits):
        """The fused argmax + finite guard: plain on one chip; under a
        mesh the :func:`ops.vocab_parallel_sample` path — per-shard
        argmax over the lm-head's OWN vocab slice and one (B,)-shaped
        cross-shard reduction (documented lowest-global-id tie rule),
        so the vocab-sharded logits are never all-gathered just to be
        argmaxed."""
        with device_scope("sample"):
            if self.mesh is not None:
                return vocab_parallel_sample(logits, self.mesh,
                                             self.tp_axis)
            return greedy_argmax(logits), finite_rows(logits)

    def _chunk_sampled_impl(self, params, cache, ids, start, length,
                            table, slot=None):
        cache, last = self._chunk_impl(params, cache, ids, start,
                                       length, table, slot)
        return (cache,) + self._sample(last)                   # (1,)

    def _decode_sampled_impl(self, params, cache, tokens, positions,
                             tables):
        cache, logits = self._decode_impl(params, cache, tokens,
                                          positions, tables)
        return (cache,) + self._sample(logits)                 # (B,)

    def _verify_sampled_impl(self, params, cache, ids, start, length,
                             tables):
        cache, logits = self._verify_impl(params, cache, ids, start,
                                          length, tables)
        return (cache,) + self._sample(logits)                 # (B, K)

    # -- stochastic twins (docs/serving.md, "Stochastic sampling") ---------
    # Same bodies, but the fused sampler is ops.sample_tokens with the
    # per-slot SamplingParams arrays and the COUNTER position of each
    # sampled token (the sequence index of the token being drawn —
    # what makes replay/preemption/speculation deterministic).  Rows
    # whose temperature is 0 (greedy requests, idle slots) take the
    # bit-exact argmax lane inside the same trace.

    def _sample_stoch(self, logits, counters, temp, tk, tp_, seed):
        """The fused stochastic sampler: plain
        :func:`ops.sample_tokens` on one chip; the no-gather
        :func:`ops.vocab_parallel_sample_tokens` under a mesh, so the
        vocab-sharded logits are never gathered for stochastic
        traffic either."""
        b = logits.shape[:-1]
        extra = logits.ndim - 1 - temp.ndim     # 1 on verify's (B, K)

        def bc(x):
            return jnp.broadcast_to(x.reshape(x.shape + (1,) * extra),
                                    b)

        with device_scope("sample"):
            args = (bc(temp), bc(tk), bc(tp_), bc(seed))
            if self.mesh is not None:
                return vocab_parallel_sample_tokens(
                    logits, *args, counters, self.mesh, self.tp_axis)
            return sample_tokens(logits, *args, counters)

    def _chunk_stoch_impl(self, params, cache, ids, start, length,
                          table, temp, tk, tp_, seed, slot=None):
        cache, last = self._chunk_impl(params, cache, ids, start,
                                       length, table, slot)
        # final chunk: start + length == the full context length
        with device_scope("sample"):
            counters = start + length
        ids_out, fin = self._sample_stoch(last, counters, temp, tk, tp_,
                                          seed)
        return cache, ids_out, fin                             # (1,)

    def _decode_stoch_impl(self, params, cache, tokens, positions,
                           tables, temp, tk, tp_, seed):
        cache, logits = self._decode_impl(params, cache, tokens,
                                          positions, tables)
        # the input token sits at `positions`; the drawn token is the
        # next sequence index
        with device_scope("sample"):
            counters = positions + 1
        ids_out, fin = self._sample_stoch(logits, counters, temp, tk,
                                          tp_, seed)
        return cache, ids_out, fin                             # (B,)

    def _verify_stoch_impl(self, params, cache, ids, start, length,
                           tables, temp, tk, tp_, seed):
        cache, logits = self._verify_impl(params, cache, ids, start,
                                          length, tables)
        # column j's logits predict the token at index start + j + 1;
        # sampling EVERY column with its own positional key is the
        # whole speculation story: the host accepts a draft iff it
        # equals the column's sample (the Gumbel-max coupling of
        # ops.sample_tokens — rejection sampling's exact accept/
        # residual probabilities, with a draft-independent stream)
        kw = ids.shape[1]
        with device_scope("sample"):
            counters = (start[:, None].astype(jnp.int32) + 1
                        + jnp.arange(kw, dtype=jnp.int32)[None, :])
        ids_out, fin = self._sample_stoch(logits, counters, temp, tk,
                                          tp_, seed)
        return cache, ids_out, fin                             # (B, K)

    # -- host API ---------------------------------------------------------

    def _mark(self, jit_fn):
        """Pre-call ``(t0, trace count)`` for the tracer's compile
        instants and the per-program accounting — ``(0.0, 0)`` when
        both are off, so the disabled path skips even the clock
        read."""
        acct = self.programs.enabled
        if not acct and not self.tracer.enabled:
            return 0.0, 0
        return ((self.programs.begin() if acct else 0.0),
                jit_fn._cache_size())

    def _account(self, jit_fn, mark, program: str, key=None,
                 **trace_args) -> None:
        """Post-call bookkeeping for one launch: a ``compile``
        instant if the call traced a new program, and a
        :class:`ProgramAccounting` tally under
        ``program[key]`` (wall time attributed to compile when the
        jit cache grew)."""
        acct, traced = self.programs.enabled, self.tracer.enabled
        if not (acct or traced):
            return
        t0, before = mark
        compiled = jit_fn._cache_size() > before
        if traced and compiled:
            self.tracer.instant("compile", program=program,
                                **trace_args)
        if acct:
            self.programs.note(
                program if key is None else f"{program}[{key}]",
                t0, compiled)

    def _qkey(self, key=None):
        """The :class:`ProgramAccounting` bucket/width key for one
        launch, grown a ``q8`` tag under quantization — quant-on
        traces account under distinct keys
        (``chunk_prefill[64q8]``, ``decode[q8]``) so compile-count and
        wall-time audits can bound the quantized program variants separately
        (``tools/ops_probe.py --programs``)."""
        if not self.quantized:
            return key
        return "q8" if key is None else f"{key}q8"

    def _put(self, *arrays):
        """ONE host→device handoff for a launch's whole argument
        struct (the per-step host-overhead fix): the prepared numpy
        arrays ship as a single ``jax.device_put`` pytree instead of
        one ``jnp.asarray`` dispatch per array.  Compile counts are
        untouched — shapes/dtypes are identical to the per-array
        path.  Under a mesh the struct commits REPLICATED: token ids,
        positions, and block tables are host-side scheduler state that
        every shard consumes whole (docs/serving.md, "Tensor-parallel
        serving")."""
        if self._repl is not None:
            return jax.device_put(arrays, self._repl)
        return jax.device_put(arrays)

    def _chunk_args(self, tokens, start, block_table, pad_to,
                    sampling=None, slot=0):
        """The chunk launch struct: (ids, start, length, table[,
        sampling params]) on device in one transfer, the chunk padded
        to the compiled width ``pad_to``; and, as keywords, the
        request's ``slot`` where the model has window layers."""
        n = len(tokens)
        if n > pad_to:
            raise ValueError(
                f"chunk of {n} tokens exceeds pad_to={pad_to}")
        self._check_fed(pad_to)
        ids = np.zeros((1, pad_to), np.int32)
        ids[0, :n] = tokens
        table = np.zeros((1, self.blocks_per_seq), np.int32)
        table[0, :len(block_table)] = block_table
        arrays = (ids, np.asarray([start], np.int32),
                  np.asarray([n], np.int32), table)
        if sampling is not None:
            arrays += tuple(sampling)
        if self.layers is None:
            return self._put(*arrays), {}
        *args, slot = self._put(*arrays, np.asarray([slot], np.int32))
        return tuple(args), {"slot": slot}

    def _check_fed(self, rows: int, verify: bool = False) -> None:
        """A window layer's ring holds its window and ``max_fed_rows``
        more: a launch that fed more would overwrite rows it attends.
        A state layer's ring holds what a launch reads before its first
        row and ``max_verify_rows`` more: a verify launch that fed more
        would overwrite rows the next launch reads if drafts were
        rejected."""
        if self.window is not None and rows > self.max_fed_rows:
            raise ValueError(
                f"a launch of {rows} rows a sequence: the window layers' "
                f"ring of {self.ring_rows} rows holds the window of "
                f"{self.window} and {self.max_fed_rows} fed rows")
        if verify and self.state is not None \
                and rows > self.max_verify_rows:
            raise ValueError(
                f"a verify launch of {rows} rows a sequence: the state "
                f"layers' ring of {self.state_rows} rows holds the "
                f"{self.state[0]} rows a launch reads before its first "
                f"and {self.max_verify_rows} fed rows")

    def swap_params(self, params) -> None:
        """In-place weight swap: rebind ``self.params`` to a new
        pytree WITHOUT touching any compiled program.  Params are an
        ARGUMENT to every jitted call here (never a captured
        constant), so as long as the new tree has the same structure,
        shapes, and dtypes, the next launch simply traces nothing and
        runs the existing executable with the new weights — this is
        what makes a zero-downtime rollout (``serving/elastic``)
        possible.  Under a mesh the new tree is resharded through the
        same ``shard_params`` rules as construction, so placement is
        identical too."""
        if self.mesh is not None:
            from apex_tpu.parallel.tensor_parallel import shard_params
            params = shard_params(params, self.mesh, self._tp_rules)
        self.params = params

    def chunk_prefill(self, tokens, start: int, block_table,
                      pad_to: int, slot: int = 0) -> jax.Array:
        """Run one prefill chunk — ``tokens`` at absolute positions
        ``start..start+len-1`` — writing its K/V through
        ``block_table``; K/V for positions < start must already be
        materialized (earlier chunks or shared prefix-cache blocks).
        Returns the chunk's last-token logits (V,).

        ``pad_to`` is the compiled chunk width: the serve loop passes
        its fixed ``prefill_chunk``, so exactly one chunk program ever
        compiles.  ``slot``: the request's decode slot (a model with
        window layers keeps their rows in the slot's ring)."""
        args, kw = self._chunk_args(tokens, start, block_table, pad_to,
                                    slot=slot)
        mark = self._mark(self._chunk_jit)
        self.cache, last = self._chunk_jit(self.params, self.cache,
                                           *args, **kw)
        self._account(self._chunk_jit, mark, "chunk_prefill",
                      key=self._qkey(pad_to), width=pad_to)
        return last[0]

    def chunk_prefill_sampled(self, tokens, start: int, block_table,
                              pad_to: int, sampling=None, slot: int = 0):
        """The fused-sampling twin of :meth:`chunk_prefill`: returns
        ``(token_ids (1,) int32, finite (1,) bool)`` device arrays for
        the chunk's last valid token (only meaningful on the final
        chunk, exactly like the logits twin) without materializing
        logits on the host.  ``sampling=None`` (the default) launches
        the greedy argmax program; a ``(temperature, top_k, top_p,
        seed)`` tuple of ``(1,)`` arrays launches the stochastic twin
        (``docs/serving.md``, "Stochastic sampling"; a 0-temperature
        row inside it is still bit-exact argmax)."""
        args, kw = self._chunk_args(tokens, start, block_table, pad_to,
                                    sampling=sampling, slot=slot)
        if sampling is None:
            jit_fn, name = (self._chunk_sampled_jit,
                            "chunk_prefill_sampled")
        else:
            jit_fn, name = self._chunk_stoch_jit, "chunk_prefill_stoch"
        mark = self._mark(jit_fn)
        self.cache, ids, fin = jit_fn(self.params, self.cache, *args,
                                      **kw)
        self._account(jit_fn, mark, name, key=self._qkey(pad_to),
                      width=pad_to)
        return ids, fin

    def copy_blocks(self, pairs) -> None:
        """Duplicate physical blocks ``[(src, dst), ...]`` inside the
        pool (copy-on-write).  Launches in fixed-width batches of
        ``_COPY_WIDTH`` padded with (0, 0) no-op pairs, so the copy
        program compiles once."""
        for i in range(0, len(pairs), _COPY_WIDTH):
            batch = pairs[i:i + _COPY_WIDTH]
            src = np.zeros((_COPY_WIDTH,), np.int32)
            dst = np.zeros((_COPY_WIDTH,), np.int32)
            for j, (s, d) in enumerate(batch):
                src[j], dst[j] = s, d
            args = self._put(src, dst)
            mark = self._mark(self._copy_jit)
            self.cache = self._copy_jit(self.cache, *args)
            self._account(self._copy_jit, mark, "copy_blocks",
                          key=self._qkey())

    # -- disaggregated hand-off (docs/serving.md) --------------------------

    def _no_rings(self, what: str) -> None:
        """The block movers carry the leaves the tables address; a
        window layer's rows and a state layer's state are in its slot's
        ring."""
        if self.layers is not None:
            kept = "window layers keep their rows" if self.window \
                else "state layers keep their state"
            raise NotImplementedError(
                f"{what} moves a request's blocks, and this model's "
                f"{kept} in no block but in the slot's ring, which no "
                f"block mover carries.  Open work: ROADMAP.md Reach.")

    def copy_blocks_from(self, src_engine, pairs) -> None:
        """Copy physical blocks ``[(src, dst), ...]`` from ANOTHER
        engine's pool into this one — the same-host disaggregated
        hand-off: a finished prefill's KV moves from the prefill pool
        into the decode pool without either pool's programs ever
        sharing an array.  Both pools must share geometry (layers,
        heads, block size, quantization mode — the server constructs
        them that way).  Fixed-width ``_COPY_WIDTH`` launches, exactly
        like :meth:`copy_blocks`, so one program serves every
        hand-off."""
        self._no_rings("copy_blocks_from")
        for i in range(0, len(pairs), _COPY_WIDTH):
            batch = pairs[i:i + _COPY_WIDTH]
            src = np.zeros((_COPY_WIDTH,), np.int32)
            dst = np.zeros((_COPY_WIDTH,), np.int32)
            for j, (s, d) in enumerate(batch):
                src[j], dst[j] = s, d
            args = self._put(src, dst)
            mark = self._mark(self._xfer_jit)
            self.cache = self._xfer_jit(self.cache, src_engine.cache,
                                        *args)
            self._account(self._xfer_jit, mark, "handoff_copy",
                          key=self._qkey())

    def export_blocks(self, block_ids, *,
                      per_block_crc: bool = False) -> dict:
        """Materialize ``block_ids``' contents as a host payload — the
        CROSS-REPLICA hand-off transfer unit (``docs/serving.md``,
        "Disaggregated prefill/decode"): every cache leaf's rows for
        those blocks (scale sidecars included under quantization) plus
        a per-leaf crc32, so a torn transfer is DETECTED at import
        instead of silently decoding garbage.

        ``per_block_crc=True`` additionally records a crc32 PER BLOCK
        per leaf: the offload tier demotes blocks in one batched
        export and re-verifies each block against these at promote
        time (``offload.split_payload``), so rot between demote and
        promote is still caught per block even though the device
        gather ran once.  The hot hand-off path leaves it off — the
        whole-leaf crc already covers a one-shot transfer."""
        import zlib

        self._no_rings("export_blocks")
        if len(block_ids):
            leaves = self._export_jit(
                self.cache, *self._put(np.asarray(block_ids, np.int32)))
        else:
            leaves = {name: arr[:, :0] for name, arr in
                      pool_leaves(self.cache).items()}
        leaves = {name: np.ascontiguousarray(np.asarray(arr))
                  for name, arr in leaves.items()}
        bs = self.block_size
        payload = {
            "num_blocks": len(block_ids),
            "block_size": bs,
            "leaves": leaves,
            "crc": {name: zlib.crc32(a.tobytes())
                    for name, a in leaves.items()},
        }
        if per_block_crc:
            payload["block_crc"] = {
                name: [zlib.crc32(np.ascontiguousarray(
                    a[:, i * bs:(i + 1) * bs]).tobytes())
                    for i in range(len(block_ids))]
                for name, a in leaves.items()}
        return payload

    def import_blocks(self, block_ids, payload) -> None:
        """Scatter an :meth:`export_blocks` payload into THIS pool's
        ``block_ids`` (same count, same geometry).  Verifies the
        per-leaf checksums first and raises :class:`ValueError` on any
        mismatch — a torn hand-off must be rejected whole (the caller
        falls back to a fresh prefill, which is bit-identical), never
        half-imported."""
        import zlib

        self._no_rings("import_blocks")
        if payload.get("block_size") != self.block_size \
                or payload.get("num_blocks") != len(block_ids):
            raise ValueError(
                f"hand-off payload geometry mismatch: payload holds "
                f"{payload.get('num_blocks')} blocks of "
                f"{payload.get('block_size')} slots, importing "
                f"{len(block_ids)} blocks of {self.block_size}")
        leaves = payload["leaves"]
        if set(leaves) != set(pool_leaves(self.cache)):
            raise ValueError(
                f"hand-off payload leaves {sorted(leaves)} != pool "
                f"leaves {sorted(pool_leaves(self.cache))} (quantization modes "
                f"must match across replicas)")
        for name, arr in leaves.items():
            got = zlib.crc32(np.ascontiguousarray(arr).tobytes())
            want = payload["crc"].get(name)
            if got != want:
                # name the culprit: which leaf, which destination
                # blocks, and both crcs — a torn payload in a
                # postmortem must not read as "rejected whole, no
                # idea where" (the offload promote path and the
                # cross-replica hand-off both route through here)
                raise ValueError(
                    f"torn hand-off payload: leaf {name!r} for "
                    f"block(s) {list(map(int, block_ids))} has "
                    f"checksum {got} (actual) != {want} (expected); "
                    f"payload rejected whole")
        if not len(block_ids):
            # an empty (but geometry-consistent) transfer is a no-op:
            # launching the scatter anyway would pad the id list with
            # zeros and overwrite block 0's slots with zero bytes
            return
        w = self.blocks_per_seq
        ids = np.zeros((w,), np.int32)          # padding: garbage block
        ids[:len(block_ids)] = block_ids
        padded = {}
        for name, arr in leaves.items():
            full = np.zeros((arr.shape[0], w * self.block_size)
                            + arr.shape[2:], arr.dtype)
            full[:, :arr.shape[1]] = arr
            padded[name] = full
        args = self._put(ids, padded)
        mark = self._mark(self._import_jit)
        self.cache = self._import_jit(self.cache, *args)
        self._account(self._import_jit, mark, "import_blocks",
                      key=self._qkey())

    def _decode_args(self, tokens, positions, tables, sampling=None):
        extra = tuple(sampling) if sampling is not None else ()
        return self._put(np.asarray(tokens, np.int32),
                         np.asarray(positions, np.int32),
                         np.asarray(tables, np.int32), *extra)

    def decode(self, tokens, positions, tables) -> jax.Array:
        """One iteration-level decode step over all slots.  Arrays are
        (B,), (B,), (B, blocks_per_seq) with inactive slots zeroed.
        Returns next-token logits (B, V)."""
        args = self._decode_args(tokens, positions, tables)
        mark = self._mark(self._decode_jit)
        self.cache, logits = self._decode_jit(self.params, self.cache,
                                              *args)
        self._account(self._decode_jit, mark, "decode",
                      key=self._qkey())
        return logits

    def decode_sampled(self, tokens, positions, tables, sampling=None):
        """The fused-sampling twin of :meth:`decode`: returns
        ``(token_ids (B,) int32, finite (B,) bool)`` DEVICE arrays.
        Nothing is materialized — the pipelined serve loop stashes the
        handles and consumes them next iteration, so the device runs
        this step while the host plans the next one.

        ``sampling=None`` launches the greedy argmax program; a
        ``(temperature, top_k, top_p, seed)`` tuple of per-slot
        ``(B,)`` arrays launches the stochastic twin — greedy/idle
        slots (temperature 0) stay bit-exact argmax inside it
        (``docs/serving.md``, "Stochastic sampling")."""
        args = self._decode_args(tokens, positions, tables,
                                 sampling=sampling)
        if sampling is None:
            jit_fn, name = self._decode_sampled_jit, "decode_sampled"
        else:
            jit_fn, name = self._decode_stoch_jit, "decode_stoch"
        mark = self._mark(jit_fn)
        self.cache, ids, fin = jit_fn(self.params, self.cache, *args)
        self._account(jit_fn, mark, name, key=self._qkey())
        return ids, fin

    def _verify_args(self, tokens, lengths, positions, tables,
                     sampling=None):
        extra = tuple(sampling) if sampling is not None else ()
        return self._put(np.asarray(tokens, np.int32),
                         np.asarray(positions, np.int32),
                         np.asarray(lengths, np.int32),
                         np.asarray(tables, np.int32), *extra)

    def verify(self, tokens, lengths, positions, tables) -> jax.Array:
        """One speculative verify step over all slots: tokens (B, K)
        — pending token + drafts per slot, zero-padded; lengths (B,)
        valid tokens per slot (0 = idle); positions (B,) each slot's
        cached context length; tables (B, blocks_per_seq).  Writes all
        valid tokens' K/V and returns per-column logits (B, K, V); the
        caller (``serving.api``) runs greedy acceptance and rolls back
        rejected suffix blocks.  One trace per distinct K — a server
        with a fixed speculation depth compiles this exactly once."""
        args = self._verify_args(tokens, lengths, positions, tables)
        kw = int(np.asarray(tokens).shape[1])
        self._check_fed(kw, verify=True)
        mark = self._mark(self._verify_jit)
        self.cache, logits = self._verify_jit(self.params, self.cache,
                                              *args)
        self._account(self._verify_jit, mark, "verify",
                      key=self._qkey(kw), width=kw)
        return logits

    def verify_sampled(self, tokens, lengths, positions, tables,
                       sampling=None):
        """The fused-sampling twin of :meth:`verify`: returns
        ``(token_ids (B, K) int32, finite (B, K) bool)`` device
        arrays — every row's sampled token and finite flag, the exact
        inputs acceptance needs — without materializing the
        ``(B, K, V)`` logits block.  Same one-trace-per-width compile
        discipline as :meth:`verify`.

        ``sampling=None``: every row is argmax (greedy acceptance
        compares drafts to argmax).  With per-slot params, each column
        is sampled with its own positional counter key — acceptance
        then compares drafts to the column's SAMPLE, which realizes
        rejection sampling's accept/residual probabilities exactly
        while keeping the emitted stream draft-independent
        (``ops.sample_tokens``, the Gumbel-max coupling)."""
        args = self._verify_args(tokens, lengths, positions, tables,
                                 sampling=sampling)
        kw = int(np.asarray(tokens).shape[1])
        self._check_fed(kw, verify=True)
        if sampling is None:
            jit_fn, name = self._verify_sampled_jit, "verify_sampled"
        else:
            jit_fn, name = self._verify_stoch_jit, "verify_stoch"
        mark = self._mark(jit_fn)
        self.cache, ids, fin = jit_fn(self.params, self.cache, *args)
        self._account(jit_fn, mark, name, key=self._qkey(kw),
                      width=kw)
        return ids, fin

    # -- introspection ----------------------------------------------------

    def compile_counts(self):
        """(chunk-prefill traces, decode traces) — the recompile audit
        the scheduler tests pin: one chunk trace a width (a server
        feeds one, its ``prefill_chunk``), decode == 1 regardless of
        traffic.  Logits, sampled, and stochastic twins count
        together: greedy-only traffic runs exactly one path per
        program, and the first stochastic request adds at most one
        extra trace per program family — still O(1) per shape key,
        never per request."""
        return (self._chunk_jit._cache_size()
                + self._chunk_sampled_jit._cache_size()
                + self._chunk_stoch_jit._cache_size(),
                self._decode_jit._cache_size()
                + self._decode_sampled_jit._cache_size()
                + self._decode_stoch_jit._cache_size())

    def _decode_compiled(self):
        """The greedy decode program at this engine's shapes, lowered
        and compiled once (a persistent-cache hit once the program has
        run), never executed."""
        if self._decode_exe is None:
            b = self.max_batch_size
            args = self._decode_args(
                np.zeros((b,), np.int32), np.zeros((b,), np.int32),
                np.zeros((b, self.blocks_per_seq), np.int32))
            self._decode_exe = self._decode_sampled_jit.lower(
                self.params, self.cache, *args).compile()
        return self._decode_exe

    def decode_hlo(self) -> str:
        """Compiled HLO text of the greedy decode program.
        ``chip_smoke.py`` looks for the ``_decode_kernel`` Mosaic call
        in it."""
        return self._decode_compiled().as_text()

    def verify_compiles(self) -> int:
        """Verify-program traces (logits + sampled + stochastic
        twins) — the speculation half of the compile audit: a
        greedy-only server with a fixed speculation depth must show
        exactly 1 (0 with speculation off/idle) no matter how drafts
        and batch composition vary; stochastic traffic adds at most
        one more trace per width."""
        return (self._verify_jit._cache_size()
                + self._verify_sampled_jit._cache_size()
                + self._verify_stoch_jit._cache_size())

    def collective_programs(self) -> int:
        """Compiled traces currently lowered THROUGH the mesh (all
        program families, logits + sampled + stochastic twins + block
        copy) — the ``stats()["sharding"]`` audit that sharded serving
        compiled one program per logical (program, shape) key, not per
        shard.  0 on an unsharded engine: nothing it compiles carries
        a collective."""
        if self.mesh is None:
            return 0
        return sum(j._cache_size() for j in (
            self._chunk_jit, self._decode_jit, self._verify_jit,
            self._copy_jit, self._chunk_sampled_jit,
            self._decode_sampled_jit, self._verify_sampled_jit,
            self._chunk_stoch_jit, self._decode_stoch_jit,
            self._verify_stoch_jit))

    def memory_info(self) -> dict:
        """Static pool geometry for ``stats()["memory"]`` and
        postmortem manifests: usable blocks, tokens per block, the
        pool's LOGICAL footprint (whole rows, all shards), what a row is
        (``cache_kind``, ``row_bytes_per_token_layer``), and —
        what per-chip HBM budgeting must use — the ACTUAL per-device
        bytes, read off the live arrays' shard shape and dtype (under
        tensor parallelism each device holds ``num_heads/tp`` heads of
        the pool, so the logical size overstates per-chip HBM by
        tp×).  Under quantization every count includes the scale
        sidecar — summed over ALL live cache leaves' shard shapes, so
        ``pool_bytes_per_device`` is what the int8 pool plus its fp32
        scales actually pin on each chip, and ``bytes_per_block`` is
        the true per-block HBM price headroom math divides by.

        ``decode_temp_bytes`` is what the compiler reserves for the
        decode program's temporaries beside the pool (0 where the pool
        is updated in place and nothing of its size is copied): read
        from the compiled program once, after a decode launch has
        built it; ``None`` before."""
        cfg = self.cache_cfg
        temp = None
        if self._decode_exe is not None or self.compile_counts()[1]:
            analysis = self._decode_compiled().memory_analysis()
            temp = (int(analysis.temp_size_in_bytes)
                    if analysis is not None else None)
        leaves = dict(pool_leaves(self.cache))
        for name in (WINDOW_LEAF, STATE_LEAF):
            if name in self.cache:
                leaves[name] = self.cache[name]
        per_device = sum(
            int(np.prod(arr.sharding.shard_shape(arr.shape)))
            * jnp.dtype(arr.dtype).itemsize
            for arr in leaves.values())
        by_kind = {"full": {"layers": cfg.num_layers,
                            "blocks_usable": cfg.num_blocks - 1,
                            "bytes": cfg.bytes()}}
        if self.window_cfg is not None:
            by_kind["window"] = {
                "layers": self.window_cfg.num_layers,
                "window": self.window,
                "rows_a_slot": self.ring_rows,
                "rows_usable": self.max_batch_size * self.ring_rows,
                "bytes": self.window_cfg.bytes()}
        state_bytes = 0
        if self._state_shape is not None:
            state_bytes = 4 * int(np.prod(self._state_shape))
            by_kind["state"] = {
                "layers": self._state_shape[0],
                "rows_kept": self.state[0],
                "rows_a_slot": self.state_rows,
                "width": self.state[1],
                "dtype": "float32",
                "bytes": state_bytes}
        return {
            # what each kind of layer keeps (models/family.py (d), (e)):
            # the table's blocks for the layers that keep every token,
            # a ring a slot for the window layers and for the state
            "by_kind": by_kind,
            "blocks_usable": cfg.num_blocks - 1,
            "block_size": cfg.block_size,
            "pool_tokens": cfg.usable_tokens,
            "pool_bytes": cfg.bytes() + state_bytes + (
                self.window_cfg.bytes() if self.window_cfg else 0),
            "pool_bytes_per_device": per_device,
            "bytes_per_block": cfg.bytes_per_block,
            "cache_kind": self.row.kind,
            "row_bytes_per_token_layer": cfg.row_bytes,
            "decode_temp_bytes": temp,
            "cache_dtype": str(cfg.storage_dtype()),
            "quantize": cfg.quantize,
            "compute_dtype": str(cfg.resolved_dtype()),
        }

    def sharding_info(self) -> dict:
        """The pinned ``stats()["sharding"]`` block: tensor-parallel
        degree and axis, mesh geometry, per-shard KV bytes, and the
        mesh-lowered program count (``docs/serving.md``,
        "Tensor-parallel serving")."""
        return {
            "enabled": self.mesh is not None,
            "tp": self.tp,
            "axis": self.tp_axis,
            "devices": (int(self.mesh.size)
                        if self.mesh is not None else 1),
            "mesh": ({name: int(n)
                      for name, n in self.mesh.shape.items()}
                     if self.mesh is not None else None),
            "kv_pool_bytes_per_device":
                self.memory_info()["pool_bytes_per_device"],
            "collective_programs": self.collective_programs(),
        }

    def reset_cache(self):
        """Zero the pool and refill the allocator in place (between
        workloads; schedulers holding the allocator stay wired)."""
        self.cache = self._fresh_cache()
        self.allocator.reset()
