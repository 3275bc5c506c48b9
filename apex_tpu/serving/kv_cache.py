"""Block-table-indexed KV cache — the serving memory manager.

vLLM's PagedAttention insight, re-derived for jit-stability on TPU:
the cache is ONE preallocated fixed-shape pool of ``num_blocks``
physical blocks of ``block_size`` token slots each, and every request
owns an ordered *block table* mapping its logical token positions to
physical blocks.  Fixed shapes mean the jitted prefill/decode steps
never recompile as requests come and go; block granularity means a
request's memory grows in ``block_size`` quanta with zero copying, and
a finished request's blocks return to the free list immediately (no
compaction, no fragmentation beyond the last partial block).

The pool's layout, and why.  One leaf holds keys and values:

    kv: (num_layers, num_blocks * block_size, num_heads * 2 * head_dim)

one row a token slot, and in it every head's ``K_h`` beside its
``V_h`` (``pack_rows``).  This module is the one place that spells the
layout out; everything else goes through its functions.  It follows
from how XLA:TPU lays arrays out in HBM.  The minor dimension fills the
128 lanes of a tile.  A leaf whose minor dimension is ``head_dim`` 64
(the former ``(L, slots, H, D)``) is narrower than the lanes, so the
compiler puts the SLOT dimension there instead
(``bf16[48,8208,25,64]{1,3,2,0:T(8,128)(2,1)}`` at GPT-2 XL's size,
compiled for a described v5e), and every gather or scatter along slots
first copies the whole leaf to a slot-major layout and back: a 1.26 GB
leaf moved five times in every launch (ledger, PR 24).  A minor
dimension that is a multiple of 128 gets the plain row-major layout,
and ``2 * head_dim`` = 128 is one head's ``K | V`` pair exactly.  Then

- a token's row is contiguous and a block is ``block_size`` rows:
  writing rows at ``[layer, slots]`` (``write_layer``) and copying
  whole blocks (``copy_blocks``, ``read_blocks``, ``write_blocks``)
  update the donated buffer in place, with no temporary (the compiled
  decode program at 8 slots of 1,024: 0.23 GiB of temporaries, where
  it had 9.5; ``tests/L0/test_serving_programs_compiled.py``);
- a Pallas kernel can index the leaf by block through the table with
  no relayout: one page of one layer is ``block_size x (H * 2D)``, a
  whole number of (16, 128) tiles, and a head's ``K | V`` one lane
  tile of it (``ops.decode_attention.paged_attention``);
- the lane dimension is heads-major, so tensor parallelism splits it
  into whole heads (``pool_specs``).

What a row holds is the model family's to say
(``models.family.CacheRow``, from ``cfg.cache_row()``).  The leaf is
``(num_layers, num_slots, row width)`` for both kinds the pool can hold:

- ``"kv"``: every head's ``K_h | V_h`` pair, as above;
- ``"latent"``: ONE compressed row ``c | k_pe`` a token and layer
  (latent attention: 512 + 64 values where 32 heads of 192 + 128 would
  be 10,240), padded with zeros to whole lane tiles (640), which all
  query heads read and whose first ``rank`` values are also the value.
  The model hands :meth:`CacheView.attend` its absorbed queries.

Writes, block copies, hand-off payloads, the allocator and the prefix
cache's block hashing see rows of some width and nothing else.

Which tokens a LAYER keeps is the family's to say too
(``models.family.layer_windows``), and the pool has a leaf for each
kind of layer:

- ``"kv"``: the layers that attend every token before them.  A
  sequence's rows live in the blocks of its table for as long as it
  does, as above; this is the only leaf of a model without window
  layers, and layer ``l`` is its row ``l``.
- ``"kv_window"``: the layers whose row at ``p`` attends
  ``p - w + 1 .. p`` alone.  Each decode slot OWNS a ring of
  ``ring_rows`` rows a layer (:func:`ring_rows`: the window and the
  most rows one launch may feed), position ``p`` at ring row ``p %
  ring_rows``: a row is overwritten, and so let go, once it lies
  ``ring_rows`` behind the newest.  No table grows, nothing is
  allocated or freed, and the host does nothing as a sequence slides:
  the ring's block ids are the slot's (:func:`ring_tables`).  Why a
  ring and not a second table whose blocks are freed as they slide
  out: that would take a second allocator, admission over two kinds
  of block, and host work in every step, to save memory that is a
  twentieth of the full layers' already.  What it costs: a ring is its
  slot's, so the prefix cache cannot keep a finished request's window
  rows, and a model with window layers takes no prefix hit
  (``InferenceServer``).  A rejected draft needs no rollback here: its
  rows lie past the accepted length, and what they overwrote lay
  ``ring_rows`` behind them, outside every later row's window.

Token slots are axis 1 of every leaf (the scale sidecar's too), which
is what the hand-off and offload payloads slice by.  The forms that
index ACROSS layers at once (``arr.at[:, slots]``) are the ones to
avoid: they make the compiler transpose the leaf to bring the slots
outermost, a pool-sized copy each way.

Split of responsibilities:

- device side (this module's pure functions): writes of freshly
  projected K/V at flat slots (``write_layer``; ``write_tokens`` /
  ``write_prefill`` over all layers), the fixed-shape gather of a
  request batch's context (``gather_layer``; ``gather_context`` for
  the oracle), whole-block copies, and :class:`CacheView`, what the
  model sees of the pool — all jit-traceable, cache pytree in/out;
- host side (:class:`BlockAllocator`): the free list.  Allocation is
  control flow, not math — it stays in Python where it is O(blocks)
  trivial, exactly like the schedulers it serves.

Physical block 0 is RESERVED as the garbage sink: unallocated
block-table entries and padded prefill positions all point at it, so
every write and read stays in-bounds with no data-dependent branching
— reads from it are masked (by the context bias built from lengths, or
by position inside the kernel), writes to it land on data nothing will
ever read.

Dtype policy: the cache is typically the HBM hog (2 * L * T * H * D
per token), so it defaults to the amp "half" dtype — the active
``amp.initialize`` policy's ``cast_model_type`` when one is installed,
else bfloat16 (``amp.properties.HALF``).  ``KVCacheConfig(dtype=...)``
overrides explicitly (tests pin fp32 for bit-parity runs).

Quantized mode (``docs/serving.md``, "Quantized KV cache"):
``KVCacheConfig(quantize="int8")`` stores the pool as int8 with a
per-token-slot, per-head fp32 absmax scale SIDECAR — two extra cache
leaves ``k_scale`` / ``v_scale`` of shape (L, num_slots, H), allocated
block-granular alongside the pool so every block-lifecycle path (COW
duplication, prefix-cache holds, speculation rollback, preemption
re-prefill) carries scales with their blocks by construction, and
head-sharded with their heads under tensor parallelism.  ``dtype``
keeps meaning the COMPUTE dtype the dequantized values widen to; the
STORAGE dtype becomes int8 (:meth:`KVCacheConfig.storage_dtype`).
Scales are per token slot — not one scalar per block — because a
block fills incrementally (decode writes one token at a time) and a
shared per-block scalar would have to requantize earlier tokens from
their already-lossy int8, destroying the bit-stability the serving
stack pins across preemption / chunked prefill / COW (BENCH_NOTES,
kv-quant decision table).
"""

from __future__ import annotations

import dataclasses
import functools
from typing import Dict, List, Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

from apex_tpu.models.family import CacheRow
# the quantization numeric contract lives with the kernels that widen
# it back (ops); re-exported here because the cache is what stores it
from apex_tpu.ops.kv_quant import (  # noqa: F401  (re-export)
    INT8_QMAX,
    dequantize_kv,
    quantize_kv,
)

NEG_INF = -1e9

# env twin of the ``kv_quant=`` knob (InferenceServer reads it)
KV_QUANT_ENV = "APEX_TPU_KV_QUANT"

_QUANT_MODES = (None, "int8")


def resolve_kv_quant(value):
    """Normalize a ``kv_quant`` knob / ``APEX_TPU_KV_QUANT`` env value
    to ``None`` or ``"int8"``; anything else is a loud error."""
    if value is None:
        return None
    if isinstance(value, str):
        v = value.strip().lower()
        if v in ("", "0", "none", "off"):
            return None
        if v in ("1", "int8"):
            return "int8"
    raise ValueError(
        f"unknown KV quantization mode {value!r} "
        f"(expected one of: None/'', 'int8')")


def resolve_cache_dtype(dtype=None):
    """The ONE resolution of ``KVCacheConfig.dtype=None``: an explicit
    dtype wins; else the installed amp policy's half type (``O1``-``O3``
    set ``cast_model_type``); else bfloat16 (TPU-native half).

    Integer dtypes are rejected: ``dtype`` is the COMPUTE dtype the
    pool's values carry through attention, and an int pool here would
    silently store garbage K/V — int8 storage is a quantization mode
    (``KVCacheConfig(quantize="int8")``), not a cache dtype."""
    if dtype is not None:
        dt = jnp.dtype(dtype)
        if not jnp.issubdtype(dt, jnp.floating):
            raise TypeError(
                f"cache dtype must be a floating-point compute dtype, "
                f"got {dt}; for an int8-quantized KV pool pass "
                f"KVCacheConfig(quantize='int8') (per-block-scaled "
                f"storage), not dtype={dt}")
        return dt
    try:
        from apex_tpu.amp._amp_state import _amp_state
        props = _amp_state.opt_properties
        cast = getattr(props, "cast_model_type", None) if props else None
        if cast is not None:
            return jnp.dtype(cast)
    except Exception:
        pass
    from apex_tpu.amp.properties import HALF
    return jnp.dtype(HALF)


@dataclasses.dataclass(frozen=True)
class KVCacheConfig:
    """Geometry of the block pool.

    A token's row in one layer is ``num_heads`` groups of
    ``2 * head_dim`` values (:attr:`row_width` in all): a head's ``K``
    beside its ``V``, ``head_dim`` each, or ONE group for a latent row
    that all query heads share (``num_heads`` 1, ``head_dim`` half the
    stored row; ``DecodeEngine`` fills both from the model family's
    ``CacheRow``).  All byte accounting goes by the row's width.

    ``num_blocks`` INCLUDES the reserved garbage block 0, so the
    usable capacity is ``(num_blocks - 1) * block_size`` tokens.
    ``dtype=None`` defers to :func:`resolve_cache_dtype`.

    ``quantize="int8"`` turns on quantized storage: the pool leaves
    become int8 and a per-slot, per-head fp32 scale sidecar
    (``k_scale`` / ``v_scale``, shape (L, num_slots, H)) rides along;
    ``dtype`` then names the COMPUTE dtype dequantized values widen
    to.  All byte accounting (:meth:`bytes`, :attr:`bytes_per_block`)
    includes the sidecar — occupancy and headroom math must price a
    block at what it actually costs in HBM."""

    num_layers: int
    num_heads: int
    head_dim: int
    num_blocks: int
    block_size: int = 16
    dtype: Optional[object] = None
    quantize: Optional[str] = None

    def __post_init__(self):
        if self.num_blocks < 2:
            raise ValueError(
                "num_blocks must be >= 2 (block 0 is the reserved "
                f"garbage sink); got {self.num_blocks}")
        if self.block_size < 1:
            raise ValueError(f"block_size must be >= 1; got "
                             f"{self.block_size}")
        if self.quantize not in _QUANT_MODES:
            raise ValueError(
                f"quantize must be one of {_QUANT_MODES}; got "
                f"{self.quantize!r}")
        self.resolved_dtype()   # reject int compute dtypes loudly

    @property
    def num_slots(self) -> int:
        return self.num_blocks * self.block_size

    @property
    def row_width(self) -> int:
        """Stored values of one token in one layer: the pool leaf's
        minor dimension."""
        return self.num_heads * 2 * self.head_dim

    @property
    def row_bytes(self) -> int:
        """HBM bytes of one token in one layer, scale sidecar
        included."""
        return self.bytes_per_block // (self.num_layers * self.block_size)

    @property
    def usable_tokens(self) -> int:
        return (self.num_blocks - 1) * self.block_size

    @property
    def quantized(self) -> bool:
        return self.quantize is not None

    def resolved_dtype(self):
        return resolve_cache_dtype(self.dtype)

    def storage_dtype(self):
        """The dtype the pool's K/V payload is actually stored in:
        int8 under quantization, the compute dtype otherwise."""
        if self.quantized:
            return jnp.dtype(jnp.int8)
        return self.resolved_dtype()

    @property
    def scale_bytes_per_block(self) -> int:
        """HBM cost of one block's share of the scale sidecar (both
        K and V legs); 0 when quantization is off."""
        if not self.quantized:
            return 0
        return 2 * self.num_layers * self.block_size * self.num_heads \
            * jnp.dtype(jnp.float32).itemsize

    @property
    def bytes_per_block(self) -> int:
        """TRUE HBM cost of one physical block — the rows' payload plus
        the scale sidecar under quantization.  The allocator's
        occupancy/fragmentation math and the fixed-pool-bytes bench
        arms price blocks with this, so quantized headroom claims are
        net of the sidecar."""
        payload = (self.num_layers * self.block_size * self.row_width
                   * self.storage_dtype().itemsize)
        return payload + self.scale_bytes_per_block

    def bytes(self) -> int:
        """HBM footprint of the pool (whole rows, scale sidecar
        included when quantized)."""
        return self.num_blocks * self.bytes_per_block


def pool_specs(axis):
    """``PartitionSpec`` of every pool leaf under tensor parallelism
    over mesh axis ``axis``: the payload's lane dimension is heads-major
    (``H`` groups of ``2 * D``), so splitting it ``tp`` ways hands each
    device whole heads; the scale sidecar's heads are its last
    dimension."""
    from jax.sharding import PartitionSpec as P
    return {"kv": P(None, None, axis), "k_scale": P(None, None, axis),
            "v_scale": P(None, None, axis)}


def init_kv_cache(cfg: KVCacheConfig, sharding=None,
                  scale_sharding=None):
    """Allocate the zeroed pool: ``{"kv"}`` of shape
    (L, num_slots, ``cfg.row_width``) in the storage dtype (the layout
    the module docstring derives), plus — under ``quantize="int8"`` — the
    fp32 scale sidecar ``{"k_scale", "v_scale"}`` each
    (L, num_slots, H).  Token slots are axis 1 of every leaf.

    ``sharding``: optional ``jax.sharding.Sharding`` for the payload —
    tensor-parallel serving passes the head-sharded placement
    (:func:`pool_specs`) so every device materializes ONLY its ``H/tp``
    heads of every block; the zeros are created sharded (jit
    ``out_shardings``), never allocated whole and scattered.
    ``scale_sharding`` is the sidecar's placement, so scales live on
    the same shard as the heads they dequantize."""
    shape = (cfg.num_layers, cfg.num_slots, cfg.row_width)
    dt = cfg.storage_dtype()

    def build():
        cache = {"kv": jnp.zeros(shape, dt)}
        if cfg.quantized:
            sshape = shape[:2] + (cfg.num_heads,)
            cache["k_scale"] = jnp.zeros(sshape, jnp.float32)
            cache["v_scale"] = jnp.zeros(sshape, jnp.float32)
        return cache

    if sharding is None:
        return build()
    outs = {"kv": sharding}
    if cfg.quantized:
        outs["k_scale"] = scale_sharding
        outs["v_scale"] = scale_sharding
    return jax.jit(build, out_shardings=outs)()


# ---------------------------------------------------------------------------
# device-side pure functions (jit-traceable, cache pytree in -> out)
# ---------------------------------------------------------------------------

def pack_rows(k, v):
    """(..., H, D) keys and values -> (..., H * 2 * D) pool rows: every
    head's ``K_h`` beside its ``V_h``."""
    return jnp.concatenate([k, v], axis=-1).reshape(
        *k.shape[:-2], k.shape[-2] * 2 * k.shape[-1])


def unpack_rows(rows, num_heads: int):
    """The inverse of :func:`pack_rows`: (..., H * 2 * D) -> ``(k, v)``
    each (..., H, D)."""
    r = rows.reshape(*rows.shape[:-1], num_heads, 2, -1)
    return r[..., 0, :], r[..., 1, :]


def slot_index(block_tables, positions, block_size: int):
    """Flat pool slot of logical ``positions`` — (B,) one per
    sequence, or (B, S) many per sequence — under ``block_tables``
    (B, max_blocks): ``table[pos // bs] * bs + pos % bs``.
    Unallocated table entries are 0, so out-of-range logical positions
    land in the garbage block."""
    blk = positions // block_size
    off = positions % block_size
    squeeze = blk.ndim == block_tables.ndim - 1
    if squeeze:
        blk = blk[..., None]
    phys = jnp.take_along_axis(block_tables, blk, axis=-1)
    if squeeze:
        phys = phys[..., 0]
    return phys * block_size + off


def write_layer(cache, layer, kv, slots):
    """Write one layer's fresh rows into the (donated) pool, in place.

    kv: ``(k, v)`` each (B, S, H, D), or the rows themselves (B, S, W)
    where the model makes them whole (a latent row; zeros fill the
    pool's lane padding); slots: (B, S) flat slot indices (padded
    positions pointed at the garbage block by the caller).
    Under quantization kv is ``((k_q, k_scale), (v_q, v_scale))`` with
    int8 payloads and (B, S, H) fp32 scales — ALREADY quantized by the
    model's projection path, so the pool receives byte-for-byte the
    values attention uses.

    The form matters on the chip: a scatter of whole rows at
    ``[layer, slots]`` compiles to an update of the donated buffer with
    no temporary, where one over every layer at once (``[:, slots]``)
    makes XLA:TPU transpose the whole leaf there and back."""
    flat = slots.reshape(-1)
    out = dict(cache)
    if isinstance(kv, tuple):
        k, v = kv
        if "k_scale" in cache:
            (k, ks), (v, vs) = k, v
            out["k_scale"] = cache["k_scale"].at[layer, flat].set(
                ks.reshape(-1, ks.shape[-1]))
            out["v_scale"] = cache["v_scale"].at[layer, flat].set(
                vs.reshape(-1, vs.shape[-1]))
        rows = pack_rows(k, v)
    else:
        rows = jnp.pad(kv, [(0, 0)] * (kv.ndim - 1) + [
            (0, cache["kv"].shape[-1] - kv.shape[-1])])
    out["kv"] = cache["kv"].at[layer, flat].set(
        rows.astype(cache["kv"].dtype).reshape(-1, rows.shape[-1]))
    return out


def _per_layer(kvs, layer):
    """Layer ``layer`` of a stacked (L, ...) fresh-K/V struct, plain or
    quantized."""
    return jax.tree.map(lambda x: x[layer], kvs)


def write_tokens(cache, kvs, slots):
    """Write one new token per sequence into every layer of the pool.

    kvs: ``(k_new, v_new)`` each (L, B, 1, H, D) stacked per layer;
    slots: (B,) flat slot indices.  Under quantization kvs is
    ``((k_q, k_scale), (v_q, v_scale))`` with the payloads
    (L, B, 1, H, D) int8 and the scales (L, B, 1, H) fp32."""
    return write_prefill(cache, kvs, slots[:, None])


def write_prefill(cache, kvs, slots):
    """Write a whole prompt's K/V into every layer of the pool.

    kvs: tuple of (L, B, S, H, D); slots: (B, S) flat slot indices with
    padded positions pointed at the garbage block by the caller.
    Under quantization kvs is ``((k_q, k_scale), (v_q, v_scale))``
    exactly as in :func:`write_tokens` (payloads (L, B, S, H, D),
    scales (L, B, S, H)).  One :func:`write_layer` a layer: each is an
    in-place update."""
    for layer in range(cache["kv"].shape[0]):
        cache = write_layer(cache, layer, _per_layer(kvs, layer), slots)
    return cache


def _by_block(arr, block_size: int):
    """(L, num_slots, ...) -> (L, num_blocks, block_size, ...): a
    reshape of leading dimensions, free in the pool's layout."""
    return arr.reshape(arr.shape[0], -1, block_size, *arr.shape[2:])


def gather_layer(cache, layer, block_tables, block_size: int,
                 num_heads: int):
    """One layer's logical context of each sequence, gathered from the
    pool block by block: ``(k_ctx, v_ctx)`` of shape (B, T, H, D) with
    T = max_blocks * block_size, plus ``(k_scale, v_scale)`` (B, T, H)
    under quantization.  Position j IS logical token j because tables
    are ordered; unallocated entries read the garbage block and the
    caller's context bias masks them."""
    b, mb = block_tables.shape
    rows = _by_block(cache["kv"], block_size)[layer][block_tables]
    out = unpack_rows(rows.reshape(b, mb * block_size, -1), num_heads)
    if "k_scale" in cache:
        out += tuple(
            _by_block(cache[n], block_size)[layer][block_tables].reshape(
                b, mb * block_size, num_heads)
            for n in ("k_scale", "v_scale"))
    return out


def gather_context(cache, block_tables, block_size: int, num_heads: int,
                   out_dtype=None):
    """Gather each sequence's logical context from the pool, every
    layer at once — the oracle's form (the serving programs read one
    layer at a time, :func:`gather_layer`, or in place).

    block_tables: (B, max_blocks) int32 (0 = unallocated -> garbage
    block; masked by the caller's ctx bias).  Returns ``(k_ctx,
    v_ctx)`` of shape (L, B, max_blocks * block_size, H, D): gathered
    position j IS logical token j because tables are ordered."""
    b, mb = block_tables.shape
    rows = _by_block(cache["kv"], block_size)[:, block_tables]
    k, v = unpack_rows(rows.reshape(rows.shape[0], b, mb * block_size,
                                    -1), num_heads)
    if out_dtype is not None:
        k = k.astype(out_dtype)
        v = v.astype(out_dtype)
    return k, v


POOL_LEAVES = ("kv", "k_scale", "v_scale")
# the window layers' leaf: indexed by the slot's ring, not by block
# table, so no block mover carries it
WINDOW_LEAF = "kv_window"
# the state layers' leaf: a ring a slot, float32, no block table either
STATE_LEAF = "conv_state"


def ring_rows(window: int, block_size: int) -> int:
    """Rows of a window layer's ring: the ``window`` a row attends and
    up to three windows of rows one launch may feed behind it (a
    prefill chunk, a verify launch), in whole blocks."""
    return -(-4 * window // block_size) * block_size


def state_rows(keep: int, fed: int, block_size: int) -> int:
    """Rows of a state layer's ring: the ``keep`` rows a launch reads
    before its first and the ``fed`` rows of a verify launch, whose
    rejected drafts must not overwrite them, in whole blocks."""
    return -(-(keep + fed) // block_size) * block_size


def ring_tables(ring, ring_blocks: int):
    """The block ids of each row's ring, (B, ring_blocks): ring ``n``
    owns the blocks ``1 + n * ring_blocks ..`` of the window leaf
    (block 0 is its garbage sink)."""
    return (1 + ring.astype(jnp.int32)[:, None] * ring_blocks
            + jnp.arange(ring_blocks, dtype=jnp.int32)[None, :])


def ring_slots(ring, positions, live, rows: int, block_size: int):
    """Flat slots in the window leaf of ``positions`` (B, S) of rings
    ``ring`` (B,): position ``p`` at ring row ``p % rows``; rows that
    are no tokens (``live`` false) at the garbage block."""
    base = block_size + ring.astype(jnp.int32)[:, None] * rows
    return jnp.where(live, base + positions % rows, 0)


def pool_leaves(cache):
    """The leaves of the cache pytree that are indexed by token slot:
    the payload and, quantized, its scale sidecar.  What else a family
    carries beside the pool (``cfg.serving_counters()``) moves with no
    block."""
    return {n: a for n, a in cache.items() if n in POOL_LEAVES}


def pool_dtype(cache):
    """The dtype the pool's K/V payload is stored in."""
    return cache["kv"].dtype


def scale_sidecar(cache):
    """The quantized pool's ``(k_scale, v_scale)`` leaves, each
    (L, num_slots, H) fp32."""
    return cache["k_scale"], cache["v_scale"]


def block_slots(block_ids, block_size: int):
    """Flat slots of physical blocks ``block_ids``' token rows, block
    after block (host side, numpy)."""
    ids = np.asarray(block_ids, np.int64).reshape(-1, 1)
    return (ids * block_size + np.arange(block_size)[None, :]).reshape(-1)


def read_slots(cache, slots, num_heads: int):
    """What the pool holds at flat ``slots`` (n,), by name: ``k`` and
    ``v`` (L, n, H, D) in the storage dtype, and ``k_scale`` /
    ``v_scale`` (L, n, H) under quantization.  For tests and
    postmortems; no serving program calls it."""
    k, v = unpack_rows(cache["kv"][:, slots], num_heads)
    out = {"k": k, "v": v}
    out.update({n: cache[n][:, slots] for n in cache if n != "kv"})
    return out


def context_bias(lengths, max_context: int):
    """(B,) valid-token counts -> (B, T) additive bias: 0 for logical
    slots < length, NEG_INF beyond (covers unwritten slots, freed
    garbage, and the tail of the last partial block)."""
    t = jnp.arange(max_context, dtype=jnp.int32)[None, :]
    return jnp.where(t < lengths[:, None].astype(jnp.int32),
                     0.0, NEG_INF).astype(jnp.float32)


def read_blocks(cache, block_ids, block_size: int):
    """Every leaf's rows of physical blocks ``block_ids`` (n,), in
    order: ``{name: (L, n * block_size, ...)}`` — the export half of
    the hand-off and offload payloads.  Whole blocks are sliced, so
    nothing of the pool's size is touched."""
    return {name: jnp.concatenate(
        [lax.dynamic_slice_in_dim(arr, block_ids[i] * block_size,
                                  block_size, 1)
         for i in range(block_ids.shape[0])], axis=1)
        for name, arr in pool_leaves(cache).items()}


def write_blocks(cache, block_ids, leaves, block_size: int):
    """Write ``leaves`` (``{name: (L, n * block_size, ...)}``, as
    :func:`read_blocks` gives them) into physical blocks ``block_ids``
    (n,) of the (donated) pool, one in-place block update each.
    Padding ids point at the garbage block."""
    def put(i, arr, rows):
        return lax.dynamic_update_slice_in_dim(
            arr, lax.dynamic_slice_in_dim(rows, i * block_size,
                                          block_size, 1),
            block_ids[i] * block_size, 1)

    return {**cache, **{name: lax.fori_loop(
        0, block_ids.shape[0],
        functools.partial(put, rows=leaves[name].astype(arr.dtype)), arr)
        for name, arr in pool_leaves(cache).items()}}


def copy_blocks_across(dst_cache, src_cache, src, dst, block_size: int):
    """Whole-block copy ``src[i] (in src_cache) -> dst[i] (in
    dst_cache)`` BETWEEN two pools of identical geometry — the device
    half of the disaggregated prefill/decode hand-off
    (``docs/serving.md``, "Disaggregated prefill/decode"): a finished
    prefill's blocks move from the prefill pool into the decode pool
    as fixed-shape block reads and in-place block updates, so the two
    pools' programs share no array and their compute never serializes
    through a common pool version.

    src, dst: (M,) int32 physical block ids, (0, 0)-padded exactly
    like :func:`copy_blocks` (garbage block -> garbage block is a
    no-op by construction).  Copies EVERY leaf the two caches share —
    under quantization the scale sidecar rows move with their int8
    payload, so a handed-off block dequantizes bit-identically on the
    decode side."""
    return write_blocks(dst_cache, dst,
                        read_blocks(src_cache, src, block_size),
                        block_size)


def copy_blocks(cache, src, dst, block_size: int):
    """Whole-block copy ``src[i] -> dst[i]`` inside the pool — the
    device half of copy-on-write duplication (a request that must
    write into a block shared through the prefix cache first clones it
    into a private block).  Every source is read before any
    destination is written.

    src, dst: (M,) int32 physical block ids.  Unused pairs pad with
    (0, 0): copying the garbage block onto itself is a no-op by
    construction, so the call stays fixed-shape.

    Copies EVERY cache leaf — under quantization the scale sidecar
    legs duplicate with their payload in the same program, so a COW
    clone dequantizes bit-identically to its source block."""
    return copy_blocks_across(cache, cache, src, dst, block_size)


# ---------------------------------------------------------------------------
# what the model sees of the pool
# ---------------------------------------------------------------------------

@functools.partial(jax.jit, static_argnames=(
    "block_size", "scale", "latent_value", "heads_per_group", "window"))
def _attend_in_place(pool, layer, q, kv, tables, start, slots, *,
                     block_size, scale=None, latent_value=None,
                     heads_per_group=1, window=None):
    """One layer of the table path: write the fed rows, then attend
    the pool in place.  A jitted function with ``layer`` an array, so
    that a program of many layers traces and lowers it once and calls
    it for each.  (Traced layer by layer with a Python ``layer``, the
    wrapper's index arrays were made on the device while tracing and
    read back while lowering: 30 s more set-up at GPT-2 XL's 48 layers;
    my chip runs, PR 25.)"""
    from apex_tpu.ops.decode_attention import paged_attention

    pool = write_layer({"kv": pool}, layer, kv, slots)["kv"]
    return paged_attention(q, pool, layer, tables, start,
                           block_size=block_size, scale=scale,
                           latent_value=latent_value,
                           heads_per_group=heads_per_group,
                           window=window), pool


@functools.partial(
    jax.tree_util.register_dataclass,
    data_fields=("cache", "tables", "start", "slots", "ring"),
    meta_fields=("block_size", "row", "table", "layers"))
@dataclasses.dataclass(frozen=True)
class CacheView:
    """The pool as one serving launch sees it, threaded through the
    model's layers: each layer's attention calls :meth:`attend` with its
    queries and fresh rows and gets back its context and the view with
    the pool updated.  The model never learns the pool's layout.

    ``tables`` (B, blocks_per_seq) and ``start`` (B,) — each
    sequence's cached length, the position of its first fed row —
    address the reads; ``slots`` (B, S) are the flat slots the S fed
    rows are written to (invalid ones pointed at the garbage block).
    ``row`` is what the model's family keeps a token and layer
    (``models.family.CacheRow``).  ``table`` picks the attention path,
    fixed when the engine builds its programs
    (``DecodeEngine.attention_paths``):

    - ``True``, *attend through the table*: the layer's rows are
      written first, then :func:`ops.decode_attention.paged_attention`
      reads the pool in place, only the pages up to each sequence's
      last row.  No gathered copy, no bias row, no concatenation.
    - ``False``, *gathered*: the layer's context is gathered block by
      block (:func:`gather_layer`) and the jnp-or-kernel ops of the
      gathered form attend it (``ops.cached_attention`` for one row,
      ``ops.chunk_cached_attention`` for more, over the fresh K/V
      concatenated behind the context; a latent pool's
      ``ops.decode_attention.latent_attention_reference`` over the
      context with the rows written into it).  The int8 pool, a mesh
      and the CPU take this path.

    ``cache`` is the engine's whole cache pytree: the pool's leaves
    and, beside them, the counters a family carries through its
    programs (:meth:`count`).

    ``layers`` says where each layer's rows live, ``(leaf, row of the
    leaf, window)`` a layer: ``("kv", l, None)`` for a layer that keeps
    every token, read through ``tables``; ``("kv_window", n, w)`` for
    a window layer, read through the ring of each sequence's slot,
    ``ring`` (B,); ``("conv_state", n, None)`` for a layer that keeps a
    state, which the model reaches through :meth:`convolve`.
    :meth:`attend` picks by the layer; the model never learns which.
    None: every layer is ``("kv", l, None)``.  ``ring`` None: row ``b``
    of the launch is slot ``b`` (a launch of the whole batch)."""

    cache: dict
    tables: jax.Array
    start: jax.Array
    slots: jax.Array
    block_size: int
    row: CacheRow
    table: bool
    ring: Optional[jax.Array] = None
    layers: Optional[tuple] = None

    @property
    def live(self):
        """(B, S) which fed rows are tokens: the rows of idle slots and
        padding were pointed at the garbage block."""
        return self.slots >= self.block_size

    @property
    def rings(self):
        """(B,) each row's slot, whose rings its window and state layers
        use."""
        if self.ring is not None:
            return self.ring
        return jnp.arange(self.slots.shape[0], dtype=jnp.int32)

    def convolve(self, layer: int, bcx, taps):
        """A state layer's short convolution (``ops.short_conv``):
        ``bcx`` (B, S, 3W) the fed rows ``B | C | x``, ``taps`` (K, W).
        The slot's ring is read for the positions before the first fed
        row, then its last fed rows are written.  Returns ``(C * z
        (B, S, W), view)``."""
        from apex_tpu.ops.short_conv import short_conv

        leaf, index, _ = self.layers[layer]
        y, state = short_conv(
            bcx, taps, self.cache[leaf], np.int32(index), self.rings,
            self.start, jnp.sum(self.live, axis=1, dtype=jnp.int32),
            consecutive=self.ring is None)
        return y, dataclasses.replace(self,
                                      cache={**self.cache, leaf: state})

    def count(self, name: str, index: int, amounts):
        """The view with ``amounts`` added to row ``index`` of the
        counter ``name`` the family declared
        (``cfg.serving_counters()``): accumulated on the device, read
        only when somebody asks (``stats()``)."""
        cache = dict(self.cache)
        cache[name] = cache[name].at[index].add(
            amounts.astype(cache[name].dtype))
        return dataclasses.replace(self, cache=cache)

    def attend(self, layer: int, q, kv, scale=None):
        """The fed rows' attention over their cached past and
        themselves, causally, and the view after the write.

        A ``"kv"`` row: ``q`` (B, S, H, D) and the layer's fresh ``kv``
        — ``(k, v)`` each (B, S, G, D), G the key-value heads, or,
        quantized, ``((k_q, k_scale), (v_q, v_scale))`` — to ``(context
        (B, S, H, D), view)``; a window layer's rows go to the slot's
        ring and it attends its window alone.  A
        ``"latent"`` row: ``q`` (B, S, H, used) the absorbed queries
        ``q_lat | q_pe``, ``kv`` (B, S, used) the rows ``c | k_pe``,
        ``scale`` the expanded form's (a ``"kv"`` row's is always
        ``1/sqrt(D)``) — to ``(context over the value
        lanes (B, S, H, rank), view)``."""
        if self.row.kind == "latent":
            return self._attend_latent(layer, q, kv, scale)
        from apex_tpu.ops.decode_attention import (
            cached_attention,
            chunk_cached_attention,
        )

        leaf, index, window = (self.layers[layer] if self.layers
                               else ("kv", layer, None))
        if window is not None or self.row.shared:
            return self._attend_grouped(leaf, index, window, q, kv)
        if self.table:
            ctx, pool = _attend_in_place(
                self.cache["kv"], np.int32(index), q, kv, self.tables,
                self.start, self.slots, block_size=self.block_size)
            return ctx, dataclasses.replace(
                self, cache={**self.cache, "kv": pool})
        ctx_kv = gather_layer(self.cache, index, self.tables,
                              self.block_size, self.row.groups)
        k, v = kv
        ks = vs = None
        if "k_scale" in self.cache:
            # int8 end to end: quantized context + the fed rows' own
            # quantized K/V concatenate with their scale rows; the
            # attention ops widen at read
            (k, ks), (v, vs) = k, v
            ks = jnp.concatenate([ctx_kv[2], ks], axis=1)
            vs = jnp.concatenate([ctx_kv[3], vs], axis=1)
        k_full = jnp.concatenate([ctx_kv[0].astype(k.dtype), k], axis=1)
        v_full = jnp.concatenate([ctx_kv[1].astype(v.dtype), v], axis=1)
        bias = context_bias(self.start, ctx_kv[0].shape[1])
        if q.shape[1] == 1:
            # decode: the self slot is always live (bias 0)
            bias = jnp.concatenate(
                [bias, jnp.zeros((q.shape[0], 1), jnp.float32)], axis=1)
            ctx = cached_attention(q, k_full, v_full, kv_bias=bias,
                                   k_scale=ks, v_scale=vs)
        else:
            # context masked to slots < start, causal within the rows
            ctx = chunk_cached_attention(q, k_full, v_full, bias,
                                         k_scale=ks, v_scale=vs)
        return ctx, dataclasses.replace(
            self, cache=write_layer(self.cache, index, kv, self.slots))

    def _attend_grouped(self, leaf, index, window, q, kv):
        """A ``"kv"`` row that several query heads read, or a window
        layer: the rows are written first, into the table's blocks or
        the slot's ring, and attention goes by positions."""
        from apex_tpu.ops.decode_attention import (
            grouped_attention_reference,
        )

        bs = self.block_size
        q_pos = self.start[:, None] + jnp.arange(
            q.shape[1], dtype=jnp.int32)[None, :]
        if window is None:
            tables, slots = self.tables, self.slots
        else:
            blocks = ring_rows(window, bs) // bs
            tables = ring_tables(self.rings, blocks)
            slots = ring_slots(self.rings, q_pos, self.live, blocks * bs,
                               bs)
        if self.table:
            ctx, pool = _attend_in_place(
                self.cache[leaf], np.int32(index), q, kv, tables,
                self.start, slots, block_size=bs,
                heads_per_group=self.row.heads_per_group, window=window)
            return ctx, dataclasses.replace(
                self, cache={**self.cache, leaf: pool})
        pool = write_layer({"kv": self.cache[leaf]}, index, kv,
                           slots)["kv"]
        b, nb = tables.shape
        k, v = unpack_rows(_by_block(pool, bs)[index][tables].reshape(
            b, nb * bs, -1), self.row.groups)
        k_pos = jnp.arange(nb * bs, dtype=jnp.int32)[None, :]
        if window is not None:
            # ring row i holds the newest position at or before the
            # last fed row that is i modulo the ring (negative: none)
            newest = q_pos[:, -1:]
            k_pos = newest - (newest - k_pos) % (nb * bs)
        ctx = grouped_attention_reference(q, k, v, q_pos, k_pos,
                                          window=window)
        return ctx, dataclasses.replace(
            self, cache={**self.cache, leaf: pool})

    def _attend_latent(self, layer, q, rows, scale):
        """The latent row's :meth:`attend`: one absorbed path for
        decode, verify and chunk alike."""
        from apex_tpu.ops.decode_attention import (
            latent_attention_reference,
        )

        width, rank = self.row.width, self.row.value[1]
        q = jnp.pad(q, ((0, 0),) * 3 + ((0, width - q.shape[-1]),))
        if self.table:
            ctx, pool = _attend_in_place(
                self.cache["kv"], np.int32(layer), q, rows, self.tables,
                self.start, self.slots, block_size=self.block_size,
                scale=float(scale), latent_value=rank)
            return ctx, dataclasses.replace(
                self, cache={**self.cache, "kv": pool})
        cache = write_layer(self.cache, layer, rows, self.slots)
        b, mb = self.tables.shape
        ctx_rows = _by_block(cache["kv"], self.block_size)[layer][
            self.tables].reshape(b, mb * self.block_size, width)
        positions = self.start[:, None] + jnp.arange(
            q.shape[1], dtype=jnp.int32)[None, :]
        ctx = latent_attention_reference(q, ctx_rows, positions,
                                         value=rank, scale=float(scale))
        return ctx, dataclasses.replace(self, cache=cache)


# ---------------------------------------------------------------------------
# host-side allocator
# ---------------------------------------------------------------------------

class BlockAllocator:
    """Refcounted free-list over physical blocks 1..num_blocks-1 (0 is
    the garbage sink and is never handed out).

    LIFO reuse (a stack) keeps hot blocks hot — a freed request's
    blocks are the most recently touched HBM and the next allocation
    gets them first.  A parallel ``_free_set`` mirrors the list so
    double-free detection and :meth:`free` are O(1) per block instead
    of an O(n) list scan.

    Refcounts are what make prefix caching possible: a block shared by
    several requests' tables carries one ref per table
    (:meth:`incref`), and :meth:`free` only returns it to the free
    list when the last ref drops.  A block whose refcount reaches zero
    is first offered to ``release_hook`` (the prefix cache): the hook
    returning True keeps the block out of the free list — still
    resident, evictable later via :meth:`release_to_free` — so cached
    prefixes survive their original request.  Every block is therefore
    in exactly one of three states: free (in the list+set), live
    (refcount >= 1), or cache-held (refcount 0, hook-retained)."""

    def __init__(self, cfg: KVCacheConfig):
        self.cfg = cfg
        self.release_hook = None      # blk -> bool; True = hook keeps it
        self.reset_hooks: List = []   # called on reset() (cache clears)
        self.reset()

    def reset(self):
        """Return every block to the free list (between workloads;
        in-place so schedulers holding this allocator stay wired).
        Reset hooks fire so a prefix cache indexing the old blocks
        drops its now-dangling entries."""
        self._free: List[int] = list(range(self.cfg.num_blocks - 1, 0,
                                           -1))
        self._free_set = set(self._free)
        self._refs: Dict[int, int] = {}
        self.live_peak = 0          # high-watermark of live blocks
        for hook in self.reset_hooks:
            hook()

    @property
    def num_free(self) -> int:
        return len(self._free)

    @property
    def num_live(self) -> int:
        """Blocks currently referenced by at least one table (memory
        observability: ``usable - num_free - num_live`` is the
        cache-held remainder)."""
        return len(self._refs)

    def _note_live(self) -> None:
        if len(self._refs) > self.live_peak:
            self.live_peak = len(self._refs)

    def can_alloc(self, n: int) -> bool:
        return n <= len(self._free)

    def alloc(self, n: int) -> List[int]:
        """Pop n blocks at refcount 1; raises :class:`MemoryError` when
        the pool is exhausted (the scheduler checks :meth:`can_alloc` /
        evicts / preempts first, so reaching this is a caller bug)."""
        if n <= 0:
            return []
        if n > len(self._free):
            raise MemoryError(
                f"KV cache pool exhausted: requested {n} blocks, "
                f"{len(self._free)} free "
                f"(pool={self.cfg.num_blocks - 1})")
        out = self._free[-n:][::-1]
        del self._free[len(self._free) - n:]
        for blk in out:
            self._free_set.discard(blk)
            self._refs[blk] = 1
        self._note_live()
        return out

    def refs(self, blk: int) -> int:
        return self._refs.get(blk, 0)

    def incref(self, blocks: List[int]):
        """Add one ref per block (a second table now references it)."""
        for blk in blocks:
            if blk not in self._refs:
                raise ValueError(
                    f"incref of unallocated block {blk}")
            self._refs[blk] += 1

    def adopt(self, blk: int):
        """Re-own a cache-held block (refcount 0, hook-retained) at
        refcount 1 — the prefix cache reactivating an evictable block a
        new request just matched."""
        if blk in self._free_set or blk in self._refs:
            raise ValueError(
                f"adopt of block {blk} that is not cache-held "
                f"(free={blk in self._free_set}, "
                f"refs={self._refs.get(blk)})")
        self._refs[blk] = 1
        self._note_live()

    def free(self, blocks: List[int]):
        """Drop one ref per block; blocks reaching zero return to the
        free list unless ``release_hook`` claims them (prefix cache
        hold).  All blocks validate before any state changes."""
        for blk in blocks:
            if not 1 <= blk < self.cfg.num_blocks:
                raise ValueError(f"freeing invalid block id {blk}")
            if blk in self._free_set:
                raise ValueError(f"double free of block {blk}")
            if blk not in self._refs:
                raise ValueError(f"freeing unallocated block {blk}")
        for blk in blocks:
            if self._refs[blk] > 1:
                self._refs[blk] -= 1
                continue
            del self._refs[blk]
            if self.release_hook is not None and self.release_hook(blk):
                continue
            self._free.append(blk)
            self._free_set.add(blk)

    def release_to_free(self, blk: int):
        """Return a cache-held block (refcount 0) to the free list —
        the prefix cache's eviction path."""
        if blk in self._free_set or blk in self._refs:
            raise ValueError(
                f"release_to_free of block {blk} that is not "
                f"cache-held")
        self._free.append(blk)
        self._free_set.add(blk)

    @staticmethod
    def blocks_for(num_tokens: int, block_size: int) -> int:
        return -(-max(num_tokens, 1) // block_size)
