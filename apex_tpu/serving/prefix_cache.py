"""Block-level prefix cache — RadixAttention's sharing, hash-chained.

Shared-prefix traffic (system prompts, few-shot templates, multi-turn
chat) re-prefills the same tokens for every request; SGLang's
RadixAttention observation is that a block-granular KV cache already
holds everything needed to skip that work — the only missing piece is
an INDEX from token content to physical blocks.  This module is that
index:

- the unit of sharing is one FULL block (``block_size`` tokens): a
  partial block is still being written and can never be shared;
- the key of block i is ``(parent physical block, tuple of its
  block_size tokens)`` — chaining on the parent's physical id makes
  the key cover the entire prefix without hashing it (two prefixes
  agreeing on blocks 0..i-1 share the same parent id by induction),
  which is a flat-dict encoding of the radix tree;
- :meth:`match` walks a new request's context down the chain and
  returns the longest cached run of full blocks with one refcount
  taken per block (``BlockAllocator.incref`` / ``adopt``);
- a block whose refcount drops to zero is NOT freed if registered
  here: the allocator's ``release_hook`` parks it in an LRU of
  evictable holds, so a finished request's prefix keeps serving
  matches until the pool actually needs the space;
- :meth:`evict` reclaims LRU holds for the allocator, cascading over
  registered descendants (their chain keys dangle once the parent id
  is reusable — a reused id plus equal tokens would alias a stale
  entry onto garbage).

The cache never touches device memory: like the scheduler it is pure
host bookkeeping over block ids; the KV bytes themselves were written
by whichever request prefilled them first and are bit-identical to
what any later request would have written (same tokens, same absolute
positions, same jitted program).

Quantized pools (``docs/serving.md``, "Quantized KV cache") need no
special handling here: the int8 payload and its fp32 scale sidecar
are both indexed by the SAME flat slot (block * block_size + offset),
so a block id in this index names its scales too — registration,
LRU holds, adoption, eviction, and COW duplication
(``kv_cache.copy_blocks`` copies every cache leaf) all carry scales
with their blocks by construction.  Quantization is elementwise and
deterministic, so the first-writer-wins sharing argument above holds
byte-for-byte for quantized blocks as well.
"""

from __future__ import annotations

import hashlib
import time
from collections import OrderedDict
from typing import Dict, List, Optional, Set, Tuple

from apex_tpu.serving.kv_cache import BlockAllocator
from apex_tpu.serving.transport.base import TransportError
from apex_tpu.serving.offload import (
    merge_payloads,
    split_payload,
    verify_payload,
)
from apex_tpu.utils.meters import CounterMeter

# chain parent of a sequence's first block — the reserved garbage
# block's id, which is never allocated and so never collides
ROOT = 0

# chain hash of ROOT — the seed of every sequence's content-hash
# chain (serving/offload): block i's hash covers its whole prefix by
# induction, like the (parent id, chunk) key covers it by id chaining
_ROOT_HASH = b"\x00" * 16


def _chunk_hash(parent_hash: bytes, chunk) -> bytes:
    """Content hash of a chain node: ``blake2b(parent_hash || chunk
    tokens)`` — a pure function of token content (NOT block ids), so
    it stays valid across block-id reuse and process restarts, which
    is what lets it key the offload store's host/disk tiers."""
    h = hashlib.blake2b(parent_hash, digest_size=16)
    for t in chunk:
        h.update(int(t).to_bytes(8, "little", signed=True))
    return h.digest()


class PrefixCache:
    """Content -> physical-block index over a :class:`BlockAllocator`.

    Wires itself into the allocator on construction: ``release_hook``
    parks registered ref-0 blocks in the evictable LRU instead of
    freeing them, and a reset hook drops the whole index when the
    allocator resets (the ids it stored are dangling after that).

    ``counters`` (a :class:`CounterMeter`) accumulates
    ``prefix_hit_tokens`` / ``prefix_miss_tokens`` /
    ``prefix_hit_requests`` / ``prefix_miss_requests`` /
    ``prefix_evicted_blocks`` / ``prefix_cow_blocks`` — surfaced by
    ``InferenceServer.stats``.
    """

    def __init__(self, allocator: BlockAllocator, block_size: int,
                 counters: Optional[CounterMeter] = None):
        self.allocator = allocator
        self.block_size = block_size
        self.counters = counters if counters is not None else CounterMeter()
        self._map: Dict[Tuple[int, tuple], int] = {}   # key -> block
        self._key_of: Dict[int, Tuple[int, tuple]] = {}
        self._children: Dict[int, Set[int]] = {}       # block -> blocks
        self._lru: "OrderedDict[int, None]" = OrderedDict()  # evictable
        self.evictable_peak = 0     # high-watermark of LRU holds
        # hierarchical offload (serving/offload; attached by the
        # server when enable_kv_offload= is on): chain content hashes
        # per registered block, the store, and the engine's
        # export/import closures — all None when offload is off, and
        # every offload branch below guards on the store
        self._hash_of: Dict[int, bytes] = {}
        self._demote_pending: List[Tuple[int, bytes]] = []
        self._offload = None
        self._exporter = None
        self._importer = None
        self._off_counters: Optional[CounterMeter] = None
        self._promote_hist = None
        self._clock = time.monotonic
        allocator.release_hook = self._on_release
        allocator.reset_hooks.append(self.clear)

    def attach_offload(self, store, exporter, importer, *,
                       counters: Optional[CounterMeter] = None,
                       promote_hist=None, clock=None) -> None:
        """Wire the host/disk offload tiers in (docs/serving.md,
        "Hierarchical KV offload").  ``exporter`` / ``importer`` are
        the cache-home engine's ``export_blocks`` / ``import_blocks``
        (as closures, so chaos wrappers installed later still
        intercept); must be attached before any block registers —
        chain hashes are computed at registration time."""
        if self._key_of:
            raise RuntimeError(
                "attach_offload must run before any block registers "
                "(chain hashes are computed at registration)")
        self._offload = store
        self._exporter = exporter
        self._importer = importer
        self._off_counters = (counters if counters is not None
                              else CounterMeter())
        self._promote_hist = promote_hist
        if clock is not None:
            self._clock = clock

    # -- allocator hooks --------------------------------------------------

    def _on_release(self, blk: int) -> bool:
        """Refcount hit zero: keep registered blocks as evictable LRU
        holds (newest at the back); unregistered blocks go free."""
        if blk in self._key_of:
            self._lru[blk] = None
            if len(self._lru) > self.evictable_peak:
                self.evictable_peak = len(self._lru)
            return True
        return False

    def clear(self):
        """Drop the whole index (allocator reset — every stored id is
        dangling)."""
        self._map.clear()
        self._key_of.clear()
        self._children.clear()
        self._lru.clear()
        self._hash_of.clear()
        # dropped, not demoted: a reset means every stored id is
        # dangling, so there is nothing coherent left to export
        self._demote_pending.clear()
        self.evictable_peak = 0

    # -- introspection ----------------------------------------------------

    @property
    def num_cached_blocks(self) -> int:
        """Registered blocks (shared-or-shareable index size)."""
        return len(self._key_of)

    @property
    def num_evictable(self) -> int:
        """Ref-0 holds reclaimable by :meth:`evict`."""
        return len(self._lru)

    def held_blocks(self) -> Set[int]:
        return set(self._lru)

    def is_registered(self, blk: int) -> bool:
        return blk in self._key_of

    # -- the index --------------------------------------------------------

    def match(self, tokens: List[int]) -> List[int]:
        """Longest cached run of ``tokens``' full-block chunks, as
        physical block ids with one ref taken per block (LRU holds are
        reactivated out of the evictable set).  The caller either
        commits the blocks into a table or returns them via
        :meth:`cancel` — never both."""
        bs = self.block_size
        out: List[int] = []
        parent = ROOT
        for i in range(len(tokens) // bs):
            blk = self._map.get((parent, tuple(tokens[i * bs:(i + 1) * bs])))
            if blk is None:
                break
            if blk in self._lru:
                del self._lru[blk]
                self.allocator.adopt(blk)
            else:
                self.allocator.incref([blk])
            out.append(blk)
            parent = blk
        return out

    def cancel(self, blocks: List[int]):
        """Undo :meth:`match`'s refs for an admission that didn't go
        through (registered blocks drop back into the LRU via the
        release hook)."""
        self.allocator.free(blocks)

    def register(self, parent: int, chunk: Tuple[int, ...],
                 blk: int) -> bool:
        """Index the full block ``blk`` holding ``chunk`` under its
        chain ``parent``.  First registration wins: if the key already
        maps to ANOTHER block (two requests prefilled the same content
        independently) the existing entry stays and this block remains
        private — the caller must then stop registering descendants,
        whose chain would dangle off an unindexed id.  Returns whether
        ``blk`` is the indexed block for this key."""
        if len(chunk) != self.block_size:
            raise ValueError(
                f"register needs a full block of {self.block_size} "
                f"tokens; got {len(chunk)}")
        key = (parent, tuple(chunk))
        cur = self._map.get(key)
        if cur is not None:
            return cur == blk
        if blk in self._key_of:
            # same block under two keys would corrupt eviction; keep
            # the first registration
            return False
        self._map[key] = blk
        self._key_of[blk] = key
        self._children.setdefault(parent, set()).add(blk)
        if self._offload is not None:
            ph = (_ROOT_HASH if parent == ROOT
                  else self._hash_of.get(parent))
            if ph is not None:
                self._hash_of[blk] = _chunk_hash(ph, key[1])
        return True

    # -- cross-replica warm-up (serving/elastic) ---------------------------

    def export_nodes(self, max_blocks: Optional[int] = None
                     ) -> List[Tuple[int, Tuple[int, ...], int]]:
        """The registered radix tree as ``(parent, chunk, block)``
        rows in parent-before-child order (BFS from ``ROOT``,
        children sorted by chunk tokens — deterministic for a given
        index state).  A scale-up warms a NEW replica's cache from a
        donor with this: rows bound by ``max_blocks`` always form a
        valid tree prefix, so the importer can remap parent ids
        row-by-row and never dangles a chain."""
        budget = (len(self._key_of) if max_blocks is None
                  else max(0, int(max_blocks)))
        out: List[Tuple[int, Tuple[int, ...], int]] = []
        frontier = [ROOT]
        while frontier and len(out) < budget:
            nxt: List[int] = []
            for parent in frontier:
                for blk in sorted(
                        self._children.get(parent, ()),
                        key=lambda b: self._key_of[b][1]):
                    if len(out) >= budget:
                        return out
                    out.append((parent, self._key_of[blk][1], blk))
                    nxt.append(blk)
            frontier = nxt
        return out

    def seed_nodes(self, nodes, id_map: Dict[int, int]) -> int:
        """Register imported donor nodes under THIS cache's block ids
        and park them as evictable LRU holds.  ``nodes`` is a donor
        :meth:`export_nodes` listing; ``id_map`` maps donor block id
        -> local block id (freshly allocated, refcount 1, KV bytes
        already imported via the checksummed ``import_blocks`` path).
        A node whose key is already taken (or whose parent failed to
        seed) frees its local block back to the pool.  Returns how
        many blocks were seeded."""
        seeded = 0
        for parent, chunk, src_blk in nodes:
            dst = id_map[src_blk]
            dst_parent = ROOT if parent == ROOT \
                else id_map.get(parent, -1)
            ok = False
            if dst_parent != -1 and (dst_parent == ROOT
                                     or dst_parent in self._key_of):
                ok = self.register(dst_parent, tuple(chunk), dst)
            if ok:
                seeded += 1
                # drop our alloc ref: the release hook parks the
                # registered block in the evictable LRU — warm, free
                # to reclaim, exactly like a finished request's prefix
                self.allocator.free([dst])
            else:
                del id_map[src_blk]     # descendants must not chain
                self.allocator.free([dst])  # unregistered -> free list
        return seeded

    # -- promotion (serving/offload) ---------------------------------------

    def promote(self, tokens: List[int], matched: List[int],
                alloc_fn) -> int:
        """Extend a :meth:`match` run with blocks re-materialized
        from the offload store — the host/disk -> device tier
        crossing, called by the scheduler at admission time right
        after the device-tier walk stops.  Continues the radix walk
        by CONTENT hash: each missing chunk's chain hash is probed in
        the store, imported through the checksummed ``import_blocks``
        path into a fresh device block (``alloc_fn``, the scheduler's
        evicting allocator — colder LRU holds may demote to make
        room), registered, and appended to ``matched`` with the same
        one-ref-per-block contract :meth:`match` gives.

        Every failure mode degrades to cold prefill, never to wrong
        output: a store miss or full pool stops the walk; a checksum
        reject discards the corrupt payload whole (``crc_rejects``);
        a transient import OOM puts every payload back for next time
        (``capacity_skips``).  Returns how many blocks promoted.

        The walk is two-staged for dispatch economy: stage 1 probes /
        integrity-checks / allocates per chunk host-side (crc32 over a
        few KB each — the torn-spill reject happens HERE, before any
        device or radix state moves), stage 2 scatters the whole
        collected run through ONE batched ``import_blocks`` launch —
        a 20-block promote costs one device dispatch, not 20."""
        if self._offload is None:
            return 0
        bs = self.block_size
        total = len(tokens) // bs
        if len(matched) >= total:
            return 0
        parent = matched[-1] if matched else ROOT
        ph = (_ROOT_HASH if parent == ROOT
              else self._hash_of.get(parent))
        if ph is None:
            return 0
        t0 = self._clock()
        # -- stage 1: walk the chain, collect verified payloads ------
        pending = []            # (hash, chunk, payload, tier)
        for i in range(len(matched), total):
            chunk = tuple(tokens[i * bs:(i + 1) * bs])
            h = _chunk_hash(ph, chunk)
            hit = self._offload.take(h)
            if hit is None:
                break
            payload, tier = hit
            try:
                verify_payload(payload)
            except ValueError:
                # checksum reject: the payload is corrupt — discard
                # it WHOLE (re-storing it would re-fail forever) and
                # fall back to cold prefill, bit-identically
                self._off_counters.incr("crc_rejects")
                break
            pending.append((h, chunk, payload, tier))
            ph = h
        if not pending:
            return 0
        # -- stage 2: one bulk alloc (one batched demote-eviction on
        # the way, when the pool is tight), one batched import ------
        fresh = alloc_fn(len(pending))
        if fresh is None:
            # pool dry even after eviction: keep the payloads warm
            # for a later admission, cold-prefill this one
            for h, _, payload, _ in pending:
                self._offload.put(h, payload)
            self._off_counters.incr("capacity_skips")
            return 0
        try:
            self._importer(fresh, merge_payloads(
                [p[2] for p in pending]))
        except MemoryError:
            # transient device OOM mid-import: the payloads are still
            # good — put them all back and retry next admission
            self.allocator.free(fresh)
            for h, _, payload, _ in pending:
                self._offload.put(h, payload)
            self._off_counters.incr("capacity_skips")
            return 0
        except ValueError:
            # belt-and-braces: stage 1 already verified the stored
            # checksums, so a reject here means the bytes rotted
            # in-flight — discard, cold-prefill
            self.allocator.free(fresh)
            self._off_counters.incr("crc_rejects")
            return 0
        except TransportError:
            # the transport exhausted its envelope (retries, deadline,
            # or an open breaker): the payloads are still good — put
            # them back for a later admission and cold-prefill this
            # one, exactly like the capacity path
            self.allocator.free(fresh)
            for h, _, payload, _ in pending:
                self._offload.put(h, payload)
            self._off_counters.incr("transport_skips")
            return 0
        promoted = 0
        parent = matched[-1] if matched else ROOT
        for j, (_, chunk, _, tier) in enumerate(pending):
            blk = fresh[j]
            if not self.register(parent, chunk, blk):
                # cannot happen on a single-threaded walk (the chain
                # was missing moments ago), but never leak: free this
                # block and every unregistered one behind it
                self.allocator.free(fresh[j:])
                break
            matched.append(blk)
            self._off_counters.incr(
                "promotes_host" if tier == "host" else "promotes_disk")
            promoted += 1
            parent = blk
        if promoted and self._promote_hist is not None:
            self._promote_hist.record(self._clock() - t0)
        return promoted

    # -- eviction ---------------------------------------------------------

    def evict(self, n: int = 1) -> int:
        """Reclaim at least ``n`` blocks from the evictable LRU
        (oldest first) back to the allocator's free list, cascading
        each victim's registered subtree.  Returns how many blocks
        actually freed (0 = nothing evictable)."""
        freed = 0
        while freed < n and self._lru:
            blk = next(iter(self._lru))
            freed += self._evict_subtree(blk)
        self._flush_demotes()
        if freed:
            self.counters.incr("prefix_evicted_blocks", freed)
        return freed

    def _evict_subtree(self, blk: int) -> int:
        """Unregister ``blk`` and every registered descendant; free the
        ones sitting in the LRU (a descendant still referenced by a
        live table merely loses shareability)."""
        freed = 0
        # children before their parent, without recursion: a prompt of
        # 16k tokens is a chain of a thousand blocks, deeper than the
        # interpreter's stack allows
        stack = [(blk, False)]
        while stack:
            blk, children_done = stack.pop()
            if not children_done:
                stack.append((blk, True))
                stack.extend((child, False) for child in reversed(
                    list(self._children.get(blk, ()))))
                continue
            h = self._hash_of.get(blk)    # before _unregister drops it
            self._unregister(blk)
            if blk in self._lru:
                del self._lru[blk]
                if self._offload is not None and h is not None:
                    self._demote_pending.append((blk, h))
                self.allocator.release_to_free(blk)
                freed += 1
        return freed

    def _flush_demotes(self) -> None:
        """Export every block the eviction pass just victimized into
        the offload store in ONE batched device gather — the device
        -> host tier crossing (docs/serving.md, "Hierarchical KV
        offload").  Safe after ``release_to_free``: freed slots'
        KV bytes stay untouched until an engine call re-writes them,
        and the flush runs before :meth:`evict` returns the ids to
        the allocator's caller.  Each block is stored under its own
        content hash with the crc the engine recorded for it
        (``offload.split_payload``).  A transient export OOM drops
        the whole batch (the blocks die exactly as they did before
        offload existed — never an error path)."""
        pending, self._demote_pending = self._demote_pending, []
        if not pending:
            return
        try:
            payload = self._exporter([blk for blk, _ in pending])
        except MemoryError:
            self._off_counters.incr("demote_failed", len(pending))
            return
        for (_, h), sub in zip(pending, split_payload(payload)):
            self._offload.put(h, sub)
        self._off_counters.incr("demotes", len(pending))

    def _unregister(self, blk: int):
        self._hash_of.pop(blk, None)
        key = self._key_of.pop(blk, None)
        if key is None:
            return
        del self._map[key]
        kids = self._children.get(key[0])
        if kids is not None:
            kids.discard(blk)
            if not kids:
                del self._children[key[0]]
        self._children.pop(blk, None)

    # -- invariants (tests + bench) ---------------------------------------

    def audit(self):
        """Index consistency: map/key_of are inverse bijections, chain
        parents are indexed (or ROOT), LRU holds are registered and
        ref-0, and no registered block is on the free list."""
        assert len(self._map) == len(self._key_of)
        for key, blk in self._map.items():
            assert self._key_of.get(blk) == key
            parent = key[0]
            assert parent == ROOT or parent in self._key_of, \
                f"block {blk} chained to unindexed parent {parent}"
            assert blk in self._children.get(parent, ()), \
                f"block {blk} missing from parent {parent}'s children"
        for blk in self._lru:
            assert blk in self._key_of, f"unregistered LRU hold {blk}"
            assert self.allocator.refs(blk) == 0, \
                f"LRU hold {blk} has refs {self.allocator.refs(blk)}"
        for blk in self._key_of:
            assert blk not in self.allocator._free_set, \
                f"registered block {blk} is on the free list"
        for blk in self._hash_of:
            assert blk in self._key_of, \
                f"chain hash held for unregistered block {blk}"
        assert not self._demote_pending, \
            "demote batch not flushed by the evict pass"
