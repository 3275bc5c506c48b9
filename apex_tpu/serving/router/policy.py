"""Placement policy for the multi-replica router — who serves this
prompt?

Two signals compose (``docs/serving.md``, "Multi-replica routing"):

**Least pressure.**  Every replica already publishes the PR-5 overload
signal — ``Scheduler.pressure()``, the max of queue fill and
(live blocks + queued demand) / usable blocks — so the balanced
default is simply "place on the replica under the least pressure",
ties toward the lowest index (deterministic, so tests and the chaos
replay never depend on dict order).

**Prefix affinity.**  Shared-prefix traffic (system prompts, few-shot
templates, multi-turn sessions) only profits from a replica's prefix
cache if it keeps LANDING on that replica — spraying a session across
the fleet re-prefills the shared blocks N times and caches them N
times.  The router keeps its own radix index over SUBMITTED prompts
(the same hash-chained full-chunk encoding as
:mod:`serving.prefix_cache`, but mapping token content -> replica
instead of -> physical block): a new prompt walks the chain, and the
deepest match votes for the replica that last served that prefix.
Affinity is a hint, never a mandate — it YIELDS to pressure (a match
whose replica sits above ``spill_threshold`` spills to least-pressure
rather than pile onto a hot spot) and to health (dead or draining
replicas are skipped).

The index is bounded (``max_entries``) with LRU eviction cascading
over chain descendants — a dangling parent must take its children
with it, exactly the :class:`~serving.prefix_cache.PrefixCache`
eviction rule, because a child key embeds its parent's node id.

``kind="random"`` (seeded) is the control arm: the A/B that proves
affinity actually concentrates cache hits is affinity-vs-random on
identical shared-prefix traffic.
"""

from __future__ import annotations

import dataclasses
from collections import OrderedDict
from typing import Dict, List, Optional, Set, Tuple

__all__ = ["AffinityIndex", "RouterPolicy"]

# chain parent of a prompt's first chunk (mirrors prefix_cache.ROOT)
_ROOT = 0


@dataclasses.dataclass
class RouterPolicy:
    """Knobs for :meth:`ReplicaRouter.place` (``serving.router``).

    Args:
      kind: ``"affinity"`` (the default: radix-affinity overriding
        least-pressure), ``"least_pressure"`` (balancing only), or
        ``"random"`` (seeded uniform — the bench control arm).
      spill_threshold: affinity yields when the matched replica's
        ``pressure()`` is at or above this — the point where piling
        more shared-prefix work onto the cache-warm replica costs
        more in queueing than the cache hit saves.  The PR-5 pressure
        signal may exceed 1.0 (queued demand counts), so 0.9 means
        "nearly full, counting what's already queued".
      affinity_block: tokens per index chunk.  Match granularity is
        one chunk; the natural value is the replicas' KV block size
        (the fleet defaults it there) so router-side matches predict
        replica-side cache hits one-to-one.
      max_entries: affinity-index bound; least-recently-touched chains
        evict first (cascading over descendants).
      seed: the ``"random"`` kind's RNG seed (deterministic benches).
      disagg_prefill_threshold: prompts at or above this token count
        route to a PREFILL-role replica when the fleet has one alive
        (``docs/serving.md``, "Disaggregated prefill/decode") — the
        prefill replica runs the prompt and ships the KV blocks to a
        decode replica.  ``None`` (default) disables phase-aware
        placement; short prompts always place monolithically (a
        cross-replica hand-off costs more than a short prefill).
    """

    kind: str = "affinity"
    spill_threshold: float = 0.9
    affinity_block: int = 16
    max_entries: int = 8192
    seed: int = 0
    disagg_prefill_threshold: Optional[int] = None

    def __post_init__(self):
        if self.kind not in ("affinity", "least_pressure", "random"):
            raise ValueError(
                f"unknown placement kind {self.kind!r} (expected "
                f"'affinity', 'least_pressure', or 'random')")
        if self.disagg_prefill_threshold is not None \
                and self.disagg_prefill_threshold < 1:
            raise ValueError(
                f"disagg_prefill_threshold must be >= 1, got "
                f"{self.disagg_prefill_threshold}")
        if self.affinity_block < 1:
            raise ValueError(
                f"affinity_block must be >= 1, got {self.affinity_block}")
        if self.max_entries < 1:
            raise ValueError(
                f"max_entries must be >= 1, got {self.max_entries}")


class AffinityIndex:
    """Token content -> replica, hash-chained over full chunks.

    The key of chunk i is ``(parent node id, tuple of its tokens)`` —
    two prompts agreeing on chunks 0..i-1 share the same parent id by
    induction, so the flat dict encodes the radix tree without hashing
    whole prefixes (the :class:`~serving.prefix_cache.PrefixCache`
    trick, host-side only: the router never sees physical blocks).

    Values are mutable replica indices: re-registering an existing
    chain under a different replica REPOINTS it (most recent placement
    wins) — after a failover or drain the next placement heals the
    index instead of chasing a dead replica forever.
    """

    def __init__(self, block: int, max_entries: int = 8192):
        self.block = int(block)
        self.max_entries = int(max_entries)
        self._next_id = 1
        # key -> [node_id, replica]; OrderedDict recency = LRU order
        self._map: "OrderedDict[Tuple[int, tuple], list]" = OrderedDict()
        self._children: Dict[int, Set[Tuple[int, tuple]]] = {}

    def __len__(self) -> int:
        return len(self._map)

    def match(self, tokens: List[int]) -> Tuple[Optional[int], int]:
        """Walk ``tokens``' full chunks down the chain; returns
        ``(replica of the deepest matched chunk, matched tokens)`` —
        ``(None, 0)`` on a cold miss.  Touches matched entries
        (LRU recency)."""
        parent, replica, matched = _ROOT, None, 0
        for i in range(len(tokens) // self.block):
            key = (parent, tuple(tokens[i * self.block:
                                        (i + 1) * self.block]))
            node = self._map.get(key)
            if node is None:
                break
            self._map.move_to_end(key)
            parent, replica = node[0], node[1]
            matched += self.block
        return replica, matched

    def record(self, tokens: List[int], replica: int) -> int:
        """Register every full chunk of ``tokens`` as served by
        ``replica`` (repointing chunks already chained elsewhere);
        returns chunks touched.  Evicts LRU chains past
        ``max_entries``."""
        parent, chunks = _ROOT, 0
        for i in range(len(tokens) // self.block):
            key = (parent, tuple(tokens[i * self.block:
                                        (i + 1) * self.block]))
            node = self._map.get(key)
            if node is None:
                node = [self._next_id, replica]
                self._next_id += 1
                self._map[key] = node
                self._children.setdefault(parent, set()).add(key)
            else:
                node[1] = replica
                self._map.move_to_end(key)
            parent = node[0]
            chunks += 1
        while len(self._map) > self.max_entries:
            oldest = next(iter(self._map))
            self._remove(oldest)
        return chunks

    def drop_replica(self, replica: int) -> int:
        """Remove every entry pointing at ``replica`` (cascading over
        descendants — a surviving child of a dropped parent would
        dangle) — called when a replica is replaced by a FRESH server
        whose cache is cold, so stale affinity stops steering traffic
        at an empty cache.  Returns entries removed."""
        doomed = [k for k, node in self._map.items()
                  if node[1] == replica]
        before = len(self._map)
        for key in doomed:
            if key in self._map:           # cascade may have taken it
                self._remove(key)
        return before - len(self._map)

    def _remove(self, key: Tuple[int, tuple]) -> None:
        node = self._map.pop(key)
        self._children.get(key[0], set()).discard(key)
        for child in list(self._children.pop(node[0], ())):
            if child in self._map:
                self._remove(child)
