"""Grouped collective primitives for process-group code (SyncBN groups,
grouped DDP).

One formulation on every backend, the one that traces inside a default
(``check_vma=True``) ``shard_map``: ``lax.all_gather`` takes
``axis_index_groups`` there, ``lax.psum`` does not (it raises
``NotImplementedError`` at trace time), so the grouped sum is a grouped
gather followed by a local sum.

Group partitions must be equal-sized (guaranteed by
``create_process_group``).
"""

from __future__ import annotations

from typing import Optional, Sequence

import jax
import jax.numpy as jnp
from jax import lax


def vary_like(x, *refs, extra_axes=()):
    """Broadcast ``x``'s varying-axes type to the union of ``refs``' (plus
    ``extra_axes``, e.g. a ring axis that ppermute will introduce) —
    needed so lax.cond/scan branches built from constants type-check
    under shard_map's vma tracking. No-op outside shard_map."""
    target = set(extra_axes)
    for r in refs:
        target |= set(jax.typeof(r).vma)
    missing = tuple(sorted(target - set(jax.typeof(x).vma)))
    return lax.pcast(x, missing, to="varying") if missing else x


def psum_g(x, axis_name: str, groups: Optional[Sequence[Sequence[int]]] = None):
    """psum over the axis, or within equal-sized groups of it."""
    if groups is None:
        return lax.psum(x, axis_name)
    return jnp.sum(lax.all_gather(x, axis_name, axis_index_groups=groups),
                   axis=0)


def pmean_g(x, axis_name: str, groups=None):
    if groups is None:
        return lax.pmean(x, axis_name)
    return psum_g(x, axis_name, groups) / len(groups[0])


def all_gather_g(x, axis_name: str, groups=None, *, axis: int = 0,
                 tiled: bool = False):
    """all_gather over the axis or within groups; group results stack the
    group's members in group order."""
    return lax.all_gather(x, axis_name, axis=axis, tiled=tiled,
                          axis_index_groups=groups)
