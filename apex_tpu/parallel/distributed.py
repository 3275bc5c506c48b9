"""Data-parallel gradient reduction over mesh axes.

Re-design of the reference ``apex/parallel/distributed.py`` (NCCL-bucketed,
hook-overlapped ``DistributedDataParallel`` at :129 and manual ``Reducer``
at :89) for the XLA/SPMD world.

What translates and what dissolves:

- The reference's core contract — "after backward, every rank holds
  world-averaged gradients" — becomes a ``lax.psum``/``pmean`` over a mesh
  axis inside the jitted train step (``reduce_gradients`` below).
- Bucketing (``message_size``), per-param autograd hooks, the dedicated
  reduction CUDA stream, and bucket-structure broadcasts exist to overlap
  comm with compute; XLA's scheduler overlaps async collectives with the
  backward pass automatically, so none of that machinery is reproduced.
  ``delay_allreduce=True`` (reference :166, skip overlap, reduce at the
  end) is therefore the *only* behavior; the eager-overlap knobs are
  accepted and ignored for API compatibility.
- Policy knobs that change *numerics* are preserved faithfully:
  ``allreduce_always_fp32`` (cast grads to fp32 before reducing, :379),
  ``gradient_average`` (divide by world size after, :387),
  ``gradient_predivide_factor`` (divide by f before, multiply f/N after,
  :162-172).
- Parameter broadcast from rank 0 at construction (:237) becomes
  ``broadcast_params`` — under SPMD, same-seed replicated init makes it a
  no-op, but it is provided for explicitly-divergent cases (e.g. restoring
  per-host state).

Two usage styles:

1. **GSPMD (recommended)**: jit the train step over a ``Mesh`` with the
   batch sharded on the data axis and params replicated; XLA inserts the
   gradient all-reduce automatically from the loss-mean math. DDP then
   only supplies numeric policy via ``DistributedDataParallel.wrap_grads``
   applied inside ``shard_map``-free code — or nothing at all.
2. **Explicit collectives** (``shard_map``/``pmap``): call
   ``ddp.reduce_gradients(grads)`` inside the mapped function, where the
   mesh axis name is bound.
"""

from __future__ import annotations

from typing import Any, Optional, Union

import jax
import jax.numpy as jnp
from jax import lax

from apex_tpu.observability.scopes import device_scope
from apex_tpu.parallel.collectives import all_gather_g, pmean_g, psum_g
from apex_tpu.parallel.mesh import ProcessGroup

Pytree = Any


def _group(pg: Union[ProcessGroup, str, None]) -> ProcessGroup:
    if pg is None:
        return ProcessGroup()
    if isinstance(pg, str):
        return ProcessGroup(pg)
    return pg


def all_reduce_tree(tree: Pytree, process_group=None, *, average: bool = False):
    """psum (or pmean) every leaf over the group. The TPU form of the
    reference's ``flat_dist_call([...], dist.all_reduce)`` (:70-85) — no
    flattening needed; XLA coalesces small collectives."""
    pg = _group(process_group)
    op = pmean_g if average else psum_g
    return jax.tree_util.tree_map(
        lambda x: op(x, pg.axis_name, pg.axis_index_groups), tree)


def all_gather_tree(tree: Pytree, process_group=None, *, axis: int = 0,
                    tiled: bool = False):
    """all_gather every leaf over the group (reference SyncBN stats path,
    ``optimized_sync_batchnorm_kernel.py:37-38``)."""
    pg = _group(process_group)
    return jax.tree_util.tree_map(
        lambda x: all_gather_g(x, pg.axis_name, pg.axis_index_groups,
                               axis=axis, tiled=tiled),
        tree)


def broadcast_params(params: Pytree, process_group=None, src: int = 0):
    """Make every rank's params equal to ``src``'s (reference DDP ctor
    broadcast, ``distributed.py:237``). Call inside shard_map/pmap.

    With groups, ``src`` indexes *within* each group (each group's src-th
    member broadcasts to its group), matching per-group semantics.
    """
    pg = _group(process_group)
    idx = lax.axis_index(pg.axis_name)
    if pg.axis_index_groups is None:
        src_mask = idx == src
    else:
        import numpy as np
        srcs = np.zeros((sum(len(g) for g in pg.axis_index_groups),), bool)
        for g in pg.axis_index_groups:
            srcs[g[src]] = True
        src_mask = jnp.asarray(srcs)[idx]

    def pick(x):
        masked = jnp.where(src_mask, x, jnp.zeros_like(x))
        return psum_g(masked, pg.axis_name, pg.axis_index_groups)

    return jax.tree_util.tree_map(pick, params)


class Reducer:
    """Manual gradient (or any-tensor) averaging helper — the reference's
    ``Reducer`` (:89): no hooks, user calls ``reduce()`` when ready."""

    def __init__(self, process_group=None):
        self.process_group = _group(process_group)

    def reduce(self, tree: Pytree) -> Pytree:
        return all_reduce_tree(tree, self.process_group, average=True)


class DistributedDataParallel:
    """Gradient-averaging wrapper with apex's numeric policy knobs.

    ``module`` may be a flax module, an ``amp.AmpModel``, or None (use the
    reduction API standalone). Ignored-for-compat args: ``message_size``,
    ``delay_allreduce``, ``allreduce_trigger_params``, ``shared_param``,
    ``retain_allreduce_buffers`` — overlap scheduling belongs to XLA (see
    module docstring).
    """

    def __init__(self, module=None, message_size: int = 10000000,
                 delay_allreduce: bool = False,
                 shared_param=None, allreduce_trigger_params=None,
                 retain_allreduce_buffers: bool = False,
                 allreduce_always_fp32: bool = False,
                 gradient_average: bool = True,
                 gradient_predivide_factor: float = 1.0,
                 process_group: Union[ProcessGroup, str, None] = None):
        self.module = module
        self.allreduce_always_fp32 = allreduce_always_fp32
        self.gradient_average = gradient_average
        self.gradient_predivide_factor = float(gradient_predivide_factor)
        self.process_group = _group(process_group)

    # -- model passthrough -------------------------------------------------
    def init(self, *args, **kwargs):
        return self.module.init(*args, **kwargs)

    def apply(self, *args, **kwargs):
        return self.module.apply(*args, **kwargs)

    def __call__(self, *args, **kwargs):
        return self.module(*args, **kwargs)

    @property
    def unwrapped(self):
        return self.module

    # -- the contract ------------------------------------------------------
    def reduce_gradients(self, grads: Pytree) -> Pytree:
        """World-average ``grads`` with the configured policy; call inside
        shard_map/pmap where the mesh axis is bound.

        Faithful to ``allreduce_bucket`` (reference :374-395): optional
        fp32 cast -> predivide -> all_reduce -> postdivide (by N/f when
        averaging, by 1 otherwise) -> cast back.

        vma-aware: under shard_map with varying-axis checking, JAX's
        autodiff already psums cotangents of *replicated* params, so those
        grads arrive as the global sum on every device. For such leaves the
        collective is skipped and only the averaging division is applied —
        preserving exact apex semantics ("every rank ends with the
        world-averaged gradient") in both conventions.
        """
        with device_scope("grad_exchange"):
            pg = self.process_group
            if pg.axis_index_groups is not None:
                n = len(pg.axis_index_groups[0])
            else:
                n = lax.psum(1, pg.axis_name)
            n_world = lax.psum(1, pg.axis_name)

            # vma tracking is only meaningful when shard_map's varying-axis
            # checking is on; under check_rep/check_vma=False EVERY value has
            # an empty vma set and "not in vma" would wrongly skip the psum.
            # Probe with axis_index, which is varying by construction.
            probe = lax.axis_index(pg.axis_name)
            vma_tracked = pg.axis_name in jax.typeof(probe).vma

            def one(g):
                orig_dtype = g.dtype
                if self.allreduce_always_fp32:
                    g = g.astype(jnp.float32)
                already_summed = (vma_tracked
                                  and pg.axis_name not in jax.typeof(g).vma)
                if already_summed:
                    # autodiff's implicit psum ran over the FULL axis, so the
                    # average divides by the world size — a sub-group mean is
                    # not recoverable from a world sum (grouped semantics need
                    # varying-typed grads, i.e. params passed through in_specs)
                    if self.gradient_average:
                        g = g / n_world
                else:
                    if self.gradient_predivide_factor != 1.0:
                        g = g / self.gradient_predivide_factor
                    g = psum_g(g, pg.axis_name, pg.axis_index_groups)
                    if self.gradient_average:
                        g = g * (self.gradient_predivide_factor / n)
                if self.allreduce_always_fp32:
                    g = g.astype(orig_dtype)
                return g

            return jax.tree_util.tree_map(one, grads)

    def broadcast_params(self, params: Pytree, src: int = 0) -> Pytree:
        return broadcast_params(params, self.process_group, src=src)
