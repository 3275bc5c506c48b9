"""Grouped matrix product: the routed experts' half of an expert layer.

``grouped_matmul(lhs, rhs, group_sizes)`` multiplies each contiguous
group of ``lhs`` rows with its own matrix of ``rhs``:

    out[start_g:end_g] = lhs[start_g:end_g] @ rhs[g]

with ``start_g``/``end_g`` the running sums of ``group_sizes``.  An
expert layer sorts its (token, expert) pairs by expert and calls it for
the gate, the up and the down projection (``models.deepseek``): static
shapes, no capacity, nothing dropped, and only the experts that got a
row are read from HBM.  Rows past ``sum(group_sizes)`` belong to no
group (idle slots, padding, experts held elsewhere) and come back as
zeros.

The kernel follows the published MegaBlocks scheme as
``jax.experimental.pallas.ops.tpu.megablox`` implements it, whose
group metadata it reuses: the grid walks (row tile, group) visits in
row order, a visit multiplies one ``tile_m`` x k tile of rows with one
whole k x n expert matrix and stores the rows that are the group's own,
and consecutive visits of one expert do not fetch its matrix again.  It
differs in what this layer needs: a whole expert matrix a block (2,048 x
768 in bfloat16 is 3 MB: one long DMA, no k loop, no accumulator), small
row tiles (a chunk of 256 tokens gives an expert 12 rows), and a name
of its own in a trace (``_moe_gmm_kernel``).

On the CPU, under a mesh the partitioner owns, or with
``use_pallas=False`` it is a plain loop over the groups, the parity
oracle.  Inference only: no backward pass.
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from apex_tpu.ops.pallas_utils import on_tpu, pallas_auto_gate, union_vma

KERNEL_NAME = "_moe_gmm_kernel"


def _reference(lhs, rhs, group_sizes):
    """The plain loop: every group's matrix over every row, kept for
    the rows that are the group's own; float32 accumulation; rows past
    the last group are zeros.  (``lax.ragged_dot`` would say the same in
    one call, and does on the CPU; compiled for a v5e in bfloat16 it
    came back with whole rows wrong, my chip run, PR 27, so the oracle
    is spelt out.)"""
    ends = jnp.cumsum(group_sizes.astype(jnp.int32))
    row = lax.broadcasted_iota(jnp.int32, (lhs.shape[0], 1), 0)

    def one(g, out):
        mine = (row >= ends[g] - group_sizes[g]) & (row < ends[g])
        return out + jnp.where(mine, jnp.dot(
            lhs, rhs[g], preferred_element_type=jnp.float32), 0.0)

    out = lax.fori_loop(0, rhs.shape[0], one, jnp.zeros(
        (lhs.shape[0], rhs.shape[2]), jnp.float32))
    return out.astype(lhs.dtype)


def _gmm_kernel(offsets_ref, group_ids_ref, tile_ids_ref, lhs_ref, rhs_ref,
                out_ref, *, tile_m):
    """One (row tile, group) visit: the tile's rows times the group's
    matrix, kept for the rows that are the group's own.  The output
    tile stays in VMEM over the visits that share it."""
    i = pl.program_id(0)
    group = group_ids_ref[i]
    start, end = offsets_ref[group], offsets_ref[group + 1]
    row = tile_ids_ref[i] * tile_m + lax.broadcasted_iota(
        jnp.int32, out_ref.shape, 0)
    got = lax.dot_general(lhs_ref[...], rhs_ref[...],
                          (((1,), (0,)), ((), ())),
                          preferred_element_type=jnp.float32)
    mine = jnp.logical_and(row >= start, row < end)
    out_ref[...] = jnp.where(mine, got, out_ref[...].astype(jnp.float32)
                             ).astype(out_ref.dtype)


@functools.partial(jax.jit, static_argnames=("tile_m", "interpret"))
def _gmm_pallas(lhs, rhs, group_sizes, *, tile_m, interpret):
    from jax.experimental.pallas.ops.tpu.megablox.gmm import (
        make_group_metadata,
    )

    m, k = lhs.shape
    groups, _, n = rhs.shape
    (offsets, group_ids, tile_ids), visits = make_group_metadata(
        group_sizes=group_sizes, m=m, tm=tile_m,
        start_group=jnp.int32(0), num_nonzero_groups=groups,
        visit_empty_groups=False)
    if interpret:
        # the interpreter wants a static grid; the visits past the last
        # real one repeat it, which stores the same rows again
        visits = group_ids.shape[0]
    itemsize = jnp.dtype(lhs.dtype).itemsize
    out = pl.pallas_call(
        functools.partial(_gmm_kernel, tile_m=tile_m),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=3,
            grid=(visits,),
            in_specs=[
                pl.BlockSpec((tile_m, k),
                             lambda i, off, gid, tid: (tid[i], 0)),
                pl.BlockSpec((None, k, n),
                             lambda i, off, gid, tid: (gid[i], 0, 0)),
            ],
            out_specs=pl.BlockSpec((tile_m, n),
                                   lambda i, off, gid, tid: (tid[i], 0))),
        out_shape=jax.ShapeDtypeStruct((m, n), lhs.dtype,
                                       vma=union_vma(lhs, rhs)),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",),
            # two buffers of one expert matrix, the row and output
            # tiles twice, and the float32 product
            vmem_limit_bytes=max(
                32 * 2 ** 20,
                3 * (2 * k * n + 2 * tile_m * (k + n)) * itemsize
                + 3 * tile_m * n * 4)),
        cost_estimate=pl.CostEstimate(
            flops=2 * m * k * n, transcendentals=0,
            bytes_accessed=(m * (k + n) + groups * k * n) * itemsize),
        interpret=interpret,
        name=KERNEL_NAME,
    )(offsets, group_ids, tile_ids, lhs, rhs)
    # a tile's rows past the last group were never stored
    live = lax.broadcasted_iota(jnp.int32, (m, 1), 0) < offsets[groups]
    return jnp.where(live, out, 0)


def grouped_matmul(lhs, rhs, group_sizes, *, tile_m: Optional[int] = None,
                   use_pallas: Optional[bool] = None,
                   interpret: Optional[bool] = None):
    """Each contiguous group of rows times its own matrix.

    Args:
      lhs: (m, k) rows, sorted so that group 0's come first.
      rhs: (groups, k, n), one matrix a group, in ``lhs``'s dtype.
      group_sizes: (groups,) int32 rows in each group; their sum may
        fall short of ``m``, and the rows past it come back as zeros.
      tile_m: rows a grid step holds, a multiple of the dtype's sublane
        tile (16 for bfloat16) that divides ``m``; default that tile.
      use_pallas: None = auto (:func:`pallas_utils.pallas_auto_gate`).
      interpret: Pallas interpret mode (defaults to not-on-TPU).

    Returns (m, n) in ``lhs``'s dtype, products accumulated in float32.
    """
    if lhs.ndim != 2 or rhs.ndim != 3 or rhs.shape[1] != lhs.shape[1] \
            or group_sizes.shape != (rhs.shape[0],):
        raise ValueError(
            f"grouped_matmul wants lhs (m, k), rhs (groups, k, n) and "
            f"group_sizes (groups,); got {lhs.shape}, {rhs.shape}, "
            f"{group_sizes.shape}")
    if not pallas_auto_gate(use_pallas):
        return _reference(lhs, rhs, group_sizes)
    sublanes = 8 * max(1, 4 // jnp.dtype(lhs.dtype).itemsize)
    if tile_m is None:
        tile_m = sublanes
    m = lhs.shape[0]
    if tile_m % sublanes:
        raise ValueError(f"tile_m={tile_m} is not a multiple of the "
                         f"{sublanes} sublanes of {lhs.dtype}")
    pad = -m % tile_m
    if pad:
        lhs = jnp.pad(lhs, ((0, pad), (0, 0)))
    if interpret is None:
        interpret = not on_tpu()
    out = _gmm_pallas(lhs, rhs.astype(lhs.dtype),
                      group_sizes.astype(jnp.int32), tile_m=int(tile_m),
                      interpret=bool(interpret))
    return out[:m] if pad else out
