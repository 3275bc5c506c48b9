"""Pytree flatten/unflatten into contiguous 1-D buffers.

TPU-native equivalent of the reference's ``apex_C`` C++ extension
(``csrc/flatten_unflatten.cpp:5-17`` wrapping
``torch::utils::flatten_dense_tensors``), used there by DDP bucketing
(``apex/parallel/distributed.py:13-33``) and by the flat-master
``FP16_Optimizer`` (``apex/optimizers/fp16_optimizer.py:61-67``).

Here flattening serves the fused optimizers: a whole parameter pytree becomes
one (or a few, per-dtype) contiguous 1-D buffers so a single Pallas kernel
can update every parameter in one launch.
"""

from __future__ import annotations

from typing import Any, NamedTuple, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from apex_tpu.ops.pallas_utils import gspmd_auto_axes

Pytree = Any


class FlatSpec(NamedTuple):
    """Static metadata needed to invert :func:`flatten`.

    ``perm``/``group_bounds`` support grouped layouts (param groups): the
    buffer holds leaves in ``perm`` order so that each group occupies one
    contiguous ``(start, size)`` slice.  Empty perm = tree order, one
    implicit group.
    """

    treedef: Any
    shapes: Tuple[Tuple[int, ...], ...]
    dtypes: Tuple[Any, ...]
    offsets: Tuple[int, ...]  # start offset of each leaf in the flat buffer
    total: int
    perm: Tuple[int, ...] = ()                      # buffer order of leaves
    group_bounds: Tuple[Tuple[int, int], ...] = ()  # (start, size) per group


def _spec_for(leaves: Sequence[jax.Array]) -> Tuple[tuple, list, tuple]:
    shapes = tuple(tuple(x.shape) for x in leaves)
    sizes = [int(np.prod(s)) if s else 1 for s in shapes]
    offsets = tuple(int(o) for o in np.cumsum([0] + sizes[:-1]))
    return shapes, sizes, offsets


def _pad_flat(flat: jax.Array, pad_to: int) -> jax.Array:
    """Zero-pad a 1-D buffer so its length is a multiple of ``pad_to``
    (makes the buffer evenly shardable across mesh axes whose size
    divides ``pad_to`` — the ZeRO-1 layout, ``parallel.zero``)."""
    if pad_to > 1 and flat.shape[0] % pad_to:
        extra = pad_to - flat.shape[0] % pad_to
        flat = jnp.concatenate([flat, jnp.zeros((extra,), flat.dtype)])
    return flat


def flatten(tree: Pytree, dtype=None, pad_to: int = 1):
    """Concatenate all leaves of ``tree`` into one 1-D array.

    Returns ``(flat, spec)``. If ``dtype`` is None the leaves are cast to the
    widest leaf dtype (mirroring apex's requirement that flattened lists are
    same-dtype — ``split_half_float_double`` at ``distributed.py:51`` exists
    precisely because torch's flatten can't mix; here we just promote).
    ``pad_to``: zero-pad the buffer length to a multiple (``spec.total``
    stays the logical element count; :func:`unflatten` ignores the tail).
    """
    leaves, treedef = jax.tree_util.tree_flatten(tree)
    if not leaves:
        return jnp.zeros((0,), dtype or jnp.float32), FlatSpec(treedef, (), (), (), 0)
    if dtype is None:
        dtype = jnp.result_type(*[x.dtype for x in leaves])
    shapes, sizes, offsets = _spec_for(leaves)
    flat = _pad_flat(
        jnp.concatenate([x.astype(dtype).reshape(-1) for x in leaves]),
        pad_to)
    spec = FlatSpec(treedef, shapes, tuple(x.dtype for x in leaves), offsets,
                    int(sum(sizes)))
    return flat, spec


def flatten_grouped(tree: Pytree, group_ids: Sequence[int], dtype=None,
                    pad_to: int = 1):
    """Like :func:`flatten`, but lay the buffer out group-by-group so each
    group is one contiguous slice (see ``FlatSpec.perm``/``group_bounds``).

    ``group_ids``: group index per leaf in tree-flatten order; groups are
    numbered 0..max contiguously.
    """
    leaves, treedef = jax.tree_util.tree_flatten(tree)
    assert len(group_ids) == len(leaves), (len(group_ids), len(leaves))
    if not leaves:
        return jnp.zeros((0,), dtype or jnp.float32), FlatSpec(
            treedef, (), (), (), 0, (), ())
    if dtype is None:
        dtype = jnp.result_type(*[x.dtype for x in leaves])
    n_groups = max(group_ids) + 1
    perm = tuple(sorted(range(len(leaves)),
                        key=lambda i: (group_ids[i], i)))
    shapes = tuple(tuple(x.shape) for x in leaves)
    sizes = [int(np.prod(s)) if s else 1 for s in shapes]
    # offsets indexed by tree position, laid out in perm order
    offsets = [0] * len(leaves)
    group_bounds = []
    cursor = 0
    for g in range(n_groups):
        start = cursor
        for i in perm:
            if group_ids[i] == g:
                offsets[i] = cursor
                cursor += sizes[i]
        group_bounds.append((start, cursor - start))
    flat = _pad_flat(jnp.concatenate(
        [leaves[i].astype(dtype).reshape(-1) for i in perm]), pad_to)
    spec = FlatSpec(treedef, shapes, tuple(x.dtype for x in leaves),
                    tuple(offsets), cursor, perm, tuple(group_bounds))
    return flat, spec


def flatten_like(tree: Pytree, spec: FlatSpec, dtype=None,
                 pad_to: int = 1) -> jax.Array:
    """Flatten ``tree`` (matching ``spec``'s structure) without rebuilding
    spec, honoring the spec's (possibly grouped) buffer layout.

    This is the gather a flat optimizer step makes of its parameters and
    of its gradients every step, so it is ONE buffer of the final length
    (``spec.total`` rounded up to ``pad_to``), each leaf written into it
    at ``spec.offsets`` by ``dynamic_update_slice``: the updates are in
    place, so the buffer is written once to initialise it and once by
    the leaves.  A ``concatenate`` of 388 leaves compiles on TPU to two
    half buffers and a pass that joins them (PERF.md, PR 30: 4.5 ms a
    step more at GPT-2 medium's 354.8M floats, and twice the
    temporaries).

    Where the SPMD partitioner owns mesh axes the ``concatenate`` stays:
    through a chain of updates it carries a sharded consumer's placement
    (ZeRO-1's flat state) back into every leaf, and so into the backward
    pass that made the gradients.
    """
    leaves = jax.tree_util.tree_leaves(tree)
    if not leaves:
        return jnp.zeros((0,), dtype or jnp.float32)
    if dtype is None:
        dtype = jnp.result_type(*[x.dtype for x in leaves])
    if gspmd_auto_axes():
        if spec.perm:
            leaves = [leaves[i] for i in spec.perm]
        return _pad_flat(
            jnp.concatenate([x.astype(dtype).reshape(-1) for x in leaves]),
            pad_to)
    flat = jnp.zeros((-(-spec.total // pad_to) * pad_to,), dtype)
    for x, off in zip(leaves, spec.offsets):
        flat = jax.lax.dynamic_update_slice(
            flat, x.astype(dtype).reshape(-1), (off,))
    return flat


def unflatten(flat: jax.Array, spec: FlatSpec, *, cast_back: bool = True) -> Pytree:
    """Invert :func:`flatten`: slice ``flat`` back into the original pytree.

    ``cast_back=False`` keeps the flat buffer's dtype (used when the flat
    buffer holds fp32 master values for bf16 model params).

    The leaves are cut (and cast) first, as 1-D pieces, and reshaped only
    behind one ``optimization_barrier``: written ``slice(...).reshape(shape)``
    XLA:TPU moves the reshape above the slice and relays out the WHOLE
    buffer once for every family of leaf shapes before cutting from the
    copies (three passes over GPT-2 medium's 1.42 GB a step; PERF.md,
    PR 30).  Behind the barrier each leaf is relaid out on its own.
    """
    pieces = []
    for shape, dt, off in zip(spec.shapes, spec.dtypes, spec.offsets):
        size = int(np.prod(shape)) if shape else 1
        piece = jax.lax.slice_in_dim(flat, off, off + size)
        pieces.append(piece.astype(dt) if cast_back else piece)
    pieces = jax.lax.optimization_barrier(pieces)
    leaves = [x.reshape(shape) for x, shape in zip(pieces, spec.shapes)]
    return jax.tree_util.tree_unflatten(spec.treedef, leaves)
