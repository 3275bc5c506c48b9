"""Shared helpers for Pallas TPU kernels: platform probing and tile sizes."""

from __future__ import annotations

import warnings

import jax
from jax._src import mesh as _mesh_lib
from jax.sharding import AxisType

# VPU lane width; last dim of every tile must be 128.
LANES = 128
# rows of one float32 vector register: a block's second-to-last dim is a
# multiple of 8 or the array's own.
SUBLANES = 8
# Default sublane rows per program for elementwise kernels: 512 rows x 128
# lanes x 4 B = 256 KiB per fp32 buffer, comfortably inside 16 MB VMEM even
# with several operands.
DEFAULT_ROWS = 512


def unpatched(fn):
    """Return the pre-amp-O1 original of a possibly-patched function.

    ``amp.patch`` installs trace-time precision wrappers on ``jnp``
    namespaces (O1 op policy).  Library internals that upcast to fp32 ON
    PURPOSE (flash-attention oracle scores, ring-attention accumulation)
    must call through this so the O1 half-list patch cannot silently
    downcast their operands — the analog of the reference keeping raw
    function handles in ``utils.get_func`` (apex/amp/utils.py:131-158)."""
    return getattr(fn, "__amp_original__", fn)


def union_vma(*xs):
    """Union of the operands' varying mesh axes (``shard_map``'s vma
    typing).  A ``pallas_call``'s ``out_shape`` must declare it
    explicitly under the default ``check_vma=True``; outside
    ``shard_map`` every set is empty."""
    return frozenset().union(*(jax.typeof(x).vma for x in xs))


def on_tpu() -> bool:
    """True when the default backend is a TPU.  No ``except``: a
    backend that fails to start raises here instead of reading as "not
    a TPU" and sending every kernel to interpret mode or its jnp
    reference."""
    return jax.devices()[0].platform == "tpu"


def require_tpu():
    """The first device, for entry points that measure on a chip:
    ``SystemExit`` naming what was found when it is not a TPU.  They
    do not fall back to the CPU."""
    dev = jax.devices()[0]
    if dev.platform != "tpu":
        raise SystemExit(
            f"no TPU: jax.devices()[0] is platform {dev.platform!r} "
            f"({dev.device_kind}); this entry point runs on a chip and "
            "has no CPU fallback")
    return dev


def gspmd_auto_axes():
    """Names of the mesh axes the SPMD partitioner owns at this point
    of the trace (empty tuple, so false, when there are none).

    Two regimes put a Mosaic call in the partitioner's hands, and its
    lowering refuses both ("Mosaic kernels cannot be automatically
    partitioned. Please wrap the call in a shard_map."):

    - an abstract mesh with a non-Manual axis: ``jax.set_mesh`` scopes,
      and partial-manual ``shard_map`` regions (pipelined Megatron TP:
      the model axis stays automatic so XLA inserts the TP collectives);
    - a legacy ``with mesh:`` scope of more than one device, which
      leaves the abstract mesh empty and shards a ``jax.jit`` by its
      arguments' placements (caught on the four-chip v5e host, PR 21:
      the GPT data-parallel step could not lower).

    Fully-manual ``shard_map`` regions (every axis Manual: DDP, ZeRO
    ``with_zero``, ring/Ulysses SP) keep the real kernels whatever
    scope encloses them.  A jit sharded only by its arguments, under no
    mesh scope at all, cannot be seen from inside the trace; there the
    lowering error itself is the report."""
    am = jax.sharding.get_abstract_mesh()
    if am.axis_names:
        owned = tuple(n for n, t in zip(am.axis_names, am.axis_types)
                      if t != AxisType.Manual)
        # a partial-manual region is refused whatever the sizes; a mesh
        # with no manual axis only once it spans several devices
        partial = len(owned) < len(am.axis_names)
        return owned if partial or am.size > 1 else ()
    legacy = _mesh_lib.thread_resources.env.physical_mesh
    if not legacy.empty and legacy.size > 1:
        return tuple(legacy.axis_names)
    return ()


_warned_auto_downgrade = False


def pallas_auto_gate(flag=None) -> bool:
    """The ONE resolution of every kernel's ``use_pallas=None`` default:
    real kernels on TPU, except where the SPMD partitioner owns the op
    and lowering must refuse a Mosaic call (:func:`gspmd_auto_axes`).
    An explicit ``flag`` always wins.

    The TPU-but-downgraded case warns ONCE per process, naming the
    mesh axes that triggered it: users otherwise read full-kernel
    throughput numbers off a silently jnp-referenced hot path (ADVICE
    round 5).  To keep the kernels on several chips, run the step (or
    the call) inside a fully-manual ``jax.shard_map`` over the mesh, as
    ``examples/gpt/main_amp.py`` does for data parallelism."""
    if flag is not None:
        return flag
    if not on_tpu():
        return False
    axes = gspmd_auto_axes()
    if axes:
        global _warned_auto_downgrade
        if not _warned_auto_downgrade:
            _warned_auto_downgrade = True
            warnings.warn(
                "pallas_auto_gate: on TPU but traced where the SPMD "
                f"partitioner owns mesh axes {axes} - it rejects Mosaic "
                "custom calls, so Pallas kernels are rerouted to their "
                "jnp reference paths for this and every later call in "
                "such regions (warned once).  Wrap the step in a "
                "fully-manual jax.shard_map to keep the kernels.",
                RuntimeWarning, stacklevel=3)
        return False
    return True
