"""The gated short convolution's memory-side work over its state ring.

A short-convolution layer (``models.lfm2_moe``) projects each token's
input to three rows ``B | C | x`` of ``W`` values, forms ``u = B * x``,
convolves ``u`` causally along the sequence with ``K`` taps of ``W``
weights each (depthwise: one weight a channel and tap) and gates the
result by ``C``:

    z_t = sum_i taps[i] * u_{t - K + 1 + i}          (u before 0 is 0)
    y_t = C_t * z_t

So what a sequence carries from one launch to the next is its last
``K - 1`` rows of ``u``, whatever its length.  The serving pool keeps
them in a ring a slot (``serving.kv_cache``, ``"conv_state"``):
``(layers, slots, R, W)`` float32, position ``p`` at ring row ``p % R``.
One call, for each row of a launch (``start`` its first fed position,
``length`` its fed tokens, the rest padding):

1. reads the ring rows of positions ``start - K + 1 .. start - 1``
   (a negative position reads as 0, so a slot handed to a new request
   needs no reset);
2. forms ``u``, applies the taps over the past and the fed rows, gates
   by ``C``;
3. writes the ``u`` of its LAST ``min(length, R)`` fed rows into the
   ring, in place.

Reading before writing is what lets a chunk of any length pass its
state to the next chunk with no host work, and a verify launch's
rejected drafts need no rollback: their rows lie at positions the next
launch does not read before it writes them, as long as a verify launch
feeds at most ``R - K + 1`` rows (``DecodeEngine`` holds it to that).
The state is float32: a prompt split into chunks anywhere carries
exactly the ``u`` rows one unsplit launch computes.

:func:`short_conv` is one Pallas kernel, ``_short_conv_kernel``, on one
TPU chip, and :func:`short_conv_reference`, the plain ``jnp`` form and
the tests' oracle, elsewhere.  The matrix products around it
(``in_proj``, ``out_proj``) stay XLA's.
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from apex_tpu.ops.pallas_utils import (
    LANES,
    SUBLANES,
    on_tpu,
    pallas_auto_gate,
    union_vma,
)

KERNEL_NAME = "_short_conv_kernel"


def _split(bcx):
    """``B``, ``C``, ``x`` (..., W) float32 from the rows ``B | C | x``."""
    w = bcx.shape[-1] // 3
    f = bcx.astype(jnp.float32)
    return f[..., :w], f[..., w:2 * w], f[..., 2 * w:]


def _ring_sources(starts, lengths, ring):
    """For each ring row ``r`` of each launch row (B, R): the fed row
    whose ``u`` it takes, the newest fed position that is ``r`` modulo
    the ring, and whether there is one (a row that is written)."""
    r = jnp.arange(ring, dtype=jnp.int32)[None, :]
    n = lengths[:, None]
    last = starts[:, None] + n - 1
    src = n - 1 - (last - r + ring) % ring
    return src, (n > 0) & (src >= 0)


def short_conv_reference(bcx, taps, state, layer, rings, starts, lengths):
    """The plain form of :func:`short_conv`, every step spelt out.

    ``bcx`` (B, S, 3W) the fed rows ``B | C | x``; ``taps`` (K, W);
    ``state`` (layers, slots, R, W) float32; ``layer`` the state row of
    this layer; ``rings`` (B,) each launch row's slot, ``starts`` (B,)
    its first fed position, ``lengths`` (B,) its fed tokens (0: idle).
    Returns ``(y (B, S, W) in bcx's dtype, state)``."""
    b_, c_, x_ = _split(bcx)
    s = bcx.shape[1]
    keep, ring = taps.shape[0] - 1, state.shape[2]
    u = b_ * x_
    mine = state[layer][rings]                          # (B, R, W)
    past = starts[:, None] - keep + jnp.arange(keep, dtype=jnp.int32)
    old = jnp.take_along_axis(mine, (past % ring)[..., None], axis=1)
    ext = jnp.concatenate([jnp.where(past[..., None] >= 0, old, 0.0), u],
                          axis=1)                       # (B, keep + S, W)
    t = taps.astype(jnp.float32)
    z = t[0] * ext[:, :s]
    for i in range(1, keep + 1):
        z = z + t[i] * ext[:, i:i + s]
    y = (c_ * z).astype(bcx.dtype)
    src, written = _ring_sources(starts, lengths, ring)
    new = jnp.where(written[..., None], jnp.take_along_axis(
        u, jnp.clip(src, 0, s - 1)[..., None], axis=1), mine)
    return y, state.at[layer, rings].set(new)


# the fed rows sit in the scratch from this row on, the past before it
_PAST = SUBLANES


def _short_conv_kernel(layer_ref, rings_ref, starts_ref, lengths_ref,
                       b_ref, c_ref, x_ref, taps_ref, state_in_ref,
                       y_ref, state_ref, ext_ref, *, rows, keep, ring):
    """One block of ``rows`` launch rows and one lane tile: the past
    read from the ring, ``u``, the taps and the gate, then the ring's
    rows rewritten, each from the newest fed row that is its position
    modulo the ring or as it was."""
    del layer_ref, rings_ref
    s = b_ref.shape[1]
    taps = taps_ref[...].astype(jnp.float32)
    state_ref[...] = state_in_ref[...]
    for t in range(rows):
        row = pl.program_id(0) * rows + t
        start, n = starts_ref[row], lengths_ref[row]
        for m in range(keep):
            p = start - keep + m
            old = state_in_ref[t, pl.ds(lax.rem(p + ring, ring), 1), :]
            ext_ref[pl.ds(_PAST - keep + m, 1), :] = jnp.where(
                p >= 0, old, 0.0)
        ext_ref[pl.ds(_PAST, s), :] = (b_ref[t].astype(jnp.float32)
                                       * x_ref[t].astype(jnp.float32))
        z = taps[0:1] * ext_ref[pl.ds(_PAST - keep, s), :]
        for i in range(1, keep + 1):
            z = z + taps[i:i + 1] * ext_ref[pl.ds(_PAST - keep + i, s), :]
        y_ref[t] = (c_ref[t].astype(jnp.float32) * z).astype(y_ref.dtype)
        last = start + n - 1
        for r in range(ring):
            src = n - 1 - lax.rem(last - r + ring, ring)
            got = ext_ref[pl.ds(_PAST + jnp.clip(src, 0, s - 1), 1), :]
            state_ref[t, pl.ds(r, 1), :] = jnp.where(
                (n > 0) & (src >= 0), got, state_in_ref[t, pl.ds(r, 1), :])


@functools.partial(jax.jit, static_argnames=("rows", "tile", "interpret"))
def _short_conv_pallas(bcx, taps, state, layer, rings, starts, lengths, *,
                       rows, tile, interpret):
    b, s, w3 = bcx.shape
    w = w3 // 3
    keep, ring = taps.shape[0] - 1, state.shape[2]
    lanes = w // tile

    def third(c):
        return pl.BlockSpec((rows, s, tile),
                            lambda i, j, *_: (i, 0, c * lanes + j))

    # a block of ``rows`` launch rows holds the rings of consecutive
    # slots from its first row's on (``short_conv``)
    state_spec = pl.BlockSpec(
        (None, rows, ring, tile),
        lambda i, j, layer_ref, rings_ref, *_: (
            layer_ref[0], rings_ref[i * rows] // rows, 0, j))
    kernel = functools.partial(_short_conv_kernel, rows=rows, keep=keep,
                               ring=ring)
    return pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=4,
            grid=(b // rows, lanes),
            in_specs=[third(0), third(1), third(2),
                      pl.BlockSpec((keep + 1, tile),
                                   lambda i, j, *_: (0, j)),
                      state_spec],
            out_specs=[pl.BlockSpec((rows, s, tile),
                                    lambda i, j, *_: (i, 0, j)),
                       state_spec],
            scratch_shapes=[pltpu.VMEM((_PAST + s, tile), jnp.float32)]),
        out_shape=[jax.ShapeDtypeStruct((b, s, w), bcx.dtype,
                                        vma=union_vma(bcx, state)),
                   jax.ShapeDtypeStruct(state.shape, state.dtype,
                                        vma=union_vma(bcx, state))],
        # the state leaf goes out where it came in: operand 8 counts
        # the four prefetched scalars
        input_output_aliases={8: 1},
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel")),
        interpret=interpret,
        name=KERNEL_NAME,
    )(layer, rings, starts, lengths, bcx, bcx, bcx, taps, state)


def short_conv(bcx, taps, state, layer, rings, starts, lengths, *,
               consecutive: bool = False, use_pallas: Optional[bool] = None,
               interpret: Optional[bool] = None):
    """The short convolution of a launch's fed rows over the state ring
    (the module docstring), as :func:`short_conv_reference` defines it.

    ``consecutive``: the launch's rows ARE slots ``0 .. B-1`` in order
    (``rings`` is ``arange(B)``: a decode or verify launch of the whole
    batch), so the kernel takes them eight at a time; otherwise one row
    a block, each at its own slot's ring.  ``use_pallas=None``: the
    kernel on one TPU chip, the ``jnp`` form elsewhere
    (``pallas_auto_gate``)."""
    if not pallas_auto_gate(use_pallas):
        return short_conv_reference(bcx, taps, state, layer, rings, starts,
                                    lengths)
    if interpret is None:
        interpret = not on_tpu()
    b, s, w3 = bcx.shape
    w = w3 // 3
    if taps.shape[0] - 1 > _PAST or w % LANES:
        raise ValueError(f"{taps.shape[0]} taps over rows of {w} values: "
                         f"the kernel keeps up to {_PAST + 1} taps over "
                         f"whole {LANES}-lane tiles")
    rows = SUBLANES if consecutive and b % SUBLANES == 0 else 1
    # a whole row of lanes a block where a launch feeds a few rows a
    # sequence; tiles of 512 lanes under a chunk's hundreds
    tile = w if s <= 2 * SUBLANES or w % 512 else 512
    layer = jnp.reshape(jnp.asarray(layer, jnp.int32), (1,))
    return _short_conv_pallas(
        bcx, taps, state, layer, rings.astype(jnp.int32),
        starts.astype(jnp.int32), lengths.astype(jnp.int32), rows=rows,
        tile=tile, interpret=bool(interpret))
