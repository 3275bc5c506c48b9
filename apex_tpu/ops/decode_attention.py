"""Cached (single-token) attention — the decode half of serving.

Prefill reuses ``ops.flash_attention`` unchanged (causal, O(S) memory,
full backward).  Decode is a different animal: one NEW query token per
sequence attends over T cached key/value positions gathered from the
``serving.kv_cache`` block pool — Sq == 1, no causality (the cache only
ever holds the past), no dropout, and no backward pass (inference
only).  Specializing buys a much leaner kernel than flash-with-Sq=1:

- grid ``(B*H, T/bk)``, k innermost; VMEM scratch carries the running
  (m, l, acc) streaming-softmax state across k blocks, so the (1, T)
  score row never exists in HBM;
- the single query row is broadcast to the 8-sublane granularity the
  TPU vector layout wants (rows 1..7 compute identical garbage that is
  sliced away on writeout — sublane padding is free relative to the
  HBM-bound K/V streaming that dominates decode);
- scores accumulate in fp32 on the MXU regardless of cache dtype
  (``preferred_element_type``), matching the flash numeric policy.

The jnp path is the parity oracle and the CPU/GSPMD-automatic
fallback; the kernel gate is the standard
``pallas_utils.pallas_auto_gate`` resolution of ``use_pallas=None``.

Quantized KV (``docs/serving.md``, "Quantized KV cache"): when the
pool stores int8, both entry points take the per-slot per-head fp32
scale sidecar (``k_scale`` / ``v_scale``, (B, T, H)) and widen
int8 -> compute dtype AT READ — the jnp oracle with one fp32 multiply
and a single cast (:func:`ops.kv_quant.dequantize_kv`), the Pallas
streaming kernel per K-block in VMEM right after the int8 HBM read —
so decode streams HALF the cache bytes and logits never see a
separately-materialized dequantized pool.

Masking: ``kv_bias`` is a (B, T) additive fp32 row (0 keep / NEG_INF
drop) — the engine builds it from per-request context lengths so
unwritten cache slots can never win the softmax.  Fully-masked rows
emit zeros (the flash convention), though the serving engine never
produces one: the new token's own k/v is always appended unmasked.
"""

from __future__ import annotations

import functools
import math
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from apex_tpu.ops.kv_quant import dequantize_kv
from apex_tpu.ops.pallas_utils import (LANES, on_tpu, pallas_auto_gate,
                                       union_vma, unpatched)

NEG_INF = -1e30

# fp32-accumulation einsum, immune to amp O1's half-list patch (the
# upcasts here are deliberate numerics, not user policy — same rationale
# as ops.flash_attention)
_einsum = unpatched(jnp.einsum)

# sublane granularity the single query row is broadcast to
_QROWS = 8


def _cdiv(a, b):
    return (a + b - 1) // b


def _reference(q, k, v, kv_bias, scale, k_scale=None, v_scale=None):
    """jnp oracle: fp32 scores/softmax, output in q.dtype.  With
    scales, k/v arrive int8 and widen to q.dtype first — the same
    dequantization rule the kernel applies per block in VMEM."""
    if k_scale is not None:
        k = dequantize_kv(k, k_scale, q.dtype)
        v = dequantize_kv(v, v_scale, q.dtype)
    s = _einsum("bqhd,bkhd->bhqk", q, k).astype(jnp.float32) * scale
    if kv_bias is not None:
        s = s + kv_bias.astype(jnp.float32)[:, None, None, :]
    m = jnp.max(s, axis=-1, keepdims=True)
    # fully-masked rows (all NEG_INF) emit zeros, not NaN
    valid = m > NEG_INF / 2
    p = jnp.exp(s - jnp.where(valid, m, 0.0))
    p = jnp.where(valid, p, 0.0)
    l = jnp.maximum(jnp.sum(p, axis=-1, keepdims=True), 1e-30)
    out = _einsum("bhqk,bkhd->bqhd", (p / l).astype(q.dtype), v)
    return out.astype(q.dtype)


def _stream_step(q, k, v, bias_row, o_ref, acc_ref, m_ref, l_ref, *,
                 scale, nk):
    """One (batch*head, k-block) step of the streaming softmax —
    shared by the plain and the int8-dequantizing kernel fronts."""
    ik = pl.program_id(1)

    @pl.when(ik == 0)
    def _init():
        acc_ref[:] = jnp.zeros_like(acc_ref)
        m_ref[:] = jnp.full_like(m_ref, NEG_INF)
        l_ref[:] = jnp.zeros_like(l_ref)

    s = lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                        preferred_element_type=jnp.float32) * scale
    s = s + bias_row[None, :]                      # (_QROWS, bk)

    m_prev = m_ref[:, 0]
    m_new = jnp.maximum(m_prev, jnp.max(s, axis=1))
    corr = jnp.exp(m_prev - m_new)
    p = jnp.exp(s - m_new[:, None])
    l_new = l_ref[:, 0] * corr + jnp.sum(p, axis=1)
    acc_ref[:] = acc_ref[:] * corr[:, None] + lax.dot_general(
        p, v.astype(jnp.float32), (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32)
    m_ref[:] = jnp.broadcast_to(m_new[:, None], m_ref.shape)
    l_ref[:] = jnp.broadcast_to(l_new[:, None], l_ref.shape)

    @pl.when(ik == nk - 1)
    def _writeout():
        # 2-D broadcast-first like flash: Mosaic cannot insert a minor
        # dim on i1 vectors
        m2 = m_ref[:, :1]
        valid2 = m2 > NEG_INF / 2
        out = acc_ref[:] / jnp.maximum(l_ref[:, :1], 1e-30)
        o_ref[0] = jnp.where(valid2, out, 0.0).astype(o_ref.dtype)


def _decode_kernel(bias_ref, q_ref, k_ref, v_ref, o_ref,
                   acc_ref, m_ref, l_ref, *, scale, bk, nk):
    _stream_step(q_ref[0], k_ref[0], v_ref[0], bias_ref[0, 0],
                 o_ref, acc_ref, m_ref, l_ref, scale=scale, nk=nk)


def _decode_kernel_q8(bias_ref, ks_ref, vs_ref, q_ref, k_ref, v_ref,
                      o_ref, acc_ref, m_ref, l_ref, *, scale, bk, nk):
    """The int8 front: the K/V block specs stream INT8 bytes from HBM
    (half the bf16 traffic decode is bound by) and widen to the
    compute dtype here in VMEM — one fp32 multiply by the block's
    per-slot scale row and a single cast, the exact
    :func:`ops.kv_quant.dequantize_kv` rule, so kernel and jnp oracle
    dequantize identically."""
    k = (k_ref[0].astype(jnp.float32)
         * ks_ref[0, 0][:, None]).astype(q_ref.dtype)
    v = (v_ref[0].astype(jnp.float32)
         * vs_ref[0, 0][:, None]).astype(q_ref.dtype)
    _stream_step(q_ref[0], k, v, bias_ref[0, 0],
                 o_ref, acc_ref, m_ref, l_ref, scale=scale, nk=nk)


@functools.partial(jax.jit,
                   static_argnames=("scale", "bk", "interpret"))
def _decode_pallas(q3, k3, v3, bias, ksc=None, vsc=None, *,
                   scale, bk, interpret):
    """q3: (BH, _QROWS, D) broadcast query; k3/v3: (BH, Tp, D);
    bias: (B, Tp) additive row, already NEG_INF over T padding;
    ksc/vsc: optional (BH, Tp) fp32 dequant scale rows — k3/v3 are
    then int8 and the q8 kernel widens each block in VMEM."""
    bh, _, d = q3.shape
    tp = k3.shape[1]
    nk = tp // bk
    b = bias.shape[0]
    h = bh // b
    lanes = 128
    q_spec = pl.BlockSpec((1, _QROWS, d), lambda i, j: (i, 0, 0))
    k_spec = pl.BlockSpec((1, bk, d), lambda i, j: (i, j, 0))
    bias_spec = pl.BlockSpec((1, 1, bk), lambda i, j: (i // h, 0, j))
    if ksc is None:
        kernel = functools.partial(_decode_kernel, scale=scale,
                                   bk=bk, nk=nk)
        in_specs = [bias_spec, q_spec, k_spec, k_spec]
        args = (bias[:, None, :], q3, k3, v3)
    else:
        # scale rows are per (batch*head, slot), so they index like
        # the K blocks, not like the per-batch bias
        s_spec = pl.BlockSpec((1, 1, bk), lambda i, j: (i, 0, j))
        kernel = functools.partial(_decode_kernel_q8, scale=scale,
                                   bk=bk, nk=nk)
        in_specs = [bias_spec, s_spec, s_spec, q_spec, k_spec, k_spec]
        args = (bias[:, None, :], ksc[:, None, :], vsc[:, None, :],
                q3, k3, v3)
    return pl.pallas_call(
        kernel,
        grid=(bh, nk),
        in_specs=in_specs,
        out_specs=q_spec,
        out_shape=jax.ShapeDtypeStruct((bh, _QROWS, d), q3.dtype,
                                       vma=union_vma(*args)),
        scratch_shapes=[pltpu.VMEM((_QROWS, d), jnp.float32),
                        pltpu.VMEM((_QROWS, lanes), jnp.float32),
                        pltpu.VMEM((_QROWS, lanes), jnp.float32)],
        interpret=interpret,
        # names the custom call in the compiled HLO and in traces
        name=kernel.func.__name__,
    )(*args)


def _layout(x):
    """(B, T, H, D) -> (B*H, T, D)."""
    b, t, h, d = x.shape
    return jnp.swapaxes(x, 1, 2).reshape(b * h, t, d)


def _layout_scale(x):
    """(B, T, H) -> (B*H, T) — the scale-row analogue of
    :func:`_layout`."""
    b, t, h = x.shape
    return jnp.swapaxes(x, 1, 2).reshape(b * h, t)


def _check_scales(k, k_scale, v_scale, what):
    """Both-or-neither scales, shaped like k minus its head_dim."""
    if (k_scale is None) != (v_scale is None):
        raise ValueError(
            f"{what}: k_scale and v_scale must be passed together")
    if k_scale is not None and (k_scale.shape != k.shape[:3]
                                or v_scale.shape != k.shape[:3]):
        raise ValueError(
            f"{what}: scales must be (B, T, H) matching k; got "
            f"k={k.shape} k_scale={k_scale.shape} "
            f"v_scale={v_scale.shape}")


def chunk_cached_attention(q, k, v, ctx_bias,
                           scale: Optional[float] = None,
                           k_scale: Optional[jax.Array] = None,
                           v_scale: Optional[jax.Array] = None):
    """Multi-token (chunked-prefill) attention over gathered cache
    context plus the chunk itself.

    Args:
      q: (B, C, H, D) — one prefill chunk's queries.
      k, v: (B, T + C, H, D) — the first T positions are the gathered
        cache context (everything already materialized precedes the
        chunk, so every chunk query may attend all of it, masked by
        ``ctx_bias``), the last C the chunk's own fresh K/V, attended
        CAUSALLY within the chunk.
      ctx_bias: (B, T) additive fp32 context mask (0 keep / NEG_INF
        for unwritten slots — the engine builds it from the chunk's
        start position).
      scale: logit scale, default 1/sqrt(D).
      k_scale, v_scale: optional (B, T + C, H) fp32 dequantization
        scales — k/v are then int8 (quantized cache context AND the
        chunk's own already-quantized fresh K/V, concatenated by the
        model) and widen to q.dtype here before the score einsum.

    jnp only, same fp32 numeric policy as :func:`cached_attention`'s
    oracle: the (C, T + C) score tile is chunk-bounded and XLA handles
    it well — decode's Sq==1 streaming kernel stays the only custom
    kernel in the serving path.  Every query row attends at least its
    own key (causal diagonal), so no fully-masked-row guard is needed.
    """
    b, c, _, d = q.shape
    t = k.shape[1] - c
    if t < 0 or v.shape != k.shape:
        raise ValueError(
            f"k/v must be (B, T + C, H, D) with T >= 0; got q={q.shape} "
            f"k={k.shape} v={v.shape}")
    _check_scales(k, k_scale, v_scale, "chunk_cached_attention")
    if k_scale is not None:
        k = dequantize_kv(k, k_scale, q.dtype)
        v = dequantize_kv(v, v_scale, q.dtype)
    if scale is None:
        scale = 1.0 / math.sqrt(d)
    s = _einsum("bqhd,bkhd->bhqk", q, k).astype(jnp.float32) * scale
    causal = jnp.where(
        jnp.arange(c)[:, None] >= jnp.arange(c)[None, :], 0.0, NEG_INF)
    bias = jnp.concatenate(
        [jnp.broadcast_to(ctx_bias.astype(jnp.float32)[:, None, :],
                          (b, c, t)),
         jnp.broadcast_to(causal[None], (b, c, c))], axis=-1)
    s = s + bias[:, None]                              # (B, H, C, T+C)
    m = jnp.max(s, axis=-1, keepdims=True)
    p = jnp.exp(s - m)
    l = jnp.maximum(jnp.sum(p, axis=-1, keepdims=True), 1e-30)
    out = _einsum("bhqk,bkhd->bqhd", (p / l).astype(q.dtype), v)
    return out.astype(q.dtype)


def cached_attention(q, k, v, *, kv_bias: Optional[jax.Array] = None,
                     scale: Optional[float] = None,
                     k_scale: Optional[jax.Array] = None,
                     v_scale: Optional[jax.Array] = None,
                     block_k: Optional[int] = None,
                     use_pallas: Optional[bool] = None,
                     interpret: Optional[bool] = None):
    """Single-new-token attention over a gathered KV-cache context.

    Args:
      q: (B, 1, H, D) — the new token's queries.
      k, v: (B, T, H, D) — gathered cache context, the new token's own
        k/v included (the engine appends it; there is no causality to
        enforce because the cache holds only the past).
      kv_bias: optional (B, T) additive fp32 mask (0 keep / NEG_INF
        drop) — position j masks cache slot j; unwritten slots MUST be
        masked by the caller.
      scale: logit scale, default 1/sqrt(D).
      k_scale, v_scale: optional (B, T, H) fp32 dequantization scales
        (the quantized pool's per-slot per-head sidecar) — k/v are
        then int8 and widen to q.dtype at read: per K-block in VMEM
        inside the streaming kernel, with one fp32 multiply on the
        jnp oracle.  The logits path never materializes a dequantized
        pool.
      block_k: k-block tile (multiple of 128 recommended); default
        min(512, padded T).
      use_pallas: None = auto (:func:`pallas_utils.pallas_auto_gate`).
      interpret: force Pallas interpret mode (defaults to not-on-TPU).

    Returns (B, 1, H, D) in q.dtype.  NOT differentiable on the kernel
    path — decode is inference-only; the jnp path differentiates like
    any jnp code.
    """
    if q.ndim != 4 or q.shape[1] != 1:
        raise ValueError(f"q must be (B, 1, H, D); got {q.shape}")
    if k.shape != v.shape or k.shape[0] != q.shape[0] \
            or k.shape[2:] != q.shape[2:]:
        raise ValueError(
            f"k/v must be (B, T, H, D) matching q; got q={q.shape} "
            f"k={k.shape} v={v.shape}")
    _check_scales(k, k_scale, v_scale, "cached_attention")
    if scale is None:
        scale = 1.0 / math.sqrt(q.shape[-1])
    if not pallas_auto_gate(use_pallas):
        return _reference(q, k, v, kv_bias, scale, k_scale, v_scale)

    if interpret is None:
        interpret = not on_tpu()
    b, t, h, d = k.shape
    if block_k is None:
        block_k = min(512, _cdiv(t, 128) * 128)
    tp = _cdiv(t, block_k) * block_k
    bias = (jnp.zeros((b, t), jnp.float32) if kv_bias is None
            else kv_bias.astype(jnp.float32))
    if tp != t:  # padded cache slots must never win the softmax
        k = jnp.pad(k, ((0, 0), (0, tp - t), (0, 0), (0, 0)))
        v = jnp.pad(v, ((0, 0), (0, tp - t), (0, 0), (0, 0)))
        bias = jnp.pad(bias, ((0, 0), (0, tp - t)),
                       constant_values=NEG_INF)
        if k_scale is not None:  # zero scale: padding dequants to 0
            k_scale = jnp.pad(k_scale, ((0, 0), (0, tp - t), (0, 0)))
            v_scale = jnp.pad(v_scale, ((0, 0), (0, tp - t), (0, 0)))
    q3 = jnp.broadcast_to(_layout(q), (b * h, _QROWS, d))
    ksc = _layout_scale(k_scale) if k_scale is not None else None
    vsc = _layout_scale(v_scale) if v_scale is not None else None
    out = _decode_pallas(q3, _layout(k), _layout(v), bias, ksc, vsc,
                         scale=float(scale), bk=int(block_k),
                         interpret=bool(interpret))
    # row 0 of the sublane-broadcast block is the real query
    return out[:, :1].reshape(b, h, 1, d).swapaxes(1, 2)


# -- attention in place: the pool read through the block table ---------------

# pages one window of the loop holds: 8 pages of 16 slots are 128 keys,
# one full lane row of scores and one native MXU tile of K/V per head
_PAGES = 8


def _query_row(m, shared):
    """The query row that row ``m`` of a tile belongs to, where the
    rows of ``shared`` heads lie side by side."""
    if shared == 1:
        return m
    if shared & (shared - 1) == 0:
        return lax.shift_right_logical(m, shared.bit_length() - 1)
    return m // shared


def _first_key(start, first_row, reach):
    """The first key any row of a tile may see: none lies more than
    ``reach - 1`` before the tile's first row."""
    return jnp.maximum(start + first_row - (reach - 1), 0)


def _paged_kernel(layer_ref, tables_ref, starts_ref, q_ref, pool_ref,
                  o_ref, buf_ref, sems, half_ref, acc_ref, m_ref, l_ref, *,
                  scale, block_size, rows, groups, shared, value, pages,
                  table, reach=None):
    """One (slot, row tile) step of the streaming softmax over the pool
    itself: a loop over the ``pages``-page windows the tile's rows can
    see, and no other.  ``pool_ref`` is the pool leaf, left in HBM; the
    kernel copies each window's pages into one half of ``buf_ref``
    (``(2, pages * block_size, groups * width)``: one row a token,
    ``groups`` groups of ``width`` values side by side) while the other
    half is computed, a DMA semaphore a page of each half.  The grid
    runs in order, and a step's last window fetches the next step's
    first, so only the launch's first fetch is waited on with nothing
    to compute; ``half_ref`` says which half that window is in.

    A query row is as wide as a group, with zeros over the lanes that
    are not key, so ``q . group`` is ``q . K`` and no lane is sliced.
    ``value`` names the lanes of a group that are its value: the whole
    group for a head's ``K_h | V_h`` pair (the product of the
    probabilities with the same tile then carries ``p . V_h`` in its
    upper lanes, which the caller takes), or a whole number of leading
    lane tiles for a latent row.  Where a group's key is whole lane
    tiles itself (``head_dim`` a multiple of 128) the queries are as
    wide as the key alone and ``value`` is the group's upper half: both
    slices are aligned, and the products run over half the lanes.
    ``shared`` query heads read one group; their rows lie side by side
    in the tile (row ``m`` is query row ``m // shared``), so a
    sequence's heads meet a page as one matrix.

    ``reach``: a row sees the ``reach`` keys that end with its own and
    no earlier one (a window layer), and ``table`` is a ring: the page
    of positions ``n * block_size ..`` is entry ``n % table``.  The
    loop then starts at the window that holds the tile's first visible
    key."""
    b, i = pl.program_id(0), pl.program_id(1)
    tiles = pl.num_programs(1)
    span = pages * block_size           # keys in one window
    tile, q_width = q_ref.shape[1], q_ref.shape[2]
    width = buf_ref.shape[2] // groups
    query_row = functools.partial(_query_row, shared=shared)

    def bounds(b, i):
        """The first key and the last row of slot ``b``'s row tile
        ``i``: beyond its last live row a tile holds padding, and a row
        sees every key at or before its own position (the rows were
        written before this call)."""
        start = starts_ref[b]
        first = 0 if reach is None else _first_key(
            start, query_row(i * tile), reach)
        return first, start + query_row(
            i * tile + jnp.minimum(tile, rows * shared - i * tile) - 1)

    def fetch(b, i, w, half):
        """The copies of window ``w`` of slot ``b``'s row tile ``i``
        into ``half``: every page slot filled, those past the tile's
        pages with the nearest of them (a probability of 0 meets a
        value there, never uninitialised memory)."""
        first, last = bounds(b, i)
        copies = []
        for k in range(pages):
            blk = jnp.clip(w * pages + k, first // block_size,
                           last // block_size)
            blk = blk % table if reach is not None else jnp.minimum(
                blk, table - 1)
            row0 = tables_ref[b * table + blk] * block_size
            copies.append(pltpu.make_async_copy(
                pool_ref.at[layer_ref[0], pl.ds(row0, block_size)],
                buf_ref.at[half, pl.ds(k * block_size, block_size)],
                sems.at[half, k]))
        return copies

    def begin(copies):
        for copy in copies:
            copy.start()

    first, last = bounds(b, i)
    w0, w1 = first // span, last // span
    # the step after this one, whose first window the last window of
    # this one fetches
    b_next = jnp.where(i + 1 < tiles, b, b + 1)
    i_next = jnp.where(i + 1 < tiles, i + 1, 0)

    @pl.when((b == 0) & (i == 0))
    def _prologue():
        half_ref[0] = 0
        begin(fetch(b, i, w0, 0))

    acc_ref[...] = jnp.zeros_like(acc_ref)
    m_ref[...] = jnp.full_like(m_ref, NEG_INF)
    l_ref[...] = jnp.zeros_like(l_ref)
    half0 = half_ref[0]

    def window(w, carry):
        half = (half0 + w - w0) % 2

        @pl.when(w < w1)
        def _ahead():
            begin(fetch(b, i, w + 1, 1 - half))

        @pl.when((w == w1) & (b_next < pl.num_programs(0)))
        def _next_step():
            begin(fetch(b_next, i_next,
                        bounds(b_next, i_next)[0] // span, 1 - half))
            half_ref[0] = 1 - half

        for copy in fetch(b, i, w, half):
            copy.wait()
        key = w * span + lax.broadcasted_iota(jnp.int32, (tile, span), 1)
        row = starts_ref[b] + query_row(
            i * tile + lax.broadcasted_iota(jnp.int32, (tile, span), 0))
        seen = key <= row
        if reach is not None:
            seen = seen & (key > row - reach)

        def group(g, carry):
            lanes = pl.ds(pl.multiple_of(g * width, width), width)
            kv = buf_ref[half, :, lanes]
            s = lax.dot_general(q_ref[g], kv if q_width == width
                                else kv[:, :q_width],
                                (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32)
            s = jnp.where(seen, s * scale, NEG_INF)      # (tile, span)
            m_prev = m_ref[g]
            m_new = jnp.maximum(m_prev,
                                jnp.max(s, axis=1, keepdims=True))
            corr = jnp.exp(m_prev - m_new)
            p = jnp.exp(s - m_new[:, :1])
            l_ref[g] = l_ref[g] * corr + jnp.sum(p, axis=1,
                                                 keepdims=True)
            v = kv if value == (0, width) else kv[:, value[0]:value[1]]
            acc_ref[g] = acc_ref[g] * corr[:, :1] + lax.dot_general(
                p.astype(kv.dtype), v, (((1,), (0,)), ((), ())),
                preferred_element_type=jnp.float32)
            m_ref[g] = m_new
            return carry

        # one traced body, unrolled when lowered: the compiled kernel
        # is the Python loop's (163.6 us a layer either way with eight
        # slots live at GPT-2 XL, 391 rolled up; my chip run, PR 25)
        # and the host traces a twenty-fifth of it
        lax.fori_loop(0, groups, group, 0, unroll=True)
        return carry

    lax.fori_loop(w0, w1 + 1, window, 0)
    # every row sees its own key, so no row is fully masked
    o_ref[...] = (acc_ref[...] / l_ref[...][:, :, :1]).astype(o_ref.dtype)


# query rows one grid step holds: more rows a step reuse each K/V tile
# the MXU has latched for more work, and cost VMEM
_ROW_TILE = 128
# ... where ``heads_per_group`` query heads read one key-value head's
# ``K | V`` pair (or a layer attends a window): 64 positions of 8 heads
# a tile and 256 keys a window.  Every group's accumulator is in VMEM at
# once (8 groups of 512 rows of 128 float32 lanes are 2 MiB)
_GROUP_ROW_TILE = 512
_GROUP_PAGES = 16
# ... and where heads share a row: 32 positions of 32 heads a tile and
# 512 keys a window.  The accumulator (row tile x value, float32) is
# rescaled once a window whatever the window's keys, so more keys a
# window cost less of it.  A chunk of 256 at 8,192 cached rows, one
# layer at the long-document cell's shapes: 2.94 ms at 8 pages and a
# tile of 512, 1.81 at 32 pages, 1.37 at 32 pages and a tile of 1,024
# (58% of the MXU's peak on the absorbed product); 8 slots decoding at
# 12,000: 0.97, 0.71, 0.71 (one TPU v5e).
_SHARED_ROW_TILE = 1024
_SHARED_PAGES = 32


@functools.partial(jax.jit, static_argnames=(
    "block_size", "rows", "shared", "value", "scale", "name", "interpret",
    "window", "tile", "reach"))
def _paged_pallas(layer, tables, starts, q4, pages, *, block_size, rows,
                  shared, value, scale, name, interpret, window, tile,
                  reach=None):
    """q4: (B, G, Mp, W) queries, one row of a group's width a tile
    row, zero over the lanes that are not key, Mp a whole number of row
    tiles (``shared`` heads' rows of one position side by side); pages:
    the pool leaf (L, num_slots, G * W), left in HBM for the kernel to
    copy its windows from; layer (1,), tables
    (B * blocks_per_seq,), starts (B,) int32 are prefetched scalars.
    With ``reach`` the table is a ring: the page of positions
    ``n * block_size ..`` is entry ``n % blocks_per_seq``."""
    b, g, mp, gw = q4.shape
    nb = tables.shape[0] // b
    width = pages.shape[2]
    window = min(window, nb)
    vw = value[1] - value[0]

    def rows_spec(lanes):
        return pl.BlockSpec((None, g, tile, lanes),
                            lambda bi, ii, *_: (bi, 0, ii, 0))

    kernel = functools.partial(_paged_kernel, scale=scale,
                               block_size=block_size, rows=rows, groups=g,
                               shared=shared, value=value, pages=window,
                               table=nb, reach=reach)
    itemsize = jnp.dtype(q4.dtype).itemsize
    vmem = (g * tile * (vw + 2 * LANES) * 4         # acc, m, l
            + 2 * g * tile * (gw + vw) * itemsize   # q and out, twice
            + 2 * window * block_size * width * itemsize)
    return pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=3,
            grid=(b, mp // tile),
            in_specs=[rows_spec(gw), pl.BlockSpec(memory_space=pl.ANY)],
            out_specs=rows_spec(vw),
            scratch_shapes=[
                pltpu.VMEM((2, window * block_size, width), pages.dtype),
                pltpu.SemaphoreType.DMA((2, window)),
                pltpu.SMEM((1,), jnp.int32),
                pltpu.VMEM((g, tile, vw), jnp.float32),
                pltpu.VMEM((g, tile, LANES), jnp.float32),
                pltpu.VMEM((g, tile, LANES), jnp.float32)]),
        out_shape=jax.ShapeDtypeStruct((b, g, mp, vw), q4.dtype,
                                       vma=union_vma(q4, pages)),
        compiler_params=pltpu.CompilerParams(
            # in order: each step fetches the next one's first window
            dimension_semantics=("arbitrary", "arbitrary"),
            # the default scoped limit is 16 MiB; a chunk's row tiles
            # need about that, so ask for what is used and half again
            vmem_limit_bytes=max(16 * 2 ** 20, vmem * 3 // 2)),
        interpret=interpret,
        name=name,
    )(layer, tables, starts, q4, pages)


def _loop_shape(rows: int, heads: int, heads_per_group: int, latent: bool,
                windowed: bool):
    """The page loop's shape for ``rows`` query rows of ``heads`` heads
    a sequence: ``(shared, padded, tile, pages)``, the heads whose rows
    lie side by side in a tile, the rows a sequence padded to whole row
    tiles (or whole sublane pairs, under one tile), the row tile and the
    pages a window."""
    if latent:
        shared, unit, tile, pages = (heads, 2 * _QROWS, _SHARED_ROW_TILE,
                                     _SHARED_PAGES)
    elif heads_per_group > 1 or windowed:
        shared, unit, tile, pages = (heads_per_group, 2 * _QROWS,
                                     _GROUP_ROW_TILE, _GROUP_PAGES)
    else:
        shared, unit, tile, pages = 1, _QROWS, _ROW_TILE, _PAGES
    m = rows * shared
    padded = _cdiv(m, unit) * unit
    if padded > tile:
        padded = _cdiv(m, tile) * tile
    return shared, padded, min(padded, tile), pages


def page_windows(starts, rows: int, table: int, *, block_size: int,
                 heads: int, heads_per_group: int = 1,
                 latent: bool = False):
    """What one launch of :func:`paged_attention` over a block table
    (no ``window``) does, counted on the host from what the launch is
    given: ``(windows the loop visits, windows a grid over the whole
    table would run)``, each summed over the sequences and their row
    tiles.  ``starts`` (B,) each sequence's first row's position,
    ``rows`` its rows, ``table`` the entries of its block table."""
    shared, padded, tile, pages = _loop_shape(rows, heads, heads_per_group,
                                              latent, False)
    pages = min(pages, table)
    ends = np.minimum(np.arange(tile, padded + 1, tile), rows * shared)
    last = np.asarray(starts, np.int64)[:, None] + (ends - 1) // shared
    return (int(np.sum(last // (pages * block_size) + 1)),
            int(last.size) * _cdiv(table, pages))


def paged_attention_fits(head_dim: int, block_size: int, dtype) -> bool:
    """Whether :func:`paged_attention` can take a pool of this
    geometry: a group of ``2 * head_dim`` values (a head's ``K | V``
    pair, or a latent row) has to fill whole 128-lane tiles and a page
    whole sublane tiles of its dtype, or the kernel's slices would not
    be aligned."""
    packing = max(1, 4 // jnp.dtype(dtype).itemsize)
    return (2 * head_dim) % LANES == 0 and block_size % (8 * packing) == 0


def _kernel_name(rows: int, prefix: str = "") -> str:
    """One row, up to a sublane tile of them, more: a trace tells the
    decode, verify and chunk programs' kernels apart."""
    return prefix + ("_decode_kernel" if rows == 1 else
                     "_verify_kernel" if rows <= _QROWS
                     else "_chunk_kernel")


def paged_attention(q, pages, layer, block_tables, starts, *,
                    block_size: int, scale: Optional[float] = None,
                    latent_value: Optional[int] = None,
                    heads_per_group: int = 1,
                    window: Optional[int] = None,
                    interpret: Optional[bool] = None):
    """Attention of freshly written rows over a paged KV pool, read in
    place through the block table.

    Args:
      q: R query rows a sequence: 1 (decode), the verify width, or a
        prefill chunk.  Row ``i`` of sequence ``b`` sits at position
        ``starts[b] + i`` and attends every key at or before itself, so
        its own row must ALREADY be in the pool.  (B, R, H, D) for a
        pool of ``K | V`` pairs; with ``latent_value`` (B, R, H, W),
        each head's absorbed query ``q_lat | q_pe`` as wide as the
        pool's row, zeros over its padding.
      pages: the pool leaf as ``serving.kv_cache`` lays it out, one row
        a token slot: (L, num_slots, G * 2 * D), every key-value head's
        ``K_h`` beside its ``V_h`` (G = H / ``heads_per_group``); or
        (L, num_slots, W), one latent row ``c | k_pe | padding`` that
        all H heads read.
      layer: int32 scalar, the layer whose pages to read.
      block_tables: (B, blocks_per_seq) int32 physical block ids;
        unallocated entries are 0 (the garbage block) and lie beyond
        every valid row's position.
      starts: (B,) int32 position of each sequence's first row (its
        cached context length).
      block_size: token slots a page.
      scale: logit scale, default 1/sqrt(D); a latent pool's caller
        gives it (the width of the expanded query, not of the row).
      latent_value: for a latent pool, how many of a row's leading
        values are also the value (whole lane tiles).
      heads_per_group: query heads that read one ``K | V`` group: head
        ``i`` reads group ``i // heads_per_group``.
      window: a row attends the ``window`` keys that end with its own
        and none before them, and ``block_tables`` is a RING: the page
        of positions ``n * block_size ..`` is entry ``n %
        blocks_per_seq``, which has to hold at least ``window`` plus
        the R rows.  Only the pages that hold ``p - window + 1 .. p``
        are read.
      interpret: Pallas interpret mode (defaults to not-on-TPU).

    The grid runs over sequences and row tiles; a loop in the kernel
    visits only the windows of pages a tile's rows can see, whatever the
    table's length, and nothing of ``max_context`` size is built.  fp32
    scores, softmax state and accumulation; the probabilities meet V in the pool's dtype, as the
    jnp oracle's do.  Returns (B, R, H, D) in q.dtype, or
    (B, R, H, latent_value).  The Pallas call is named
    ``_decode_kernel`` for one row, ``_verify_kernel`` for up to a
    sublane tile of them and ``_chunk_kernel`` beyond, with
    ``_latent`` in front for a latent pool and ``_window`` for a
    window layer, so a trace tells the programs and the kinds of layer
    apart.  Inference only."""
    b, r, h, d = q.shape
    if interpret is None:
        interpret = not on_tpu()
    scalars = (jnp.asarray(layer, jnp.int32).reshape(1),
               block_tables.astype(jnp.int32).reshape(-1),
               starts.astype(jnp.int32))
    if latent_value is not None:
        if pages.ndim != 3 or pages.shape[2] != d or scale is None \
                or latent_value % LANES or not 0 < latent_value <= d:
            raise ValueError(
                f"a latent pool is (L, num_slots, W) with queries "
                f"(B, R, H, W), a scale and a value of whole lane tiles; "
                f"got pages {pages.shape}, q {q.shape}, scale {scale}, "
                f"latent_value {latent_value}")
        if not paged_attention_fits(d // 2, block_size, pages.dtype):
            raise ValueError(
                f"paged_attention cannot tile a latent row of {d}, "
                f"block_size={block_size}, dtype={pages.dtype}")
        # the heads of one position side by side: row m is (m // H)
        _, mp, tile, window_pages = _loop_shape(r, h, 1, True, False)
        m = r * h
        q4 = jnp.pad(q.astype(pages.dtype).reshape(b, 1, m, d),
                     ((0, 0), (0, 0), (0, mp - m), (0, 0)))
        out = _paged_pallas(
            *scalars, q4, pages, block_size=int(block_size), rows=int(r),
            shared=int(h), value=(0, int(latent_value)),
            scale=float(scale), name=_kernel_name(r, "_latent"),
            interpret=bool(interpret), window=window_pages, tile=tile)
        return out[:, 0, :m].reshape(b, r, h, latent_value).astype(q.dtype)
    g = h // heads_per_group
    if pages.ndim != 3 or pages.shape[2] != g * 2 * d or h % heads_per_group:
        raise ValueError(
            f"pages must be (L, num_slots, G*2*D) = (.., .., {g * 2 * d}) "
            f"for q={q.shape} and heads_per_group={heads_per_group}; got "
            f"{pages.shape}")
    if not paged_attention_fits(d, block_size, pages.dtype):
        raise ValueError(
            f"paged_attention cannot tile head_dim={d}, "
            f"block_size={block_size}, dtype={pages.dtype}")
    if scale is None:
        scale = 1.0 / math.sqrt(d)
    _, mp, tile, window_pages = _loop_shape(
        r, h, heads_per_group, False, window is not None)
    if heads_per_group > 1 or window is not None:
        ring = block_tables.shape[1] * block_size
        if window is not None and ring < window + r:
            raise ValueError(
                f"a ring of {ring} rows cannot hold a window of {window} "
                f"beside {r} fed rows")
        # a group's heads of one position side by side: row m of group
        # g is (position m // heads_per_group, head g * heads_per_group
        # + m % heads_per_group)
        m = r * heads_per_group
        # a key of whole lane tiles is sliced from its group for nothing:
        # the queries are then as wide as the key and the value is the
        # group's upper half; a narrower one meets the whole group
        split = d % LANES == 0
        q4 = jnp.moveaxis(q.reshape(b, r, g, heads_per_group, d), 2, 1)
        q4 = jnp.pad(q4.astype(pages.dtype).reshape(b, g, m, d),
                     ((0, 0), (0, 0), (0, mp - m), (0, 0 if split else d)))
        out = _paged_pallas(
            *scalars, q4, pages, block_size=int(block_size), rows=int(r),
            shared=int(heads_per_group),
            value=(d, 2 * d) if split else (0, 2 * d), scale=float(scale),
            name=_kernel_name(r, "" if window is None else "_window"),
            interpret=bool(interpret), window=window_pages, tile=tile,
            reach=None if window is None else int(window))
        out = out[:, :, :m, -d:].reshape(b, g, r, heads_per_group, d)
        return jnp.moveaxis(out, 1, 2).reshape(b, r, h, d).astype(q.dtype)
    q4 = jnp.pad(jnp.swapaxes(q, 1, 2).astype(pages.dtype),
                 ((0, 0), (0, 0), (0, mp - r), (0, d)))
    out = _paged_pallas(
        *scalars, q4, pages, block_size=int(block_size), rows=int(r),
        shared=1, value=(0, 2 * d), scale=float(scale),
        name=_kernel_name(r), interpret=bool(interpret),
        window=window_pages, tile=tile)
    return jnp.swapaxes(out[:, :, :r, d:], 1, 2).astype(q.dtype)


def latent_attention_reference(q, rows, positions, *, value: int,
                               scale: float):
    """The jnp form of latent attention over gathered rows, the parity
    oracle of :func:`paged_attention`'s latent form and what the CPU
    runs: ``q`` (B, R, H, W) absorbed queries, ``rows`` (B, T, W) each
    sequence's logical context with the fed rows already in it (row j
    is position j), ``positions`` (B, R) each query row's own position.
    A row attends every key at or before itself.  fp32 scores and
    softmax; returns (B, R, H, value) in q.dtype."""
    s = _einsum("brhw,btw->bhrt", q, rows.astype(q.dtype)
                ).astype(jnp.float32) * scale
    key = jnp.arange(rows.shape[1], dtype=jnp.int32)
    seen = key[None, None, :] <= positions[:, :, None]       # (B, R, T)
    s = jnp.where(seen[:, None], s, NEG_INF)
    p = jax.nn.softmax(s, axis=-1)
    out = _einsum("bhrt,btv->brhv", p.astype(q.dtype),
                  rows[..., :value].astype(q.dtype))
    return out.astype(q.dtype)


def grouped_attention_reference(q, k, v, q_pos, k_pos, *,
                                window: Optional[int] = None,
                                scale: Optional[float] = None):
    """The jnp form of attention by positions, the parity oracle of
    :func:`paged_attention` with ``heads_per_group`` or ``window`` and
    what the CPU runs for them: ``q`` (B, R, H, D), ``k`` and ``v``
    (B, T, G, D) gathered rows with the fed rows already among them,
    query head ``i`` reading group ``i // (H / G)``; ``q_pos`` (B, R)
    and ``k_pos`` (B, T) the positions the rows stand for (a negative
    ``k_pos``: no key).  A row sees the keys at or before itself and,
    with ``window``, none more than ``window - 1`` before.  fp32 scores
    and softmax; returns (B, R, H, D) in q.dtype."""
    b, r, h, d = q.shape
    g = k.shape[2]
    if scale is None:
        scale = 1.0 / math.sqrt(d)
    s = _einsum("brgpd,btgd->bgprt", q.reshape(b, r, g, h // g, d),
                k.astype(q.dtype)).astype(jnp.float32) * scale
    ahead = q_pos[:, :, None] - k_pos[:, None, :]             # (B, R, T)
    seen = (ahead >= 0) & (k_pos[:, None, :] >= 0)
    if window is not None:
        seen = seen & (ahead < window)
    s = jnp.where(seen[:, None, None], s, NEG_INF)
    p = jax.nn.softmax(s, axis=-1)
    out = _einsum("bgprt,btgd->brgpd", p.astype(q.dtype),
                  v.astype(q.dtype))
    return out.reshape(b, r, h, d).astype(q.dtype)
