"""Flash attention — blockwise fused attention Pallas kernels for TPU.

The reference library predates flash attention entirely; this is part of
apex_tpu's first-class long-context support (SURVEY.md §5 notes the gap):
:func:`apex_tpu.parallel.ring_attention` scales sequence length across
chips, and this kernel makes each chip's local attention O(S) in memory —
scores are produced block-by-block in VMEM and never materialized in HBM.

Algorithm (Dao et al. flash attention 2, re-derived for the TPU grid):

forward, grid (B*H, Sq/bq, Sk/bk), k innermost so VMEM scratch carries
across k steps::

    s    = (q_blk @ k_blk^T) * scale + mask        # (bq, bk) fp32 on MXU
    m'   = max(m, rowmax(s));  corr = exp(m - m')
    p    = exp(s - m')
    l    = l * corr + rowsum(p)
    acc  = acc * corr + p @ v_blk
    out  = acc / l          (written at the last k step)
    lse  = m + log(l)       (saved for backward)

backward (custom VJP), two kernels over the same block structure::

    p   = exp(s - lse)                  # recomputed, never stored
    dv += p^T @ do
    ds  = p * (do @ v^T - delta),  delta = rowsum(do * out)
    dq += ds @ k * scale    (grid q-major)
    dk += ds^T @ q * scale  (grid k-major)

what a block costs (measured on one TPU v5e, PR 28, ``PERF.md``): the
nine products take operands in the inputs' dtype (``p``, ``ds`` rounded
to it, as every public flash kernel does) and accumulate in float32;
float32 inputs keep float32 products.  The scores, ``exp``, the running
max and sum, ``lse``, ``delta`` and the accumulators are float32
always.  Of a causal grid's blocks, those above the diagonal are
skipped, those below it build no mask, and a square block ON it is
computed in strips that stop at the diagonal (``_strips``).  The
``dkv`` kernel works on transposed scores ``k @ q^T`` so that neither
``p`` nor ``ds`` is ever transposed.  At heads of 64 the kernels are
bound by what a grid step costs whatever its size (the lane reductions
of the running max and sum, the accumulators' init and write-out, the
pipeline's own turn) and not by the products, so the default blocks are
as large as VMEM takes (``_default_block``).

attention-probability dropout (in-kernel, ``dropout_rate``/``seed``):
dropout multiplies the NORMALIZED probs by ``c = keep/(1-rate)``, so
``out_i = sum_j c_ij p_ij v_j`` with ``p_ij = exp(s_ij - lse_i)``.  In
the streaming forward, ``l`` (and lse) accumulate UNdropped ``p`` while
``acc`` accumulates ``c*p @ v`` — ``acc/l`` is then exactly
``dropout(softmax(s)) @ v``.  Backward: differentiating through the
softmax with the ``c`` weights gives ::

    d out_i / d s_ij . do_i = p_ij * (c_ij (do_i . v_j) - delta_i),
    delta_i = sum_k c_ik p_ik (do_i . v_k) = rowsum(do * out)

i.e. the usual ``ds = p * (dov - delta)`` with ``dov`` masked+scaled by
``c`` — ``delta`` needs NO change because ``out`` already carries the
dropout.  ``dv`` uses the dropped probs: ``dv_j += sum_i c_ij p_ij
do_i``.  The keep-mask is a counter-based hash of the GLOBAL (batch*
head, q, k) coordinate (``_dropout_keep``), regenerated bit-identically
in all three kernels and the jnp oracle; the lse cotangent fold is
unchanged since lse is the un-dropped statistic.

Key-position masks (additive, (B, Sk)) and causal masking are supported;
fully-masked query rows emit zeros. A pure-jnp path (``use_pallas=False``)
is the parity oracle and CPU fallback; on CPU the kernels run in
interpret mode inside the tests.
"""

from __future__ import annotations

import functools
import math
from typing import Optional

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from apex_tpu.ops.pallas_utils import (on_tpu, pallas_auto_gate,
                                       union_vma, unpatched)

NEG_INF = -1e30

# fp32-accumulation einsum, immune to amp O1's half-list patch (the
# upcasts around these calls are deliberate numerics, not user policy)
_einsum = unpatched(jnp.einsum)


def _cdiv(a, b):
    return (a + b - 1) // b


def _dropout_keep(seed, bh, rows, cols, rate):
    """Deterministic keep-mask for attention-probability dropout.

    Counter-based: a murmur3-finalizer hash of the GLOBAL logical
    coordinate (batch*head, q position, k position) and the step seed —
    plain integer jnp ops, so the SAME mask is regenerated bit-exactly
    in the forward kernel, both backward kernels, the jnp oracle, and
    interpret mode (pltpu's hardware PRNG returns zeros under interpret,
    and jax.random can't run inside a Pallas body).  ~6 VPU int ops per
    score element, overlapped with the MXU matmuls.

    ``rate`` is the DROP probability; keep => True.
    """
    x = (rows.astype(jnp.uint32) * jnp.uint32(0x9E3779B1)) ^ \
        (cols.astype(jnp.uint32) * jnp.uint32(0x85EBCA77)) ^ \
        ((jnp.asarray(bh, jnp.uint32) + jnp.uint32(1))
         * jnp.uint32(0xC2B2AE3D)) ^ \
        (jnp.asarray(seed, jnp.uint32) * jnp.uint32(0x27D4EB2F))
    x = x ^ (x >> jnp.uint32(16))
    x = x * jnp.uint32(0x85EBCA6B)
    x = x ^ (x >> jnp.uint32(13))
    x = x * jnp.uint32(0xC2B2AE35)
    x = x ^ (x >> jnp.uint32(16))
    # top-24-bit uniform; the cast routes through int32 because Mosaic's
    # TPU lowering has no uint32->float32 (caught live by
    # tools/kernel_parity.py check_flash_attention, round 5) — the value
    # is < 2^24 so int32 then float32 is bit-exact with the direct cast
    u = (x >> jnp.uint32(8)).astype(jnp.int32).astype(jnp.float32) \
        * (2.0 ** -24)
    return u >= rate


def seed_array(dropout_seed, offsets=None, *, num_heads):
    """Pack (seed, row_off, col_off, head_off, num_heads_total) into the
    (5,) int32 scalar array every dropout consumer takes — flash's SMEM
    operand, the jnp oracle, and the sequence-parallel fallbacks all
    read THIS layout (``_keep_block`` / :func:`keep_from_seed`)."""
    ro, co, ho, ht = offsets or (0, 0, 0, num_heads)
    return jnp.stack([
        jnp.asarray(dropout_seed, jnp.int32).reshape(()),
        jnp.asarray(ro, jnp.int32).reshape(()),
        jnp.asarray(co, jnp.int32).reshape(()),
        jnp.asarray(ho, jnp.int32).reshape(()),
        jnp.asarray(ht, jnp.int32).reshape(())])


def keep_from_seed(seed, b, h_local, rows, cols, rate):
    """(B, h_local, len(rows), len(cols)) keep-mask from a
    :func:`seed_array` and LOCAL coordinate ranges — the one non-kernel
    mapping of local coordinates to the global hash (the in-kernel
    block form is :func:`_keep_block`; both must agree, pinned by the
    kernel-vs-oracle parity tests)."""
    bh = (jnp.arange(b)[:, None] * seed[4] + seed[3]
          + jnp.arange(h_local)[None, :])[:, :, None, None]
    return _dropout_keep(seed[0], bh,
                         (rows + seed[1])[None, None, :, None],
                         (cols + seed[2])[None, None, None, :], rate)


def _positions(q0, k0, nq, nk, transposed):
    """LOCAL query and key positions of the ``nq`` queries from ``q0``
    against the ``nk`` keys from ``k0``, as two int32 arrays shaped
    ``(nq, nk)``, or ``(nk, nq)`` with the keys down the rows where
    ``transposed`` (the ``dkv`` kernel)."""
    shape, q_dim = ((nk, nq), 1) if transposed else ((nq, nk), 0)
    return (lax.broadcasted_iota(jnp.int32, shape, q_dim) + q0,
            lax.broadcasted_iota(jnp.int32, shape, 1 - q_dim) + k0)


def _keep_block(seed_ref, bh, q0, k0, nq, nk, rate, h, transposed=False):
    """The keep-mask of head ``bh`` for the queries and keys that
    :func:`_positions` describes, shaped as it says — the ONE in-kernel
    mapping of block coordinates to the global hash, so the forward and
    both backward kernels cannot drift apart (the host-side equivalent
    is :func:`keep_from_seed`).

    ``seed_ref`` is the (5,) SMEM scalar array
    ``[seed, row_offset, col_offset, head_offset, num_heads_total]``:
    the offsets translate LOCAL coordinates to GLOBAL ones so sharded
    callers (ring attention's rotating KV shards, Ulysses' head shards)
    drop exactly the positions the equivalent single-device call would.
    ``h`` is the LOCAL head count (the bh grid dim is batch*h_local)."""
    rows, cols = _positions(q0, k0, nq, nk, transposed)
    bh_g = (bh // h) * seed_ref[4] + seed_ref[3] + bh % h
    return _dropout_keep(seed_ref[0], bh_g, rows + seed_ref[1],
                         cols + seed_ref[2], rate)


# ---------------------------------------------------------------------------
# what a block needs: its kind, its scores, its operands' width
# ---------------------------------------------------------------------------

def _block_kind(iq, ik, bq, bk):
    """``(masked, unmasked)`` for block ``(iq, ik)`` of a causal grid,
    from LOCAL block indices (Python ints or traced scalars alike).

    Rows ``iq*bq .. iq*bq+bq-1`` meet columns ``ik*bk .. ik*bk+bk-1``:
    a block whose last row lies before its first column is wholly above
    the diagonal and is skipped (neither flag); one whose first row is
    at or past its last column is wholly below it and needs no mask;
    what is left crosses the diagonal and is the only kind that builds
    the ``iota`` compare.  The ONE place the three kernels (and
    :func:`block_kinds`) decide this, so forward and backward cannot
    drift apart."""
    reaches = iq * bq + bq - 1 >= ik * bk
    unmasked = iq * bq >= ik * bk + bk - 1
    return reaches & (iq * bq < ik * bk + bk - 1), unmasked


def block_kinds(sq, sk, block_q, block_k, causal):
    """How many blocks of the ``(ceil(sq/block_q), ceil(sk/block_k))``
    grid the kernels skip, compute with the causal mask and compute
    without one: ``{"skipped", "masked", "unmasked"}``.  At 1,024 by
    1,024 with blocks of 256: 6, 4 and 6 of 16."""
    counts = {"skipped": 0, "masked": 0, "unmasked": 0}
    for iq in range(_cdiv(sq, block_q)):
        for ik in range(_cdiv(sk, block_k)):
            masked, unmasked = (_block_kind(iq, ik, block_q, block_k)
                                if causal else (False, True))
            counts["masked" if masked else
                   "unmasked" if unmasked else "skipped"] += 1
    return counts


# queries (keys, in the dkv kernel) a strip of a diagonal block: 256 of
# {128, 256, 512} on one v5e (PR 28: dq 0.386 ms a layer at the training
# cell's shape against 0.584 whole, dkv 0.557 against 0.804; 128 gave
# dkv 5% more and dq nothing)
_STRIP = 256


def _strips(masked, bq, bk, by):
    """The parts ``(q_lo, q_hi, k_lo, k_hi)`` of a block that hold
    anything at or under the diagonal.  A block that crosses it with
    ``bq == bk`` lies ON it (``iq == ik``), so what it needs is known
    when the kernel is traced: ``by="q"``, strips of ``_STRIP`` queries,
    each against the keys up to its own last one; ``by="k"``, strips of
    keys against the queries from their own first one on.  At blocks of
    1,024 that is 10 of 16 tiles.  Any other block is one part, and so
    is every block where ``by`` is None: the forward's strips would
    queue their max, ``exp`` and sum one behind the other, and its
    products hide behind those anyway (0.660 ms in strips against 0.592
    whole, same shape)."""
    if by is None or not masked or bq != bk or bq % _STRIP or bq == _STRIP:
        return ((0, bq, 0, bk),)
    if by == "k":
        return tuple((c, bq, c, c + _STRIP) for c in range(0, bk, _STRIP))
    return tuple((r, r + _STRIP, 0, r + _STRIP)
                 for r in range(0, bq, _STRIP))


def _each_part(causal, iq, ik, bq, bk, compute, by=None):
    """Run ``compute(masked, q0, k0, q, k)`` over what block
    ``(iq, ik)`` needs: nothing above the diagonal; across it, each
    part of :func:`_strips` under the causal mask; below it, the whole
    block unmasked (branches of the one kernel).  ``q0``, ``k0`` are
    the part's first LOCAL positions and ``q``, ``k`` its slices of the
    block."""
    def run(masked):
        for q_lo, q_hi, k_lo, k_hi in _strips(masked, bq, bk, by):
            compute(masked, iq * bq + q_lo, ik * bk + k_lo,
                    slice(q_lo, q_hi), slice(k_lo, k_hi))

    if not causal:
        run(False)
        return
    masked, unmasked = _block_kind(iq, ik, bq, bk)
    pl.when(masked)(lambda: run(True))
    pl.when(unmasked)(lambda: run(False))


def _folds_scale(scale, dtype):
    """Whether ``scale`` may multiply the (bq, D) ``q`` block in place
    of every (bq, bk) score: only where that adds no rounding of a
    half-precision ``q`` (a power of two, as 1/8 at heads of 64) or the
    operands are float32 anyway."""
    return dtype == jnp.float32 or math.frexp(scale)[0] == 0.5


def _dot(a, b, contract):
    """``a`` against ``b`` over ``contract`` on the MXU at ``b``'s
    width, accumulated in float32: a float32 probability or score
    gradient is rounded to the dtype the caller's tensor came in
    (bfloat16 under amp O2, "bfloat16 compute") and stays float32
    beside float32 inputs.  On a v5e it buys no time: Mosaic's float32
    product at default precision is one bfloat16 pass already (PR 28)."""
    return jax.lax.dot_general(a.astype(b.dtype), b,
                               (contract, ((), ())),
                               preferred_element_type=jnp.float32)


_NN = ((1,), (0,))   # (m, c) @ (c, n)
_NT = ((1,), (1,))   # (m, c) @ (n, c)^T


def _scores(q, k, mask_row, masked, q0, k0, scale, transposed=False):
    """The scaled, masked scores of ``q`` (from LOCAL position ``q0``)
    against ``k`` (from ``k0``) in float32: (nq, nk), or (nk, nq) where
    ``transposed``.  ``mask_row`` is None where no key of the call is
    masked; ``masked`` says the diagonal passes through."""
    fold = _folds_scale(scale, q.dtype)
    if fold:
        q = q * scale
    s = _dot(k, q, _NT) if transposed else _dot(q, k, _NT)
    if not fold:
        s = s * scale
    if mask_row is not None:
        s = s + (mask_row[:, None] if transposed else mask_row[None, :])
    if masked:
        q_pos, k_pos = _positions(q0, k0, q.shape[0], k.shape[0], transposed)
        s = jnp.where(q_pos >= k_pos, s, NEG_INF)
    return s


# ---------------------------------------------------------------------------
# forward kernel
# ---------------------------------------------------------------------------

def _fwd_kernel(mask_ref, seed_ref, q_ref, k_ref, v_ref, o_ref, lse_ref,
                acc_ref, m_ref, l_ref, *, scale, causal, bq, bk, nk,
                dropout_rate, h, has_mask):
    ik = pl.program_id(2)
    iq = pl.program_id(1)
    bh = pl.program_id(0)  # hoisted: program_id may not appear inside
    # a pl.when body (interpret mode cannot lower it there)

    @pl.when(ik == 0)
    def _init():
        acc_ref[:] = jnp.zeros_like(acc_ref)
        m_ref[:] = jnp.full_like(m_ref, NEG_INF)
        l_ref[:] = jnp.zeros_like(l_ref)

    def _compute(masked, q0, k0, q, k):
        s = _scores(q_ref[0, q], k_ref[0, k],
                    mask_ref[0, 0, k] if has_mask else None,
                    masked, q0, k0, scale)                   # (nq, nk)
        # the running max and sum are (nq, 1) columns throughout (a
        # 1-D value would lie along the lanes)
        m_prev = m_ref[q, :1]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=1, keepdims=True))
        corr = jnp.exp(m_prev - m_new)
        p = jnp.exp(s - m_new)
        # dropout applies to the normalized probs: the normalizer l
        # accumulates UNdropped p, the value accumulator the dropped —
        # out = acc/l then equals dropout(softmax(s)) @ v exactly
        p_v = p
        if dropout_rate > 0.0:
            keep = _keep_block(seed_ref, bh, q0, k0, *s.shape, dropout_rate,
                               h)
            p_v = jnp.where(keep, p / (1.0 - dropout_rate), 0.0)
        l_new = l_ref[q, :1] * corr + jnp.sum(p, axis=1, keepdims=True)
        acc_ref[q] = acc_ref[q] * corr + _dot(p_v, v_ref[0, k], _NN)
        m_ref[q] = jnp.broadcast_to(m_new, (s.shape[0], m_ref.shape[1]))
        l_ref[q] = jnp.broadcast_to(l_new, (s.shape[0], l_ref.shape[1]))

    # init/writeout above/below stay unconditional
    _each_part(causal, iq, ik, bq, bk, _compute)

    @pl.when(ik == nk - 1)
    def _writeout():
        # keep bool tensors 2-D throughout: Mosaic cannot insert a minor
        # dim on i1 vectors, so compare after broadcasting the f32 column
        m2 = m_ref[:, :1]                          # (bq, 1) f32
        l2 = l_ref[:, :1]
        valid2 = m2 > NEG_INF / 2
        out = acc_ref[:] / jnp.maximum(l2, 1e-30)
        o_ref[0] = jnp.where(valid2, out, 0.0).astype(o_ref.dtype)
        lse2 = jnp.where(valid2,
                         m2 + jnp.log(jnp.maximum(l2, 1e-30)), NEG_INF)
        lse_ref[0, 0] = lse2[:, 0]


# ---------------------------------------------------------------------------
# backward kernels
# ---------------------------------------------------------------------------

def _recompute_p(s, lse):
    """``exp(s - lse)`` with ``lse`` already shaped to broadcast over
    the scores: a (nq, 1) column, or a (1, nq) row for transposed
    ones."""
    # fully-masked rows need an explicit zero: their saved lse is NEG_INF
    # and s rounds to exactly NEG_INF in fp32 (the mask offset absorbs any
    # finite score), so exp(s - lse) would be exp(0) == 1, not 0.
    # NB: compare the f32 stats, broadcast after — Mosaic cannot insert
    # a minor dim on an i1 (bool) vector ("Insertion of minor dim ...
    # only supported for 32-bit types")
    return jnp.where(lse > NEG_INF / 2, jnp.exp(s - lse), 0.0)


def _bwd_dq_kernel(mask_ref, seed_ref, q_ref, k_ref, v_ref, do_ref,
                   lse_ref, delta_ref, dq_ref, dq_acc, *, scale, causal,
                   bq, bk, nk, dropout_rate, h, has_mask):
    ik = pl.program_id(2)
    iq = pl.program_id(1)
    bh = pl.program_id(0)  # hoisted out of the pl.when body

    @pl.when(ik == 0)
    def _init():
        dq_acc[:] = jnp.zeros_like(dq_acc)

    def _compute(masked, q0, k0, q, k):
        k_blk = k_ref[0, k]
        p = _recompute_p(
            _scores(q_ref[0, q], k_blk,
                    mask_ref[0, 0, k] if has_mask else None,
                    masked, q0, k0, scale), lse_ref[0, 0, q][:, None])
        dov = _dot(do_ref[0, q], v_ref[0, k], _NT)
        if dropout_rate > 0.0:
            # ds = p * (c * dov - delta), c = keep/(1-rate) — same mask
            # via _keep_block; delta already carries the dropped-out
            # forward (see module docstring dropout derivation)
            keep = _keep_block(seed_ref, bh, q0, k0, *p.shape, dropout_rate,
                               h)
            dov = jnp.where(keep, dov / (1.0 - dropout_rate), 0.0)
        ds = p * (dov - delta_ref[0, 0, q][:, None])
        dq_acc[q] += _dot(ds, k_blk, _NN)

    _each_part(causal, iq, ik, bq, bk, _compute, by="q")

    @pl.when(ik == nk - 1)
    def _writeout():
        dq_ref[0] = (dq_acc[:] * scale).astype(dq_ref.dtype)


def _bwd_dkv_kernel(mask_ref, seed_ref, q_ref, k_ref, v_ref, do_ref,
                    lse_ref, delta_ref, dk_ref, dv_ref, dk_acc, dv_acc,
                    *, scale, causal, bq, bk, nq, dropout_rate, h,
                    has_mask):
    """Works on TRANSPOSED scores, (nk, nq) with the keys down the rows:
    ``dv += p^T @ do`` and ``dk += ds^T @ q`` are then plain products
    with nothing to transpose, and the per-query ``lse`` and ``delta``
    broadcast down the rows as they arrive, laid along the lanes."""
    iq = pl.program_id(2)
    ik = pl.program_id(1)
    bh = pl.program_id(0)  # hoisted out of the pl.when body

    @pl.when(iq == 0)
    def _init():
        dk_acc[:] = jnp.zeros_like(dk_acc)
        dv_acc[:] = jnp.zeros_like(dv_acc)

    def _compute(masked, q0, k0, q, k):
        q_blk = q_ref[0, q]
        do = do_ref[0, q]
        p = _recompute_p(
            _scores(q_blk, k_ref[0, k],
                    mask_ref[0, 0, k] if has_mask else None,
                    masked, q0, k0, scale, transposed=True),
            lse_ref[0, :, q])                            # (nk, nq)
        p_v = p
        if dropout_rate > 0.0:
            keep = _keep_block(seed_ref, bh, q0, k0, *p.shape[::-1],
                               dropout_rate, h, transposed=True)
            p_v = jnp.where(keep, p / (1.0 - dropout_rate), 0.0)
        dv_acc[k] += _dot(p_v, do, _NN)                  # (nk, D)
        dov = _dot(v_ref[0, k], do, _NT)                 # (nk, nq)
        if dropout_rate > 0.0:
            dov = jnp.where(keep, dov / (1.0 - dropout_rate), 0.0)
        ds = p * (dov - delta_ref[0, :, q])
        dk_acc[k] += _dot(ds, q_blk, _NN)

    _each_part(causal, iq, ik, bq, bk, _compute, by="k")

    @pl.when(iq == nq - 1)
    def _writeout():
        dk_ref[0] = (dk_acc[:] * scale).astype(dk_ref.dtype)
        dv_ref[0] = dv_acc[:].astype(dv_ref.dtype)


# ---------------------------------------------------------------------------
# host-side drivers
# ---------------------------------------------------------------------------

def _layout(x):
    """(B, S, H, D) -> (B*H, S, D)."""
    b, s, h, d = x.shape
    return jnp.transpose(x, (0, 2, 1, 3)).reshape(b * h, s, d)


def _unlayout(x, b, h):
    bh, s, d = x.shape
    return jnp.transpose(x.reshape(b, h, s, d), (0, 2, 1, 3))


def _pad_seq(x, block):
    s = x.shape[1]
    pad = _cdiv(s, block) * block - s
    if pad:
        x = jnp.pad(x, ((0, 0), (0, pad), (0, 0)))
    return x


def _specs(bq, bk, d, h, k_major=False):
    """Common BlockSpecs for (BH, S, D)-laid-out operands, for a grid
    ``(bh, iq, ik)`` or, ``k_major``, ``(bh, ik, iq)``.

    Per-row scalars (mask, lse, delta) travel as 3-D (B|BH, 1, S): TPU
    lowering requires the block's last two dims to be (divisible by
    (8, 128)) or equal to the array dims, so the singleton must sit in the
    penultimate *array* dim — a 2-D (BH, S) array with block (1, bq)
    fails that check on hardware (it passed silently in interpret mode)."""
    def at(index):
        return (lambda b, j, i: index(b, i, j)) if k_major else index

    q_spec = pl.BlockSpec((1, bq, d), at(lambda b, i, j: (b, i, 0)))
    k_spec = pl.BlockSpec((1, bk, d), at(lambda b, i, j: (b, j, 0)))
    mask_spec = pl.BlockSpec((1, 1, bk), at(lambda b, i, j: (b // h, 0, j)))
    row_spec = pl.BlockSpec((1, 1, bq), at(lambda b, i, j: (b, 0, i)))
    return q_spec, k_spec, mask_spec, row_spec


_STATIC = ("scale", "causal", "bq", "bk", "h", "interpret", "dropout_rate",
           "has_mask")


@functools.partial(jax.jit, static_argnames=_STATIC)
def _fwd_pallas(q3, k3, v3, mask, seed, *, scale, causal, bq, bk, h,
                interpret, dropout_rate=0.0, has_mask=True):
    bh, sq, d = q3.shape
    sk = k3.shape[1]
    nq, nk = sq // bq, sk // bk
    lanes = 128
    q_spec, k_spec, mask_spec, row_spec = _specs(bq, bk, d, h)
    seed_spec = pl.BlockSpec(memory_space=pltpu.SMEM)
    vma = union_vma(q3, k3, v3, mask)
    o, lse = pl.pallas_call(
        functools.partial(_fwd_kernel, scale=scale, causal=causal,
                          bq=bq, bk=bk, nk=nk, dropout_rate=dropout_rate,
                          h=h, has_mask=has_mask),
        grid=(bh, nq, nk),
        in_specs=[mask_spec, seed_spec, q_spec, k_spec, k_spec],
        out_specs=[q_spec, row_spec],
        out_shape=[jax.ShapeDtypeStruct((bh, sq, d), q3.dtype, vma=vma),
                   jax.ShapeDtypeStruct((bh, 1, sq), jnp.float32, vma=vma)],
        scratch_shapes=[pltpu.VMEM((bq, d), jnp.float32),
                        pltpu.VMEM((bq, lanes), jnp.float32),
                        pltpu.VMEM((bq, lanes), jnp.float32)],
        interpret=interpret,
        name="_fwd_kernel",
    )(mask[:, None, :], seed, q3, k3, v3)
    return o, lse[:, 0, :]                           # (BH, Sq)


@functools.partial(jax.jit, static_argnames=_STATIC)
def _bwd_dq_pallas(q3, k3, v3, do3, lse, delta, mask, seed, *, scale,
                   causal, bq, bk, h, interpret, dropout_rate=0.0,
                   has_mask=True):
    bh, sq, d = q3.shape
    nq, nk = sq // bq, k3.shape[1] // bk
    q_spec, k_spec, mask_spec, row_spec = _specs(bq, bk, d, h)
    seed_spec = pl.BlockSpec(memory_space=pltpu.SMEM)
    mask3, lse3, delta3 = mask[:, None, :], lse[:, None, :], delta[:, None, :]
    vma = union_vma(q3, k3, v3, do3, lse3, delta3, mask3)
    return pl.pallas_call(
        functools.partial(_bwd_dq_kernel, scale=scale, causal=causal,
                          bq=bq, bk=bk, nk=nk, dropout_rate=dropout_rate,
                          h=h, has_mask=has_mask),
        grid=(bh, nq, nk),
        in_specs=[mask_spec, seed_spec, q_spec, k_spec, k_spec, q_spec,
                  row_spec, row_spec],
        out_specs=q_spec,
        out_shape=jax.ShapeDtypeStruct((bh, sq, d), q3.dtype, vma=vma),
        scratch_shapes=[pltpu.VMEM((bq, d), jnp.float32)],
        interpret=interpret,
        name="_bwd_dq_kernel",
    )(mask3, seed, q3, k3, v3, do3, lse3, delta3)


@functools.partial(jax.jit, static_argnames=_STATIC)
def _bwd_dkv_pallas(q3, k3, v3, do3, lse, delta, mask, seed, *, scale,
                    causal, bq, bk, h, interpret, dropout_rate=0.0,
                    has_mask=True):
    bh, sq, d = q3.shape
    sk = k3.shape[1]
    nq, nk = sq // bq, sk // bk
    seed_spec = pl.BlockSpec(memory_space=pltpu.SMEM)
    mask3, lse3, delta3 = mask[:, None, :], lse[:, None, :], delta[:, None, :]
    vma = union_vma(q3, k3, v3, do3, lse3, delta3, mask3)
    # the grid is k-major: q innermost, so the scratch carries over q
    q_spec, k_spec, mask_spec, row_spec = _specs(bq, bk, d, h, k_major=True)
    return pl.pallas_call(
        functools.partial(_bwd_dkv_kernel, scale=scale, causal=causal,
                          bq=bq, bk=bk, nq=nq, dropout_rate=dropout_rate,
                          h=h, has_mask=has_mask),
        grid=(bh, nk, nq),
        in_specs=[mask_spec, seed_spec, q_spec, k_spec, k_spec, q_spec,
                  row_spec, row_spec],
        out_specs=[k_spec, k_spec],
        out_shape=[jax.ShapeDtypeStruct((bh, sk, d), k3.dtype, vma=vma),
                   jax.ShapeDtypeStruct((bh, sk, d), v3.dtype, vma=vma)],
        scratch_shapes=[pltpu.VMEM((bk, d), jnp.float32),
                        pltpu.VMEM((bk, d), jnp.float32)],
        interpret=interpret,
        name="_bwd_dkv_kernel",
    )(mask3, seed, q3, k3, v3, do3, lse3, delta3)


def _bwd_pallas(q3, k3, v3, do3, o3, lse, mask, seed, *, dlse=None,
                **static):
    """``dq`` and ``(dk, dv)`` from their two kernels."""
    delta = jnp.sum(do3.astype(jnp.float32) * o3.astype(jnp.float32),
                    axis=-1)                         # (BH, Sq)
    if dlse is not None:
        # lse cotangent folds into delta: d lse/d s = p (softmax probs),
        # so ds = p*(dov - delta + dlse) — i.e. delta' = delta - dlse,
        # reusing the kernels unchanged
        delta = delta - dlse.astype(jnp.float32)
    args = (q3, k3, v3, do3, lse, delta, mask, seed)
    return (_bwd_dq_pallas(*args, **static),
            *_bwd_dkv_pallas(*args, **static))


# ---------------------------------------------------------------------------
# public entry
# ---------------------------------------------------------------------------

def _reference(q, k, v, kv_mask, causal, scale, return_lse: bool = False,
               dropout_rate: float = 0.0, seed=None):
    """Pure-jnp oracle (fp32 softmax), shapes (B, S, H, D).

    With ``return_lse`` also returns the per-row log-sum-exp (B, H, Sq)
    fp32 (NEG_INF for fully-masked rows) — the merge statistic for
    blockwise/ring combination.  Dropout uses the SAME deterministic
    hash mask as the kernels (``_dropout_keep``), so kernel-vs-oracle
    parity holds at any fixed (rate, seed)."""
    s = _einsum("bqhd,bkhd->bhqk", q.astype(jnp.float32),
                   k.astype(jnp.float32)) * scale
    if kv_mask is not None:
        s = s + kv_mask[:, None, None, :].astype(jnp.float32)
    if causal:
        pos_q = jnp.arange(q.shape[1])
        pos_k = jnp.arange(k.shape[1])
        s = jnp.where((pos_q[:, None] >= pos_k[None, :])[None, None],
                      s, NEG_INF)
    m = jnp.max(s, axis=-1, keepdims=True)
    valid = m > NEG_INF / 2
    p = jnp.exp(s - m)
    den = jnp.sum(p, axis=-1, keepdims=True)
    probs = p / jnp.maximum(den, 1e-30)
    if dropout_rate > 0.0:
        b, sq, h, _ = q.shape
        keep = keep_from_seed(seed, b, h, jnp.arange(sq),
                              jnp.arange(k.shape[1]), dropout_rate)
        probs = jnp.where(keep, probs / (1.0 - dropout_rate), 0.0)
    out = _einsum("bhqk,bkhd->bqhd", probs, v.astype(jnp.float32))
    out = out * jnp.transpose(valid, (0, 2, 1, 3)).astype(out.dtype)
    out = out.astype(q.dtype)
    if not return_lse:
        return out
    lse = jnp.where(valid[..., 0],
                    m[..., 0] + jnp.log(jnp.maximum(den[..., 0], 1e-30)),
                    NEG_INF)                         # (B, H, Sq)
    return out, lse


@functools.partial(jax.custom_vjp,
                   nondiff_argnums=(5, 6, 7, 8, 9, 10, 11))
def _flash_lse(q, k, v, mask, seed, causal, scale, bq, bk, interpret,
               dropout_rate, has_mask):
    """Returns ``(out, lse)`` with lse (B, H, Sq) fp32 — differentiable
    in BOTH outputs (the lse cotangent folds into the kernels' delta
    input, see ``_bwd_pallas``).  ``mask`` is always a concrete (B, Sk)
    fp32 array (zeros when the caller had none: ``has_mask`` False, and
    no kernel reads it unless keys were padded) and ``seed`` the (5,)
    int32 :func:`seed_array` (zeros when dropout is off) so the VJP can
    return well-typed cotangents."""
    (out, lse), _ = _flash_lse_fwd(q, k, v, mask, seed, causal, scale,
                                   bq, bk, interpret, dropout_rate,
                                   has_mask)
    return out, lse


def _flash_lse_fwd(q, k, v, mask, seed, causal, scale, bq, bk, interpret,
                   dropout_rate, has_mask):
    b, sq, h, d = q.shape
    sk = k.shape[1]
    q3 = _pad_seq(_layout(q), bq)
    k3 = _pad_seq(_layout(k), bk)
    v3 = _pad_seq(_layout(v), bk)
    sk_pad = k3.shape[1]
    mask_p = mask
    if sk_pad != sk:  # padded keys must never win the softmax
        mask_p = jnp.pad(mask, ((0, 0), (0, sk_pad - sk)),
                         constant_values=NEG_INF)
    o3, lse = _fwd_pallas(q3, k3, v3, mask_p, seed, scale=scale,
                          causal=causal, bq=bq, bk=bk, h=h,
                          interpret=interpret, dropout_rate=dropout_rate,
                          has_mask=has_mask or sk_pad != sk)
    out = _unlayout(o3[:, :sq], b, h)
    lse_pub = lse[:, :sq].reshape(b, h, sq)
    return (out, lse_pub), (q3, k3, v3, o3, lse, mask_p, seed, b, h, sq,
                            sk)


def _flash_lse_bwd(causal, scale, bq, bk, interpret, dropout_rate, has_mask,
                   res, g):
    do, dlse = g
    q3, k3, v3, o3, lse, mask_p, seed, b, h, sq, sk = res
    sq_pad = q3.shape[1]
    do3 = _pad_seq(_layout(do), bq)
    dlse3 = None
    if dlse is not None:
        dlse3 = dlse.astype(jnp.float32).reshape(b * h, sq)
        if sq_pad != sq:
            dlse3 = jnp.pad(dlse3, ((0, 0), (0, sq_pad - sq)))
    dq3, dk3, dv3 = _bwd_pallas(q3, k3, v3, do3, o3, lse, mask_p, seed,
                                dlse=dlse3, scale=scale, causal=causal,
                                bq=bq, bk=bk, h=h, interpret=interpret,
                                dropout_rate=dropout_rate,
                                has_mask=has_mask or k3.shape[1] != sk)
    dq = _unlayout(dq3[:, :sq], b, h)
    dk = _unlayout(dk3[:, :sk], b, h)
    dv = _unlayout(dv3[:, :sk], b, h)
    dmask = jnp.zeros((b, sk), jnp.float32)  # masks are not trained
    dseed = jnp.zeros_like(seed)
    return dq, dk, dv, dmask, dseed


_flash_lse.defvjp(_flash_lse_fwd, _flash_lse_bwd)


# out-only variant: same fwd/bwd machinery with the lse output discarded
# (one implementation to keep in sync, not two)
@functools.partial(jax.custom_vjp,
                   nondiff_argnums=(5, 6, 7, 8, 9, 10, 11))
def _flash(q, k, v, mask, seed, *static):
    return _flash_fwd(q, k, v, mask, seed, *static)[0]


def _flash_fwd(q, k, v, mask, seed, *static):
    (out, _), res = _flash_lse_fwd(q, k, v, mask, seed, *static)
    return out, res


def _flash_bwd(*args):
    *static, res, do = args
    return _flash_lse_bwd(*static, res, (do, None))


_flash.defvjp(_flash_fwd, _flash_bwd)


# XLA/Pallas crossover for the use_pallas=None auto path.  The sweep it
# came from is in records PR 21 deleted (the Pallas kernel LOSING to XLA
# attention inside BERT at s128 and at best level at s512, winning at
# gpt s1024 causal and at 16k); no cell of the benchmark runs at or
# under it, and a re-measure belongs to the encoder cell of ``PERF.md``
# section 7.  Auto routes sequences of at most this length to the XLA
# reference path.
FLASH_AUTO_MIN_SEQ = 512


def _auto_use_pallas(sq: int, sk: int, dropout_rate: float = 0.0) -> bool:
    """The decision table for ``use_pallas=None`` ON TPU (off-TPU auto
    is already the jnp path): Pallas iff the longer sequence side
    exceeds :data:`FLASH_AUTO_MIN_SEQ`, OR dropout is active — in-kernel
    dropout never materializes the (Sq, Sk) probs tensor in HBM, which
    beats raw short-sequence throughput.  Explicit ``use_pallas=True/
    False`` bypasses this entirely."""
    if dropout_rate > 0.0:
        return True
    return max(sq, sk) > FLASH_AUTO_MIN_SEQ


def _default_block(s: int, d: int, itemsize: int) -> int:
    """The block of a side of length ``s`` at heads of ``d`` elements of
    ``itemsize`` bytes, for all three kernels: the widest up to 1,024
    (up to 512 where a head's row is over 256 bytes) that does not pad
    much beyond the 128 grain.

    From a sweep on one TPU v5e (PR 28; ``tools/perf_sweep.py::
    sweep_flash``, each kernel alone on the device's clock, ``(block_q,
    block_k)`` over {256, 512, 1024} squared, bfloat16, ``d`` 64 and
    128, ``s`` 1,024, 2,048 and 4,096, causal and not; 128 too at the
    training cell's shape): 1,024 x 1,024 won for the forward, ``dq``
    and ``dkv`` alike at every shape but one tie, so the kernels share
    one choice and it depends on neither ``causal`` nor the kernel.  A
    grid step costs what it costs whatever its size (at 8 rows of 1,024
    and 16 heads of 64 the forward took 1.09 ms at 512 x 512 and 0.59
    at 1,024; ``dq`` 0.55 and 0.39; ``dkv`` 0.65 and 0.56).  Blocks of
    1,024 were compiled for a described v5e in float32 and bfloat16, at
    ``d`` 64 and 128, with a key mask and with dropout: only ``dkv`` at
    128 in float32 with dropout runs out of VMEM, hence the 256 bytes.

    The whole 128-padded length when that fits the cap, else the
    largest candidate that DIVIDES it, else the widest whose re-padding
    stays <= 1/8 of the work (1664 = 13*128 has no wide divisor; a 512
    block at 768 would run 1.78x the real FLOPs non-causally and stays
    rejected).  The kernels mask padded keys exactly (``_pad_seq`` +
    the padded-key NEG_INF mask)."""
    cap = 1024 if d * itemsize <= 256 else 512
    sp = _cdiv(s, 128) * 128
    if sp <= cap:
        return sp
    wide = [b for b in (1024, 896, 768, 640, 512, 384, 320, 256, 192)
            if b <= cap]
    for b in wide:
        if sp % b == 0:
            return b
    for b in wide:
        if _cdiv(sp, b) * b - sp <= sp // 8:
            return b
    return 128


def flash_attention(q, k, v, *, kv_mask: Optional[jax.Array] = None,
                    causal: bool = False, scale: Optional[float] = None,
                    block_q: Optional[int] = None,
                    block_k: Optional[int] = None,
                    use_pallas: Optional[bool] = None,
                    interpret: Optional[bool] = None,
                    return_lse: bool = False,
                    dropout_rate: float = 0.0,
                    dropout_seed=None,
                    dropout_offsets=None):
    """Memory-efficient exact attention.

    Args:
      q, k, v: (B, S, H, D); q and k/v sequence lengths may differ.
      kv_mask: optional (B, Sk) additive key mask (0 keep / NEG_INF drop).
      causal: causal masking on global positions.
      scale: logit scale, default 1/sqrt(D).
      block_q, block_k: VMEM tile sizes (multiples of 128 recommended),
        for all three kernels.  Default None = adaptive
        (``_default_block``: by length, head size and dtype, from a
        sweep on one v5e).
      use_pallas: None = auto — Pallas kernels on TPU when the longer
        sequence side exceeds ``FLASH_AUTO_MIN_SEQ`` (512; at and below
        it XLA attention measured faster) or dropout is
        active, jnp/XLA otherwise and always off-TPU.  True/False
        force the path.
      interpret: force Pallas interpret mode (defaults to not-on-TPU).
      return_lse: also return the per-row log-sum-exp (B, H, Sq) fp32
        (NEG_INF for fully-masked rows) — the statistic for combining
        blockwise partial attentions (ring attention's merge); both
        outputs are differentiable.
      dropout_rate: attention-probability dropout (applied to the
        normalized probs IN-KERNEL — no (Sq, Sk) mask tensor in HBM).
        The mask is a deterministic hash of (seed, batch*head, q pos,
        k pos) regenerated identically in forward, backward, and the
        jnp oracle (``_dropout_keep``); lse stays the un-dropped
        statistic.
      dropout_seed: int32 scalar (Python int or traced) — REQUIRED when
        dropout_rate > 0.  The mask is a pure function of (seed, bh, q,
        k), so the seed must be distinct per training step AND per
        attention layer — a single per-step seed shared by N layers
        would drop the same positions in every layer.  Derive per-layer
        seeds with ``jax.random.fold_in``/``randint`` from a per-layer
        rng (flax's ``make_rng('dropout')`` folds the module path in
        automatically — what ``models.bert.BertSelfAttention`` does).
      dropout_offsets: optional ``(row_offset, col_offset, head_offset,
        num_heads_total)`` int32 scalars (traced OK) translating this
        call's LOCAL coordinates to GLOBAL ones, so sharded callers drop
        exactly what the single-device call would: ring attention passes
        its q-shard/KV-hop offsets, Ulysses its head-shard offset.
        Default ``(0, 0, 0, H)``.

    Differentiable (custom VJP with recompute — no (Sq, Sk) tensor ever
    hits HBM in either pass).
    """
    if scale is None:
        scale = 1.0 / math.sqrt(q.shape[-1])
    dropout_rate = float(dropout_rate)
    if not 0.0 <= dropout_rate < 1.0:
        raise ValueError(f"dropout_rate must be in [0, 1); got "
                         f"{dropout_rate}")
    if dropout_rate > 0.0 and dropout_seed is None:
        raise ValueError(
            "flash_attention(dropout_rate>0) requires dropout_seed — a "
            "per-step int32 scalar (a fixed implicit seed would freeze "
            "the dropout mask across steps)")
    if dropout_seed is None:
        seed = jnp.zeros((5,), jnp.int32)
    else:
        seed = seed_array(dropout_seed, dropout_offsets,
                          num_heads=q.shape[2])
    # partial-manual shard_map regions (pipelined TP) auto-partition
    # every op — Mosaic calls are rejected there, jnp oracle instead
    use = pallas_auto_gate(use_pallas)
    if use and use_pallas is None and not _auto_use_pallas(
            q.shape[1], k.shape[1], dropout_rate):
        # short-sequence auto fallback: XLA attention wins below the
        # crossover (FLASH_AUTO_MIN_SEQ)
        use = False
    if not use:
        return _reference(q, k, v, kv_mask, causal, scale,
                          return_lse=return_lse,
                          dropout_rate=dropout_rate, seed=seed)
    if interpret is None:
        interpret = not on_tpu()
    d, itemsize = q.shape[3], q.dtype.itemsize
    if block_q is None:
        block_q = _default_block(q.shape[1], d, itemsize)
    if block_k is None:
        block_k = _default_block(k.shape[1], d, itemsize)
    mask = (jnp.zeros((q.shape[0], k.shape[1]), jnp.float32)
            if kv_mask is None else kv_mask.astype(jnp.float32))
    fn = _flash_lse if return_lse else _flash
    return fn(q, k, v, mask, seed, causal, float(scale), int(block_q),
              int(block_k), bool(interpret), dropout_rate,
              kv_mask is not None)


def bias_to_kv_mask(bias):
    """Collapse a (B, 1, 1, Sk) additive key-position bias (BERT padding
    masks) to (B, Sk). Rejects query- or head-dependent biases — silently
    keeping only head 0 / query row 0 would corrupt the attention.

    Shared contract of every fused-attention adapter (flash, ring,
    Ulysses)."""
    if bias is None:
        return None
    if bias.ndim != 4 or bias.shape[1] != 1 or bias.shape[2] != 1:
        raise ValueError(
            "fused-attention adapters support key-position-only biases "
            f"of shape (B, 1, 1, Sk); got {bias.shape}. Query-/head-"
            "dependent biases (relative position, custom causal) need the "
            "explicit attention API (use `causal=` for causal masking).")
    return bias[:, 0, 0, :].astype(jnp.float32)


def dropout_params(dropout_fn):
    """Extract in-kernel dropout params from an ``attention_fn``-contract
    ``dropout_fn``.

    ``models.bert.BertSelfAttention`` attaches ``.rate`` (static float)
    and ``.seed`` (per-step traced int32) to the dropout closure it
    passes to attention adapters; fused kernels consume those instead of
    calling the closure (which materializes the (Sq, Sk) probs).
    Returns ``(rate, seed)`` or raises if the closure carries no params
    (a plain function can only be applied to materialized probs, which
    defeats the fused kernel).
    """
    if dropout_fn is None:
        return 0.0, None
    rate = getattr(dropout_fn, "rate", None)
    seed = getattr(dropout_fn, "seed", None)
    if rate is None or seed is None:
        raise NotImplementedError(
            "this dropout_fn carries no (rate, seed) annotation, and a "
            "plain probs->probs dropout closure cannot run inside the "
            "fused kernel (the probs are never materialized). Attach "
            "`dropout_fn.rate` / `dropout_fn.seed` (see "
            "models.bert.BertSelfAttention) or set "
            "attention_probs_dropout_prob=0.")
    return float(rate), seed


def make_flash_attention(*, causal: bool = False, **kwargs):
    """Adapter with the ``attention_fn(q, k, v, bias, dropout_fn)``
    signature of ``models.bert.dot_product_attention``; bias must be a
    key-position-only (B, 1, 1, Sk) additive mask.  Attention dropout
    runs IN-KERNEL via the (rate, seed) annotation on ``dropout_fn``
    (see :func:`dropout_params`)."""

    def attention_fn(q, k, v, bias=None, dropout_fn=None):
        rate, seed = dropout_params(dropout_fn)
        return flash_attention(q, k, v, kv_mask=bias_to_kv_mask(bias),
                               causal=causal, dropout_rate=rate,
                               dropout_seed=seed, **kwargs)

    return attention_fn
