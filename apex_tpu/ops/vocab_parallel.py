"""Vocab-parallel ops for a vocab-sharded LM head: the Megatron-style
training loss (:func:`vocab_parallel_lm_loss`) and the serving-side
greedy sampler (:func:`vocab_parallel_sample` /
:func:`vocab_parallel_argmax`) — nothing ``(…, V)``-shaped ever
crosses the model axis in either.

With ``parallel.gpt_tp_rules`` the tied ``wte`` shards its vocab dim,
so each device can compute only its ``(B, S, V/n)`` logits slice — but
a plain ``softmax_cross_entropy(logits, ...)`` forces XLA to all-gather
the full ``(B, S, V)`` fp32 logits first, and at GPT-2 scale that
buffer dominates the step's activations (B=16, S=1024, V=50257 fp32 is
~3.2 GB — bigger than the model).  The classic fix (Megatron-LM's
``vocab_parallel_cross_entropy``; re-derived here for shard_map — no
reference-code reuse, the reference library has no TP at all) needs
only three scalar-ish collectives instead:

- global max over vocab  = ``pmax``  of the local max  (stability),
- global logsumexp       = ``psum``  of the local exp-sum,
- the target's logit     = ``psum``  of the owning shard's gather.

Loss per token = logsumexp - target_logit; everything that crosses the
axis is (B, S), never (B, S, V).  The implementation is partial-manual:
``jax.shard_map`` binds ONLY the model axis, so batch/sequence sharding
(dp/sp) stays GSPMD-automatic and composes unchanged.

The backward pass follows from the same pieces (softmax(local) minus
the one-hot on the owning shard), so plain autodiff through the
shard_map is both correct and memory-shaped like the forward — the
full-vocab softmax never exists either.

KNOWN LIMITATION (shared with ``PipelinedBert`` ``tp_axis``):
half-precision compute inside a partial-manual shard_map region trips
this jax build's XLA **CPU** backend ("Invalid binary instruction
opcode copy"); fp32 hidden works everywhere, bf16 hidden needs the TPU
backend (not yet re-run on the current installation: ``CHANGES.md`` PR 21,
``ROADMAP.md`` Speed item 10).
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax import lax
from jax.sharding import PartitionSpec as P

from apex_tpu.ops.sampling import sampling_noise

# per-shard candidate width for the stochastic sampler's threshold
# merge: each shard nominates its local top-C values, the merge is the
# only thing (beyond (B,)-shaped scalars) that crosses the model axis.
# Exactness holds while the kept set lives inside the global top-C
# (always true for top_k <= C; true for top_p whenever the nucleus
# fits in C tokens — the realistic serving regime by orders of
# magnitude).  top_k is CLAMPED to C on the sharded path (documented).
# Both caps are this path's alone: the unsharded sampler selects its
# thresholds over the whole row (``ops/sampling.py::_thresholds``) and
# honors any k and any nucleus; 32 collectives a launch would be the
# wrong cure here.
SHARD_CANDIDATES = 128


def _shard_map(f, mesh, in_specs, out_specs, axis: str):
    """Partial-manual ``shard_map``: only ``axis`` is manual — dp/sp
    sharding stays GSPMD-automatic."""
    return jax.shard_map(f, mesh=mesh, in_specs=in_specs,
                         out_specs=out_specs, axis_names={axis},
                         check_vma=False)


@functools.lru_cache(maxsize=32)
def _build_sample(mesh, axis, ndim, true_vocab):
    """Cached jitted vocab-parallel greedy sampler for rank-``ndim``
    logits: per-shard argmax + finite guard, then one scalar-ish
    cross-shard reduction each — the serving analog of the loss above
    (nothing (…, V)-shaped crosses the axis).  ``true_vocab`` is None
    for an exactly-divisible vocab, else the real width (columns past
    it are -inf padding the caller appended: excluded from the argmax
    candidates and the finite check).  Same caching discipline as
    :func:`_build`: jit keys on the function object, so eager per-step
    callers must hit one build per (mesh, axis, rank, pad)."""
    n = mesh.shape[axis]

    def per_shard(lg):
        # sub-fp32 logits compare exactly after an (exact) upcast; it
        # also sidesteps the half-precision-inside-partial-manual-
        # shard_map XLA:CPU limitation noted in the module docstring
        if jnp.issubdtype(lg.dtype, jnp.floating) \
                and jnp.finfo(lg.dtype).bits < 32:
            lg = lg.astype(jnp.float32)
        vshard = lg.shape[-1]
        v_pad = vshard * n            # padded global vocab
        v_true = true_vocab if true_vocab is not None else v_pad
        off = lax.axis_index(axis) * vshard
        gidx = (lax.broadcasted_iota(jnp.int32, lg.shape, lg.ndim - 1)
                + off)                # this shard's GLOBAL token ids
        valid = gidx < v_true
        gmax = lax.pmax(jnp.max(lg, axis=-1, keepdims=True), axis)
        # lowest-global-id tie rule in two exact stages: min global id
        # among this shard's valid maxima, then min across shards.  A
        # row whose global max is NaN matches nothing and clamps to
        # the last TRUE id — exactly ops.greedy_argmax's rule (such
        # rows are always flagged non-finite and never consumed).
        cand = jnp.min(jnp.where((lg == gmax) & valid, gidx,
                                 jnp.int32(v_pad)), axis=-1)
        ids = jnp.minimum(lax.pmin(cand, axis),
                          v_true - 1).astype(jnp.int32)
        # jnp.max propagates NaN but lax.pmax does not: a NaN on one
        # shard must poison the whole row's max exactly as it does in
        # the unsharded reduction, clamping the row to the last id
        row_nan = lax.pmax(
            jnp.any(jnp.isnan(lg) & valid, axis=-1).astype(jnp.int32),
            axis) > 0
        ids = jnp.where(row_nan, jnp.int32(v_true - 1), ids)
        fin = lax.pmin(
            jnp.all(jnp.isfinite(lg) | ~valid, axis=-1)
            .astype(jnp.int32), axis).astype(bool)
        return ids, fin

    spec = P(*([None] * (ndim - 1) + [axis]))
    return jax.jit(_shard_map(per_shard, mesh, (spec,), (P(), P()),
                              axis))


def vocab_parallel_sample(logits, mesh, axis: str = "model"):
    """Greedy argmax + finite-row guard over vocab-sharded logits —
    the serving engine's fused on-device sampling for a tensor-parallel
    LM head (``serving.engine.DecodeEngine(mesh=...)``).

    With ``parallel.gpt_tp_rules`` the tied head's logits come out of
    the matmul sharded on their vocab dim; a plain
    ``ops.greedy_argmax`` would force GSPMD to all-gather the full
    ``(…, V)`` block first.  This runs the argmax and the finite guard
    per shard (each shard reduces over its ``V/n`` slice with GLOBAL
    token ids) and crosses the axis with three (…,)-shaped collectives
    (pmax of the shard maxima, pmin of the candidate ids, pmin of the
    finite flags) — never the logits.

    Semantics are bit-exact :func:`ops.greedy_argmax` +
    :func:`ops.finite_rows` by construction, INCLUDING exact ties that
    straddle shard boundaries: each shard nominates the lowest global
    id among its rows' global maxima and the cross-shard pmin picks
    the lowest nominee — ``np.argmax``'s first-maximum rule, which the
    speculative-acceptance comparison relies on
    (``tests/L0/test_vocab_parallel.py``).

    ``logits``: (…, V) floating point.  A vocab that does not divide
    the ``axis`` size is padded here with -inf columns (excluded from
    both the argmax candidates and the finite check, so the result is
    exactly the unpadded one — the serving twin of the loss's
    ``true_vocab`` masking).  Returns ``(ids (…,) int32,
    finite (…,) bool)``, replicated.
    """
    v = logits.shape[-1]
    n = mesh.shape[axis]
    pad, true_vocab = (-v) % n, None
    if pad:
        true_vocab = v
        widths = [(0, 0)] * (logits.ndim - 1) + [(0, pad)]
        logits = jnp.pad(logits, widths, constant_values=-jnp.inf)
    return _build_sample(mesh, axis, logits.ndim, true_vocab)(logits)


def vocab_parallel_argmax(logits, mesh, axis: str = "model"):
    """The ids half of :func:`vocab_parallel_sample` — sharded greedy
    argmax, bit-exact against :func:`ops.greedy_argmax` ties
    included.  (Under jit the unused finite guard is dead-code
    eliminated, so this costs nothing over the fused pair.)"""
    return vocab_parallel_sample(logits, mesh, axis)[0]


@functools.lru_cache(maxsize=32)
def _build_sample_tokens(mesh, axis, ndim, true_vocab):
    """Cached jitted vocab-parallel STOCHASTIC sampler for
    rank-``ndim`` logits — the no-gather serving twin of
    :func:`ops.sampling.sample_tokens` (``docs/serving.md``,
    "Stochastic sampling").  Per shard:

    - greedy rows run the exact :func:`_build_sample` lane (bit-exact
      argmax + finite guard, lowest-global-id ties);
    - stochastic rows compute the temperature-scaled local slice, each
      shard nominates its local top-``SHARD_CANDIDATES`` values, and
      ONE small ``all_gather`` merges the nominations so every shard
      derives the same global top-k / nucleus VALUE thresholds (the
      kth merged value; the nucleus boundary from the merged cumsum
      against the psum'd global normalizer).  The kept-set mask is
      then applied shard-locally, per-position counter-keyed Gumbel
      noise is generated from the SAME ``(V,)`` stream as the
      unsharded sampler (:func:`ops.sampling.sampling_noise` — noise
      is compute, not communication; each shard slices its own vocab
      range), and the winner crosses the axis through the existing
      three-(…,)-shaped-collective argmax pattern.

    Nothing ``(…, V)``-shaped ever crosses the model axis: the
    collectives are the candidate merge (``n x SHARD_CANDIDATES``
    values per row), two scalar reductions (global max, global
    exp-sum), and the argmax pmax/pmin pair.  ``true_vocab`` is None
    for an exactly-divisible vocab, else the real width (the -inf
    padding columns the caller appended are excluded from candidates,
    thresholds, the finite check, and the noise stream — the noise is
    generated at the TRUE width so sharded draws match unsharded ones
    bit-for-bit)."""
    n = mesh.shape[axis]

    def per_shard(lg, temp, tk, tp_, seed, pos):
        if jnp.issubdtype(lg.dtype, jnp.floating) \
                and jnp.finfo(lg.dtype).bits < 32:
            lg = lg.astype(jnp.float32)
        vshard = lg.shape[-1]
        v_pad = vshard * n
        v_true = true_vocab if true_vocab is not None else v_pad
        off = lax.axis_index(axis) * vshard
        gidx = (lax.broadcasted_iota(jnp.int32, lg.shape, lg.ndim - 1)
                + off)
        valid = gidx < v_true

        # -- greedy lane: byte-for-byte _build_sample ------------------
        gmax_raw = lax.pmax(jnp.max(lg, axis=-1, keepdims=True), axis)
        cand_g = jnp.min(jnp.where((lg == gmax_raw) & valid, gidx,
                                   jnp.int32(v_pad)), axis=-1)
        ids_g = jnp.minimum(lax.pmin(cand_g, axis),
                            v_true - 1).astype(jnp.int32)
        row_nan = lax.pmax(
            jnp.any(jnp.isnan(lg) & valid, axis=-1).astype(jnp.int32),
            axis) > 0
        ids_g = jnp.where(row_nan, jnp.int32(v_true - 1), ids_g)
        fin = lax.pmin(
            jnp.all(jnp.isfinite(lg) | ~valid, axis=-1)
            .astype(jnp.int32), axis).astype(bool)

        # -- stochastic lane -------------------------------------------
        t = jnp.maximum(temp, 1e-6)[..., None]
        scaled = jnp.where(valid, lg / t, -jnp.inf)
        c = min(vshard, SHARD_CANDIDATES)
        local_top = lax.top_k(scaled, c)[0]              # (…, C) desc
        cand = lax.all_gather(local_top, axis,
                              axis=lg.ndim - 1, tiled=True)
        merged = -jnp.sort(-cand, axis=-1)               # (…, nC) desc
        nc = merged.shape[-1]
        gmax = merged[..., :1]                           # global max
        z = lax.psum(
            jnp.sum(jnp.where(valid, jnp.exp(scaled - gmax), 0.0),
                    axis=-1), axis)
        k = jnp.clip(jnp.where(tk <= 0, 1, tk), 1, c)
        kth = jnp.take_along_axis(merged, (k - 1)[..., None], axis=-1)
        kth = jnp.where((tk <= 0)[..., None], -jnp.inf, kth)
        cum = jnp.cumsum(jnp.exp(merged - gmax), axis=-1) \
            / z[..., None]
        bnd = jnp.minimum(
            jnp.sum((cum < tp_[..., None]).astype(jnp.int32), axis=-1,
                    keepdims=True), nc - 1)
        pth = jnp.take_along_axis(merged, bnd, axis=-1)
        pth = jnp.where((tp_ >= 1.0)[..., None], -jnp.inf, pth)
        thresh = jnp.maximum(kth, pth)
        keep = valid & (scaled >= thresh)
        # the unsharded noise stream, generated at the TRUE vocab
        # width on every shard (identical bits), -inf-padded to the
        # padded width, then sliced to this shard's range
        g = sampling_noise(seed, pos, v_true)
        if v_pad > v_true:
            g = jnp.concatenate(
                [g, jnp.full(g.shape[:-1] + (v_pad - v_true,),
                             -jnp.inf, g.dtype)], axis=-1)
        g_loc = lax.dynamic_slice_in_dim(g, off, vshard, axis=-1)
        noisy = jnp.where(keep, scaled + g_loc, -jnp.inf)
        m = lax.pmax(jnp.max(noisy, axis=-1, keepdims=True), axis)
        cand_s = jnp.min(jnp.where((noisy == m) & keep, gidx,
                                   jnp.int32(v_pad)), axis=-1)
        ids_s = jnp.minimum(lax.pmin(cand_s, axis),
                            v_true - 1).astype(jnp.int32)

        ids = jnp.where(temp <= 0.0, ids_g, ids_s)
        return ids.astype(jnp.int32), fin

    vspec = P(*([None] * (ndim - 1) + [axis]))
    pspec = P()
    return jax.jit(_shard_map(
        per_shard, mesh,
        (vspec, pspec, pspec, pspec, pspec, pspec), (P(), P()), axis))


def vocab_parallel_sample_tokens(logits, temperature, top_k, top_p,
                                 seeds, positions, mesh,
                                 axis: str = "model"):
    """Stochastic sampling over vocab-sharded logits — the
    tensor-parallel twin of :func:`ops.sampling.sample_tokens`, fused
    into the serving engine's sampled programs so TP decode never
    materializes (or gathers) full logits for stochastic traffic
    either (``serving.engine.DecodeEngine(mesh=...)``).

    Semantics: greedy rows (``temperature <= 0``) are bit-exact
    :func:`vocab_parallel_sample` (itself bit-exact
    :func:`ops.greedy_argmax`); stochastic rows draw via the same
    counter-keyed Gumbel-max as the unsharded sampler, over the same
    value-threshold keep set, with the same per-position noise stream
    — so sharded and unsharded token streams agree, ties and all,
    whenever the kept set lives inside the global
    top-:data:`SHARD_CANDIDATES` (``tests/L0/test_sampling.py``
    asserts tp∈{2,4} parity).  Documented caps of the no-gather path:
    ``top_k`` clamps to :data:`SHARD_CANDIDATES`, and a nucleus wider
    than the merged candidate set truncates to it (both far outside
    the serving regime; the unsharded sampler is exact at any width).

    ``logits``: ``(…, V)`` floating point; params/seeds/positions
    ``(…,)`` as in :func:`ops.sampling.sample_tokens`.  A vocab that
    does not divide the ``axis`` size is padded here with -inf columns
    exactly like :func:`vocab_parallel_sample`.  Returns
    ``(ids (…,) int32, finite (…,) bool)``, replicated."""
    v = logits.shape[-1]
    n = mesh.shape[axis]
    pad, true_vocab = (-v) % n, None
    if pad:
        true_vocab = v
        widths = [(0, 0)] * (logits.ndim - 1) + [(0, pad)]
        logits = jnp.pad(logits, widths, constant_values=-jnp.inf)
    f = _build_sample_tokens(mesh, axis, logits.ndim, true_vocab)
    b = logits.shape[:-1]
    return f(logits,
             jnp.broadcast_to(temperature, b).astype(jnp.float32),
             jnp.broadcast_to(top_k, b).astype(jnp.int32),
             jnp.broadcast_to(top_p, b).astype(jnp.float32),
             jnp.broadcast_to(seeds, b).astype(jnp.int32),
             jnp.broadcast_to(positions, b).astype(jnp.int32))


@functools.lru_cache(maxsize=32)
def _build(mesh, axis, vshard, true_vocab, logits_dtype, has_mask):
    """Cached jitted kernel: eager per-batch callers (eval loops) must
    hit the jit cache, and jit keys on the function object — a closure
    rebuilt per call would retrace + recompile the shard_map every
    invocation."""

    def per_shard(h, w_local, ids, *mask_arg):
        # local logits slice: the matmul runs in the hidden's dtype
        # (bf16 under amp — same as the tied head, which casts wte at
        # apply), the reduction in fp32 (GPTLMHeadModel's
        # .astype(float32) policy)
        lg = jnp.einsum("bsh,vh->bsv", h,
                        w_local.astype(h.dtype)).astype(logits_dtype)
        if true_vocab is not None and true_vocab < vshard * mesh.shape[axis]:
            # padded-vocab rows must not leak into the logsumexp
            vids = (lax.axis_index(axis) * vshard
                    + jnp.arange(vshard))
            lg = jnp.where(vids[None, None, :] < true_vocab, lg, -1e9)
        lg = lg[:, :-1]                      # positions with a target
        tgt = ids[:, 1:]
        # stable logsumexp across shards: subtract the GLOBAL max
        # (detached — the standard stabilization, zero gradient)
        gmax = lax.pmax(lax.stop_gradient(jnp.max(lg, axis=-1)), axis)
        z = jnp.exp(lg - gmax[..., None])
        lse = jnp.log(lax.psum(z.sum(-1), axis)) + gmax
        # the target logit lives on exactly one shard
        off = lax.axis_index(axis) * vshard
        local_t = tgt - off
        owned = (local_t >= 0) & (local_t < vshard)
        picked = jnp.take_along_axis(
            lg, jnp.clip(local_t, 0, vshard - 1)[..., None], axis=-1
        )[..., 0]
        tgt_logit = lax.psum(jnp.where(owned, picked, 0.0), axis)
        per_tok = lse - tgt_logit
        if not has_mask:
            return per_tok.mean()
        keep = mask_arg[0][:, 1:].astype(per_tok.dtype)
        return (per_tok * keep).sum() / jnp.maximum(keep.sum(), 1.0)

    in_specs = (P(), P(axis, None), P()) + ((P(),) if has_mask else ())
    # jit-wrapped (inlined under an outer jit): an EAGER partial-manual
    # shard_map rejects inputs whose committed sharding names automatic
    # axes ("out_specs refers to 'data'"); under jit GSPMD owns them
    return jax.jit(_shard_map(per_shard, mesh, in_specs, P(), axis))


def vocab_parallel_lm_loss(hidden, wte, input_ids, mesh,
                           axis: str = "model",
                           attention_mask=None,
                           true_vocab: Optional[int] = None,
                           logits_dtype=jnp.float32):
    """Next-token LM loss from the FINAL hidden states and the
    vocab-sharded tied embedding, without materializing full logits.

    Args:
      hidden: (B, S, H) final-LN output (``GPTLMHeadModel``'s tensor
        just before ``wte.attend``); any dp/sp sharding stays
        automatic.
      wte: (V, H) tied embedding, placed ``P(axis, None)``
        (``parallel.gpt_tp_rules``).  V must divide the axis size.
      input_ids: (B, S) int tokens — same shift semantics as
        :func:`models.lm_loss` (predict t+1 from prefix <= t).
      mesh / axis: the mesh and its model-axis name.
      attention_mask: optional (B, S) 1/0; positions whose TARGET is
        padding are dropped, mean over kept positions — exactly
        :func:`models.lm_loss`.
      true_vocab: real vocabulary size when ``wte`` was PADDED to make
        V divide the axis (the Megatron ``make_vocab_size_divisible_by``
        move — GPT-2's 50257 divides nothing): logits of padding rows
        are masked to -inf so they cannot leak probability mass into
        the logsumexp, making the loss exactly the true-vocab loss.

    Returns the scalar loss; grads flow to ``hidden`` and ``wte``.
    """
    V = wte.shape[0]
    n = mesh.shape[axis]
    if V % n:
        raise ValueError(f"vocab {V} must divide the {axis!r} axis ({n})")
    f = _build(mesh, axis, V // n, true_vocab,
               jnp.dtype(logits_dtype).name,
               attention_mask is not None)
    args = (hidden, wte, input_ids) + (
        (attention_mask,) if attention_mask is not None else ())
    return f(*args)
