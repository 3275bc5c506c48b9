"""Pipeline parallelism over a mesh axis, TPU-native: GPipe and 1F1B.

The reference has no PP (SURVEY §2.3). The TPU formulation needs no
scheduler threads or p2p runtime: stages are laid out on a ``"pipe"``
mesh axis, the microbatch schedule is a ``lax.scan`` over ticks, and
stage-to-stage transfer is one ``ppermute`` hop per tick over ICI —
the whole pipeline is a single compiled SPMD program.  Two schedules:

- :func:`gpipe_spmd` / :func:`pipeline_apply` — differentiable GPipe;
  autodiff through scan + ppermute yields the reverse pipeline, XLA
  saves per-tick activations (memory grows with ``M``);
- :func:`onef1b_spmd` / :func:`onef1b_loss_and_grad` — hand-interleaved
  1F1B loss-and-grad with rematerialized backward; live stage inputs
  bounded by ``S`` regardless of ``M`` (the PipeDream-flush memory
  profile), same bubble fraction as GPipe.

Contract (classic GPipe):

- ``stage_fn(stage_params, x) -> y`` where ``x``/``y`` are an array or
  a PYTREE of arrays with identical structure and per-leaf shapes — all
  stages share one activation layout (transformer blocks, MLP stacks).
  Pytree activations carry per-example side inputs through the
  pipeline, e.g. ``(hidden, attention_bias)`` with the bias returned
  unchanged (see ``models.PipelinedBert``);
- stage parameters live STACKED with a leading stage dim ``(S, ...)``
  (build with ``jax.vmap(stage.init)`` over per-stage rngs), sharded
  ``P("pipe")`` so each device holds its own stage;
- the global batch is split into ``num_microbatches`` M; the schedule
  runs ``T = M + S - 1`` ticks with the usual bubble ``(S-1)/T``.

Use :func:`pipeline_apply` for the packaged shard_map wrapper, or
:func:`gpipe_spmd` directly inside your own shard_map when composing
with other axes (see ``tests/distributed/test_pipeline_parallel.py`` for a
(data, pipe) composition).
"""

from __future__ import annotations

from typing import Any, Callable

import jax
import jax.numpy as jnp
from jax import lax
from jax.sharding import Mesh, PartitionSpec as P

from apex_tpu.parallel.collectives import vary_like as _vary_like

Pytree = Any


def _unstack_and_microbatch(stacked_params_local: Pytree, x: Pytree,
                            m: int, axis_name: str, s: int):
    """Shared schedule prologue: validate the one-stage-per-device
    stacked layout and the shared batch dim, unstack this device's
    params, split the batch into microbatches.
    Returns ``(params, b, xs)``."""
    for leaf in jax.tree_util.tree_leaves(stacked_params_local):
        # each device must hold exactly ONE stage slice; a stacked
        # stage count that is a multiple of the axis size would
        # otherwise silently run only every k-th stage
        if leaf.shape[0] != 1:
            raise ValueError(
                f"stacked stage params have leading dim "
                f"{leaf.shape[0]} per device; the stage count must "
                f"equal the size of mesh axis {axis_name!r} ({s})")
    params = jax.tree_util.tree_map(lambda a: a[0], stacked_params_local)
    x_leaves = jax.tree_util.tree_leaves(x)
    b = x_leaves[0].shape[0]
    for leaf in x_leaves:
        if leaf.shape[0] != b:
            raise ValueError(
                "every activation leaf must share the batch dim; got "
                f"{[l.shape for l in x_leaves]}")
    assert b % m == 0, f"batch {b} must divide into {m} microbatches"
    xs = jax.tree_util.tree_map(
        lambda a: a.reshape((m, b // m) + a.shape[1:]), x)
    return params, b, xs


def gpipe_spmd(stage_fn: Callable, axis_name: str,
               num_microbatches: int):
    """Per-device GPipe body, to be called INSIDE ``shard_map`` with the
    stage axis ``axis_name``.

    Returns ``run(stacked_params_local, x)`` where
    ``stacked_params_local`` is this device's ``(1, ...)`` slice of the
    stacked stage params and ``x`` is the (replicated-per-pipe) global
    batch ``(B, ...)``; returns the pipeline output ``(B, ...)``,
    identical on every device of the axis (psum-combined).
    """

    def run(stacked_params_local: Pytree, x: Pytree) -> Pytree:
        s = lax.axis_size(axis_name)
        stage = lax.axis_index(axis_name)
        m = num_microbatches
        params, b, xs = _unstack_and_microbatch(
            stacked_params_local, x, m, axis_name, s)

        fwd_perm = [(i, i + 1) for i in range(s - 1)]

        def tick(x_buf, t):
            # stage 0 injects microbatch t (clipped; invalid ticks feed
            # garbage that never reaches the output window)
            inject = jax.tree_util.tree_map(
                lambda a: a[jnp.clip(t, 0, m - 1)], xs)
            x_in = jax.tree_util.tree_map(
                lambda i, buf: jnp.where(stage == 0, i, buf), inject, x_buf)
            y = stage_fn(params, x_in)
            x_next = jax.tree_util.tree_map(
                lambda a: lax.ppermute(a, axis_name, fwd_perm), y)
            return x_next, y

        # the carry crosses ppermute, so it is varying on the pipe axis;
        # the zeros init must carry the same vma type
        zero = jax.tree_util.tree_map(
            lambda a: _vary_like(jnp.zeros_like(a[0]),
                                 extra_axes=(axis_name,)), xs)
        _, ys = lax.scan(tick, zero, jnp.arange(m + s - 1))
        # microbatch j leaves the last stage at tick s-1+j

        def collect(leaf):
            valid = lax.dynamic_slice_in_dim(leaf, s - 1, m)
            out = jnp.where(stage == s - 1, valid, jnp.zeros_like(valid))
            out = lax.psum(out, axis_name)
            return out.reshape((b,) + out.shape[2:])

        return jax.tree_util.tree_map(collect, ys)

    return run


def onef1b_spmd(stage_fn: Callable, loss_fn: Callable, axis_name: str,
                num_microbatches: int):
    """Per-device 1F1B (PipeDream-flush) body, to be called INSIDE
    ``shard_map`` over the stage axis ``axis_name``.

    Where :func:`gpipe_spmd` relies on autodiff through the scan — XLA
    saves every tick's activations, so live memory grows with
    ``T = M + S - 1`` microbatch activations per device — this schedule
    hand-interleaves forward and backward so each device keeps at most
    ``S`` stage *inputs* alive, independent of ``M``.  The backward for
    a microbatch REMATERIALIZES its stage forward from the saved input
    (``jax.vjp`` at the backward tick), trading ~1 extra stage-forward
    per microbatch for the memory bound — the same trade
    ``jax.checkpoint`` makes, scheduled explicitly.

    Schedule (ticks ``t = 0 .. 2(M+S-1)-1``, stage ``s``, microbatch
    ``m``): forward of ``m`` on ``s`` at ``t = 2m + s``; backward at
    ``t = 2m + 2S - 1 - s``.  Adjacent stages act on opposite tick
    parities, so activations produced at ``t`` are consumed at ``t+1``
    after one ``ppermute`` hop (forward hops down the axis, gradient
    hops up), every device alternates F and B ticks in steady state
    (the 1F1B invariant), and the bubble fraction ``(S-1)/(M+S-1)``
    equals GPipe's.  A microbatch's saved input lives from its forward
    tick to its backward tick — ``2(S-s)-1`` ticks — so a ring buffer
    of ``S`` slots (slot ``m % S``) never collides.

    Because forward and backward are fused into one pass, this is a
    loss-and-grad primitive, not a differentiable layer:

    ``run(stacked_params_local, x, target[, loss_params])
    -> (loss, grads, dx[, loss_param_grads])``

    - ``loss_fn(y_pred_mb, target_mb) -> scalar`` (mean over the
      microbatch); the returned ``loss`` is the mean over microbatches,
      exact since microbatches are equal-sized;
    - ``grads`` is this device's ``(1, ...)`` stage-param grad slice
      (d loss / d params, microbatch-summed, matching the stacked
      layout of the input params).  Under cross-axis composition
      (e.g. a data axis in the caller's shard_map) these are PER-SHARD
      PARTIALS — the params are pvary'd to the activations' full
      varying set at entry precisely so no implicit reduction happens
      inside the schedule — and the caller applies its own reduction
      exactly once (``lax.pmean`` over the data axis for DDP mean
      semantics);
    - ``dx`` is d loss / d x, replicated — chain it into whatever
      produced ``x`` (embeddings, a previous parallel region) with the
      caller's own vjp; integer leaves of ``x`` (e.g. microbatch-id
      side inputs) get zero "grads" of their own dtype;
    - ``loss_params`` (optional): an extra pytree the loss closes
      over with real parameters — a task head living OUTSIDE the
      stages (``models.PipelinedBert`` puts its MLM/NSP heads here).
      When given, ``loss_fn(y_pred_mb, target_mb, loss_params)`` and a
      fourth output carries d loss / d loss_params (replicated).

    The last stage owns the loss: its backward tick rematerializes
    ``loss_fn(stage_fn(params, x_m), target_m[, loss_params])`` and
    seeds the vjp with ``1/M``, so the head can live in the last
    stage's params or in ``loss_params``.
    """

    def run(stacked_params_local: Pytree, x: Pytree,
            target: Pytree, loss_params: Pytree = None):
        s_size = lax.axis_size(axis_name)
        stage = lax.axis_index(axis_name)
        m = num_microbatches
        params, b, xs = _unstack_and_microbatch(
            stacked_params_local, x, m, axis_name, s_size)
        mb = b // m
        # same contract as the activation leaves: a target whose leading
        # dim != b would otherwise die in an opaque reshape (or, if the
        # size happens to factor, silently regroup microbatches)
        t_leaves = jax.tree_util.tree_leaves(target)
        for leaf in t_leaves:
            if leaf.ndim == 0 or leaf.shape[0] != b:
                raise ValueError(
                    "every target leaf must share the activations' "
                    f"batch dim ({b}); got "
                    f"{[l.shape for l in t_leaves]}")
        tgts = jax.tree_util.tree_map(
            lambda a: a.reshape((m, mb) + a.shape[1:]), target)
        x_leaves = jax.tree_util.tree_leaves(x)

        fwd_perm = [(i, i + 1) for i in range(s_size - 1)]
        bwd_perm = [(i + 1, i) for i in range(s_size - 1)]
        last = s_size - 1

        def _v(a, *refs):
            # fresh zeros carry no vma type; inherit the reference
            # leaves' varying axes (e.g. a data axis from composition)
            # plus the pipe axis the ppermutes will introduce
            return _vary_like(a, *refs, extra_axes=(axis_name,))

        x_ref = x_leaves[0]
        # pvary the stage params to the activations' full varying set
        # (e.g. a data axis from composition): params that stay
        # INVARIANT over an axis the activations vary on would make
        # every vjp insert a psum over that axis for their cotangent —
        # a collective inside the schedule's divergent cond branches,
        # and a silently pre-summed grad that double-counts under the
        # caller's mean-reduction. Varying params -> per-shard partial
        # grads, no branch collectives; the caller reduces once.
        params = jax.tree_util.tree_map(
            lambda a: _v(a, x_ref), params)
        if loss_params is not None:
            # make the loss params pipe-VARYING before any vjp sees
            # them: a pipe-invariant primal would make the transpose
            # insert a psum for its cotangent INSIDE the last-stage-only
            # cond branch — a collective only one device executes, which
            # deadlocks the others at the tick ppermute. Varying primal
            # -> varying cotangent; the reduction instead happens at the
            # uniform psum after the scan.
            loss_params = jax.tree_util.tree_map(
                lambda a: _v(a, x_ref), loss_params)
        carry0 = dict(
            x_inbox=jax.tree_util.tree_map(
                lambda a: _v(jnp.zeros_like(a[0]), a), xs),
            g_inbox=jax.tree_util.tree_map(
                lambda a: _v(jnp.zeros_like(a[0]), a), xs),
            ring=jax.tree_util.tree_map(
                lambda a: _v(jnp.zeros((s_size,) + a.shape[1:],
                                       a.dtype), a), xs),
            gacc=jax.tree_util.tree_map(
                lambda a: _v(jnp.zeros_like(a), a, x_ref), params),
            dxbuf=jax.tree_util.tree_map(
                lambda a: _v(jnp.zeros_like(a), a), xs),
            lacc=_v(jnp.zeros((), jnp.float32), x_ref),
        )
        if loss_params is not None:
            carry0["lpacc"] = jax.tree_util.tree_map(
                lambda a: _v(jnp.zeros_like(a), a, x_ref), loss_params)

        import numpy as _np
        from jax import dtypes as _jdtypes

        def _to_cotangents(tree):
            """vjp demands float0 cotangents for integer-dtype primal
            leaves (e.g. a microbatch-id side input riding the
            activation pytree); the carries keep primal dtypes, so
            convert right at the vjp boundary."""
            return jax.tree_util.tree_map(
                lambda ct: _np.zeros(ct.shape, _jdtypes.float0)
                if not jnp.issubdtype(ct.dtype, jnp.inexact) else ct,
                tree)

        def _from_cotangents(primal_tree, ct_tree):
            return jax.tree_util.tree_map(
                lambda p_l, ct: _v(jnp.zeros(p_l.shape, p_l.dtype), p_l)
                if ct.dtype == _jdtypes.float0 else ct,
                primal_tree, ct_tree)

        def tick(carry, t):
            mf = (t - stage) // 2
            fwd_valid = (t >= stage) & (mf < m)
            tb = t - (2 * s_size - 1 - stage)
            mb_i = tb // 2
            bwd_valid = (tb >= 0) & (mb_i < m)
            mf_c = jnp.clip(mf, 0, m - 1)
            mb_c = jnp.clip(mb_i, 0, m - 1)

            def fwd_branch(carry):
                inject = jax.tree_util.tree_map(lambda a: a[mf_c], xs)
                x_in = jax.tree_util.tree_map(
                    lambda i, buf: jnp.where(stage == 0, i, buf),
                    inject, carry["x_inbox"])
                y = stage_fn(params, x_in)
                slot = mf_c % s_size
                ring = jax.tree_util.tree_map(
                    lambda r, v: jnp.where(
                        fwd_valid,
                        lax.dynamic_update_index_in_dim(r, v, slot, 0),
                        r),
                    carry["ring"], x_in)
                out = dict(carry, ring=ring)
                g_zero = jax.tree_util.tree_map(
                    lambda a: _v(jnp.zeros_like(a), a),
                    carry["g_inbox"])
                return out, y, g_zero

            def bwd_branch(carry):
                slot = mb_c % s_size
                x_saved = jax.tree_util.tree_map(
                    lambda r: lax.dynamic_index_in_dim(
                        r, slot, 0, keepdims=False), carry["ring"])

                def _lp_norm(dlp):
                    # vjp can return SOME head-grad leaves without the
                    # varying type the other cond branch carries (the
                    # grad path for e.g. a bias may reduce away every
                    # varying operand); pvary all leaves to one type
                    if dlp is None:
                        return None
                    return jax.tree_util.tree_map(
                        lambda g: _v(g, x_ref), dlp)

                def _lp_zero():
                    if loss_params is None:
                        return None
                    return _lp_norm(jax.tree_util.tree_map(
                        jnp.zeros_like, loss_params))

                def mid(_):
                    _, vjp = jax.vjp(stage_fn, params, x_saved)
                    dp, dx = vjp(_to_cotangents(carry["g_inbox"]))
                    dx = _from_cotangents(x_saved, dx)
                    return (dp, dx, _v(jnp.zeros((), jnp.float32),
                                       carry["lacc"]), _lp_zero())

                def tail(_):
                    tgt_m = jax.tree_util.tree_map(
                        lambda a: a[mb_c], tgts)

                    if loss_params is None:
                        def f(p, xi):
                            return loss_fn(stage_fn(p, xi), tgt_m)

                        lval, vjp = jax.vjp(f, params, x_saved)
                    else:
                        def f(p, xi, lp):
                            return loss_fn(stage_fn(p, xi), tgt_m, lp)

                        lval, vjp = jax.vjp(f, params, x_saved,
                                            loss_params)
                    seed = _vary_like(jnp.asarray(1.0 / m,
                                                  dtype=lval.dtype),
                                      lval)
                    cts = vjp(seed)
                    dp, dx = cts[0], _from_cotangents(x_saved, cts[1])
                    dlp = (_lp_norm(cts[2]) if loss_params is not None
                           else None)
                    lval = _v(lval.astype(jnp.float32) / m,
                              carry["lacc"])
                    return dp, dx, lval, dlp

                dp, dx, lval, dlp = lax.cond(stage == last, tail, mid,
                                             None)
                gacc = jax.tree_util.tree_map(
                    lambda acc, g: acc + jnp.where(bwd_valid, g, 0),
                    carry["gacc"], dp)
                lacc = carry["lacc"] + jnp.where(bwd_valid, lval, 0.0)
                dxbuf = jax.tree_util.tree_map(
                    lambda buf, v: jnp.where(
                        bwd_valid & (stage == 0),
                        lax.dynamic_update_index_in_dim(buf, v, mb_c, 0),
                        buf),
                    carry["dxbuf"], dx)
                out = dict(carry, gacc=gacc, lacc=lacc, dxbuf=dxbuf)
                if loss_params is not None:
                    out["lpacc"] = jax.tree_util.tree_map(
                        lambda acc, g: acc + jnp.where(bwd_valid, g, 0),
                        carry["lpacc"], dlp)
                y_zero = jax.tree_util.tree_map(
                    lambda a: _v(jnp.zeros_like(a), a),
                    carry["x_inbox"])
                return out, y_zero, dx

            carry, y_out, g_out = lax.cond(
                (t - stage) % 2 == 0, fwd_branch, bwd_branch, carry)
            # collectives OUTSIDE the branches: every device must
            # participate every tick; off-parity payloads are garbage
            # that the receiver's schedule never reads
            carry = dict(
                carry,
                x_inbox=jax.tree_util.tree_map(
                    lambda a: lax.ppermute(a, axis_name, fwd_perm),
                    y_out),
                g_inbox=jax.tree_util.tree_map(
                    lambda a: lax.ppermute(a, axis_name, bwd_perm),
                    g_out))
            return carry, None

        ticks = jnp.arange(2 * (m + s_size - 1))
        carry, _ = lax.scan(tick, carry0, ticks)

        loss = lax.psum(jnp.where(stage == last, carry["lacc"], 0.0),
                        axis_name)
        grads = jax.tree_util.tree_map(lambda a: a[None],
                                       carry["gacc"])
        dx = jax.tree_util.tree_map(
            lambda buf: lax.psum(
                jnp.where(stage == 0, buf, jnp.zeros_like(buf)),
                axis_name).reshape((b,) + buf.shape[2:]),
            carry["dxbuf"])
        if loss_params is None:
            return loss, grads, dx
        lp_grads = jax.tree_util.tree_map(
            lambda acc: lax.psum(
                jnp.where(stage == last, acc, jnp.zeros_like(acc)),
                axis_name),
            carry["lpacc"])
        return loss, grads, dx, lp_grads

    return run


def onef1b_loss_and_grad(mesh: Mesh, axis_name: str, stage_fn: Callable,
                         loss_fn: Callable, stacked_params: Pytree,
                         x: Pytree, target: Pytree,
                         num_microbatches: int,
                         loss_params: Pytree = None):
    """One-call 1F1B: shard ``stacked_params`` over ``axis_name``, run
    the interleaved schedule, return ``(loss, grads, dx)`` — plus
    ``loss_param_grads`` when ``loss_params`` is given — with ``grads``
    stacked ``(S, ...)`` like the input params and everything else
    replicated.  This is the memory-bounded alternative to ``jax.grad``
    over :func:`pipeline_apply`; see :func:`onef1b_spmd` for the
    contract."""
    run = onef1b_spmd(stage_fn, loss_fn, axis_name, num_microbatches)
    p_spec = jax.tree_util.tree_map(lambda _: P(axis_name),
                                    stacked_params)
    r_spec = jax.tree_util.tree_map(lambda _: P(), x)
    t_spec = jax.tree_util.tree_map(lambda _: P(), target)
    in_specs, out_specs = (p_spec, r_spec, t_spec), (P(), p_spec, r_spec)
    args = (stacked_params, x, target)
    if loss_params is not None:
        lp_spec = jax.tree_util.tree_map(lambda _: P(), loss_params)
        in_specs += (lp_spec,)
        out_specs += (lp_spec,)
        args += (loss_params,)
    f = jax.shard_map(run, mesh=mesh, in_specs=in_specs,
                      out_specs=out_specs)
    return f(*args)


def pipeline_apply(mesh: Mesh, axis_name: str, stage_fn: Callable,
                   stacked_params: Pytree, x: Pytree,
                   num_microbatches: int) -> Pytree:
    """One-call GPipe: shard ``stacked_params`` over ``axis_name`` of
    ``mesh``, run the microbatch schedule, return the output (replicated
    over the pipe axis).  Differentiable; jit over it freely."""
    run = gpipe_spmd(stage_fn, axis_name, num_microbatches)
    f = jax.shard_map(
        run, mesh=mesh,
        in_specs=(jax.tree_util.tree_map(lambda _: P(axis_name),
                                         stacked_params),
                  jax.tree_util.tree_map(lambda _: P(), x)),
        out_specs=jax.tree_util.tree_map(lambda _: P(), x))
    return f(stacked_params, x)
