"""FusedAdam — Adam over flat parameter buffers with a Pallas TPU kernel.

Re-design of the reference ``apex/optimizers/fused_adam.py`` (``FusedAdam``
at :5) and its CUDA kernel ``csrc/fused_adam_cuda_kernel.cu:48-84``. The
update math is identical:

    g     = grad / combined_scale
    m     = beta1*m + (1-beta1)*g
    v     = beta2*v + (1-beta2)*g*g
    denom = sqrt(v) + eps              (eps outside sqrt, mode 1)
          | sqrt(v + eps)              (eps inside sqrt,  mode 0)
    p    -= step_size * (m/denom + weight_decay*p)

with ``step_size = lr * sqrt(1-beta2^t)/(1-beta1^t)`` when bias correction
is on (host-side fold in the reference, ``fused_adam_cuda.cpp:112-119``;
traced arithmetic here). Grad-norm clipping folds into ``combined_scale``
exactly as ``fused_adam.py:98-104``.

TPU design: instead of one CUDA launch per parameter tensor (reference
loops params at ``fused_adam.py:133-146``), one Pallas kernel updates every
parameter. The moments m/v live as contiguous flat fp32 buffers in the
optimizer state for the life of training. What a step moves (PR 30): the
params and the grads are gathered into matching flat buffers, each leaf
written once into one buffer (``ops.flatten.flatten_like``); all four
buffers enter the kernel as ``(n // 128, 128)`` views and its outputs leave
as views, so the donated state is updated where it lies (no pad, no
slice); and every leaf is cut from the kernel's output once
(``ops.flatten.unflatten``). A pure-jnp path (``use_pallas=False``)
provides the CPU fallback and the parity oracle.

The optax ``GradientTransformation`` protocol (init/update) is also
provided so FusedAdam slots into ``amp.initialize`` as the inner optimizer.
"""

from __future__ import annotations

import functools
import warnings
from typing import Any, NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from apex_tpu.observability.scopes import device_scope
from apex_tpu.ops.flatten import (FlatSpec, flatten, flatten_grouped,
                                  flatten_like, unflatten)
from apex_tpu.ops.pallas_utils import (DEFAULT_ROWS, LANES, SUBLANES, on_tpu,
                                       pallas_auto_gate, union_vma)
from apex_tpu.optimizers.param_groups import (group_hparams,
                                              resolve_group_ids)

Pytree = Any


class FusedAdamState(NamedTuple):
    step: jax.Array      # i32
    m: jax.Array         # f32 flat
    v: jax.Array         # f32 flat
    spec: FlatSpec       # static pytree metadata (hashable aux data)


# ``spec`` is static layout metadata, not an array: register the state so it
# jits cleanly with spec carried as aux data.
jax.tree_util.register_pytree_node(
    FusedAdamState,
    lambda s: ((s.step, s.m, s.v), s.spec),
    lambda spec, kids: FusedAdamState(kids[0], kids[1], kids[2], spec),
)


def _adam_math(p, m, v, g, step_size, beta1, beta2, eps, combined_scale,
               weight_decay, eps_inside_sqrt: bool, keep=None):
    """Shared update math (jnp ops — usable inside and outside Pallas).

    ``keep`` (f32 scalar 1.0/0.0, or None = unconditional): amp's
    overflow->skip-step protocol fused into the update itself. The
    wrapper-level alternative — ``jnp.where`` selects over params AND
    m/v AFTER the step (amp/optimizer.py) — re-reads and re-writes every
    flat buffer (~0.9 GB/step at ResNet-50 scale, measured on v5e,
    BENCH_NOTES.md); in-kernel the select fuses into the aliased write
    and costs nothing. ``jnp.where`` rather than an arithmetic blend: an
    overflowed g carries inf/nan and ``0 * nan`` would still be nan."""
    g = g / combined_scale
    m_new = beta1 * m + (1.0 - beta1) * g
    v_new = beta2 * v + (1.0 - beta2) * g * g
    if eps_inside_sqrt:
        denom = jnp.sqrt(v_new + eps)
    else:
        denom = jnp.sqrt(v_new) + eps
    update = m_new / denom + weight_decay * p
    p_new = p - step_size * update
    if keep is not None:
        tag = keep > 0.5
        p_new = jnp.where(tag, p_new, p)
        m_new = jnp.where(tag, m_new, m)
        v_new = jnp.where(tag, v_new, v)
    return p_new, m_new, v_new


def _adam_kernel(scalars_ref, p_ref, m_ref, v_ref, g_ref,
                 p_out, m_out, v_out, *, eps_inside_sqrt: bool):
    step_size = scalars_ref[0]
    beta1 = scalars_ref[1]
    beta2 = scalars_ref[2]
    eps = scalars_ref[3]
    combined_scale = scalars_ref[4]
    weight_decay = scalars_ref[5]
    keep = scalars_ref[6]
    p_new, m_new, v_new = _adam_math(
        p_ref[:], m_ref[:], v_ref[:], g_ref[:], step_size, beta1, beta2,
        eps, combined_scale, weight_decay, eps_inside_sqrt, keep=keep)
    p_out[:] = p_new
    m_out[:] = m_new
    v_out[:] = v_new


@functools.partial(jax.jit, static_argnames=("eps_inside_sqrt", "rows",
                                             "interpret"))
def _adam_flat_pallas(p, m, v, g, scalars, *, eps_inside_sqrt: bool,
                      rows: int = DEFAULT_ROWS, interpret: bool = False):
    """Run the fused kernel over flat fp32 buffers of one length ``n``.

    Where ``n`` is a multiple of ``LANES`` and fills a sublane tile (every
    state made by ``init``: ``pad_to`` defaults to 128) the buffers enter
    as their ``(n // LANES, LANES)`` VIEW and the outputs leave as views:
    the grid is ``cdiv`` over the rows, the last block is ragged, and
    Pallas drops what it writes past the end.  No pad, no slice: with the
    state donated, ``input_output_aliases`` update the state's own memory
    (at GPT-2 medium's 354.8M floats the pads and slices were five passes
    over 1.42 GB a step; PERF.md, PR 30).  Any other length (a group's
    slice at unaligned ``group_bounds``, a tree of tens of elements) is
    padded to whole blocks and cut back."""
    n = p.shape[0]
    view = n % LANES == 0 and n // LANES >= SUBLANES
    if view:
        total_rows = n // LANES
        # a buffer shorter than one block is its own (whole-array) block
        rows = min(rows, total_rows)
    else:
        total_rows = pl.cdiv(max(n, 1), rows * LANES) * rows

    def tile(x):
        if not view:
            x = jnp.pad(x, (0, total_rows * LANES - n))
        return x.reshape(total_rows, LANES)

    tile_spec = pl.BlockSpec((rows, LANES), lambda i: (i, 0))
    out_shape = jax.ShapeDtypeStruct((total_rows, LANES), jnp.float32,
                                     vma=union_vma(p, m, v, g, scalars))
    kernel = functools.partial(_adam_kernel, eps_inside_sqrt=eps_inside_sqrt)
    out = pl.pallas_call(
        kernel,
        grid=(pl.cdiv(total_rows, rows),),
        in_specs=[
            pl.BlockSpec(memory_space=pltpu.SMEM),
            tile_spec, tile_spec, tile_spec, tile_spec,
        ],
        out_specs=[tile_spec, tile_spec, tile_spec],
        out_shape=[out_shape, out_shape, out_shape],
        # update p/m/v in place (reference kernel mutates in place too,
        # fused_adam_cuda_kernel.cu): halves the HBM footprint of the step
        input_output_aliases={1: 0, 2: 1, 3: 2},
        interpret=interpret,
        name="_adam_kernel",
    )(scalars, tile(p), tile(m), tile(v), tile(g))
    return tuple(x.reshape(-1) if view else x.reshape(-1)[:n] for x in out)


def _with_dtypes(spec: FlatSpec, dtypes) -> FlatSpec:
    """``spec`` with other leaf dtypes, so that ``unflatten`` casts each
    leaf as it cuts it (one pass) instead of over a cut float32 tree."""
    return spec._replace(dtypes=tuple(jnp.dtype(d) for d in dtypes))


class FusedAdam:
    """Apex-compatible FusedAdam (reference ``fused_adam.py:5-49``).

    Arguments match the reference: ``lr``, ``bias_correction``, ``betas``,
    ``eps``, ``eps_inside_sqrt``, ``weight_decay``, ``max_grad_norm``
    (folded into the combined scale at step time), ``amsgrad`` rejected
    exactly like the reference (:46).

    ``param_groups``: optional list of path-predicate group specs
    (``optimizers.param_groups``) with per-group ``lr`` / ``weight_decay``
    / ``eps`` / ``betas`` / ``max_grad_norm`` overrides — the pytree
    analog of the reference's per-group loop (``fused_adam.py:50-146``).
    At ``init`` each group's leaves are laid out as one contiguous slice
    of the flat buffer, so the grouped step is still one Pallas launch per
    group over flat memory (no per-leaf launches, no extra HBM traffic).

    ``use_pallas``: None = auto (Pallas on TPU, jnp elsewhere).

    ``pad_to``: zero-pad the flat state buffers to a length multiple, so
    they shard evenly across mesh axes whose size divides it (ZeRO-1
    layout via ``parallel.shard_optimizer_state``; no reference analog —
    its flat masters are replicated per rank,
    ``apex/optimizers/fp16_optimizer.py:61-67``). Default 128 covers
    every power-of-two axis up to 128 at the cost of <=127 extra
    elements; the padding tail is zeros and stays zeros.

    ``layout``: where the moments live and how the update runs.

    - ``"flat"`` (default): contiguous flat fp32 m/v + the Pallas kernel
      — the reference's flat-buffer architecture, ZeRO-shardable as two
      arrays, one kernel for the whole model.
    - ``"tree"``: m/v as pytrees mirroring the params, updated per leaf
      by the SAME math under jit. On TPU, XLA fuses each leaf's
      unscale+update+skip-select into one HBM pass and kernel-launch
      count is irrelevant (no CUDA-style per-launch cost, the thing the
      reference's multi_tensor_apply exists to amortize) — while the
      flat layout gathers the params and the grads into flat buffers
      and cuts the leaves back out EVERY step (at GPT-2 medium's
      354.8M parameters on one v5e the whole training step is 216.0 ms
      flat and 177.9 ms tree; PERF.md, PR 30). Same update
      semantics, group support, and skip protocol; state is per-leaf
      (like optax), so checkpoints are layout-specific.

    Tensor-parallel params need ``layout="tree"``: the flat layout's
    whole-model concat cannot preserve per-param Megatron placements
    (``parallel.gpt_tp_rules`` / ``bert_tp_rules``), so a flat-layout
    step gathers the TP shards and emits replicated params — numerics
    are right but the placement is silently gone after one step (found
    by driving a dp x tp x pp train loop). The tree layout updates each
    leaf in place, so shardings propagate through. Flat + ZeRO over the
    DATA axis (``with_zero``) is unaffected — that sharding is applied
    to the flat buffers themselves.
    """

    # AmpOptimizer.apply_gradients: the overflow->skip select runs inside
    # the fused kernel (step(..., skip=...)) instead of as wrapper-level
    # tree-selects over params + state
    supports_fused_skip = True

    def __init__(self, lr: float = 1e-3, bias_correction: bool = True,
                 betas: Tuple[float, float] = (0.9, 0.999), eps: float = 1e-8,
                 eps_inside_sqrt: bool = False, weight_decay: float = 0.0,
                 max_grad_norm: float = 0.0, amsgrad: bool = False,
                 use_pallas: Optional[bool] = None, param_groups=None,
                 pad_to: int = 128, layout: str = "flat"):
        if amsgrad:
            raise RuntimeError("FusedAdam does not support the AMSGrad "
                               "variant.")
        if layout not in ("flat", "tree"):
            raise ValueError(f"layout must be 'flat' or 'tree', "
                             f"got {layout!r}")
        self.layout = layout
        self.lr = lr
        self.bias_correction = bias_correction
        self.betas = betas
        self.eps = eps
        self.eps_inside_sqrt = eps_inside_sqrt
        self.weight_decay = weight_decay
        self.max_grad_norm = max_grad_norm
        self.use_pallas = use_pallas
        self.pad_to = pad_to
        self._zero = None  # (mesh, axis) once with_zero() configures it
        self.param_groups = list(param_groups) if param_groups else []
        if self.param_groups:
            from apex_tpu.optimizers.param_groups import validate_specs
            validate_specs(self.param_groups, self._defaults().keys(),
                           "FusedAdam")

    def _defaults(self):
        return {"lr": self.lr, "betas": self.betas, "eps": self.eps,
                "weight_decay": self.weight_decay,
                "max_grad_norm": self.max_grad_norm}

    def _clone(self, **overrides) -> "FusedAdam":
        kw = dict(lr=self.lr, bias_correction=self.bias_correction,
                  betas=self.betas, eps=self.eps,
                  eps_inside_sqrt=self.eps_inside_sqrt,
                  weight_decay=self.weight_decay,
                  max_grad_norm=self.max_grad_norm,
                  use_pallas=self.use_pallas,
                  param_groups=self.param_groups, pad_to=self.pad_to,
                  layout=self.layout)
        kw.update(overrides)
        new = FusedAdam(**kw)
        new._zero = self._zero
        return new

    def with_zero(self, mesh, axis: str = "data",
                  min_shard_elems: Optional[int] = None) -> "FusedAdam":
        """Return a copy whose Pallas update runs shard-local over ``axis``.

        ZeRO-1 composition (``parallel.shard_optimizer_state``): the raw
        ``pallas_call`` lowers to a ``tpu_custom_call`` that carries no
        GSPMD partitioning rule, so under a sharded m/v state XLA would
        re-gather the flat buffers — defeating the memory win.  Configured
        with the mesh, the kernel is wrapped in ``jax.shard_map`` over the
        ZeRO axis instead: each device updates only its 1/n slice of the
        flat buffers (the update is elementwise, so no collectives), and
        the sharded placement survives the step.  The buffers are padded
        to ``pad_to`` (default 128) at ``init`` precisely so they divide
        evenly.

        ``axis`` and ``min_shard_elems`` must match what was given to
        ``parallel.shard_optimizer_state`` — the kernel's out_specs SET
        the output placement, so a mismatch would reshard the buffers
        every step.  Buffers below the threshold (default
        ``axis_size * 128`` elements, same as that helper) take the jnp
        update and stay replicated, matching its placement decision.

        ``layout="tree"`` needs no configuration at all (the per-leaf
        jnp update is GSPMD-partitionable and simply follows each
        leaf's placement); this method is then a no-op clone kept for
        call-site symmetry.
        """
        if min_shard_elems is None:
            min_shard_elems = mesh.shape[axis] * 128
        new = self._clone()
        new._zero = (mesh, axis, min_shard_elems)
        return new

    # -- optax GradientTransformation protocol ---------------------------
    def init(self, params: Pytree) -> FusedAdamState:
        if self.layout == "tree":
            zeros = jax.tree_util.tree_map(
                lambda p: jnp.zeros(p.shape, jnp.float32), params)
            return FusedAdamState(step=jnp.asarray(0, jnp.int32),
                                  m=zeros,
                                  v=jax.tree_util.tree_map(jnp.copy, zeros),
                                  spec=None)
        if self.param_groups:
            ids = resolve_group_ids(params, self.param_groups)
            # number groups densely 0..n_specs even if some are empty so
            # group_bounds aligns with group_hparams
            ids = tuple(ids)
            flat, spec = flatten_grouped(
                params, ids, dtype=jnp.float32, pad_to=self.pad_to)
            n_groups = len(self.param_groups) + 1
            if len(spec.group_bounds) < n_groups:  # trailing empty groups
                bounds = list(spec.group_bounds)
                while len(bounds) < n_groups:
                    bounds.append((spec.total, 0))
                spec = spec._replace(group_bounds=tuple(bounds))
        else:
            flat, spec = flatten(params, dtype=jnp.float32,
                                 pad_to=self.pad_to)
        return FusedAdamState(step=jnp.asarray(0, jnp.int32),
                              m=jnp.zeros_like(flat),
                              v=jnp.zeros_like(flat), spec=spec)

    # -- runtime group surgery -------------------------------------------
    def add_param_group(self, state: FusedAdamState, params: Pytree,
                        match, **overrides):
        """Mid-training group addition (reference
        ``_process_optimizer.py:333-407`` / ``test_add_param_group``):
        returns ``(new_optimizer, new_state)`` where leaves matching
        ``match`` now use ``overrides`` and every leaf keeps its Adam
        moments.  ``params`` may also contain NEW leaves (the reference's
        actual use: unfreezing fresh params) — their moments start at
        zero."""
        from apex_tpu.optimizers.param_groups import leaf_paths

        # PREPEND: group resolution is first-match-wins, so the newest
        # declaration must come first to actually override leaves an
        # earlier group already matched
        new_opt = self._clone(
            param_groups=[dict(match=match, **overrides)]
            + self.param_groups)
        if self.layout == "tree":
            # per-leaf state: carry moments over by path, zeros for new
            # leaves — no flat-layout surgery needed
            old = {}
            for path, m_leaf, v_leaf in zip(
                    leaf_paths(state.m),
                    jax.tree_util.tree_leaves(state.m),
                    jax.tree_util.tree_leaves(state.v)):
                old[path] = (m_leaf, v_leaf)
            fresh = new_opt.init(params)
            paths = leaf_paths(params)

            def carry(which, tree):
                leaves = jax.tree_util.tree_leaves(tree)
                out = []
                for path, leaf in zip(paths, leaves):
                    prev = old.get(path)
                    out.append(prev[which] if prev is not None and
                               prev[which].shape == leaf.shape else leaf)
                return jax.tree_util.tree_unflatten(
                    jax.tree_util.tree_structure(tree), out)
            return new_opt, FusedAdamState(
                step=state.step, m=carry(0, fresh.m), v=carry(1, fresh.v),
                spec=None)
        new_state = new_opt.init(params)
        # carry over moments by leaf path (old layout -> new layout)
        old_m = unflatten(state.m, state.spec, cast_back=False)
        old_v = unflatten(state.v, state.spec, cast_back=False)
        old = {}
        for path, m_leaf, v_leaf in zip(
                leaf_paths(old_m), jax.tree_util.tree_leaves(old_m),
                jax.tree_util.tree_leaves(old_v)):
            old[path] = (m_leaf, v_leaf)

        new_paths = leaf_paths(params)
        m_leaves = list(jax.tree_util.tree_leaves(
            unflatten(new_state.m, new_state.spec, cast_back=False)))
        v_leaves = list(jax.tree_util.tree_leaves(
            unflatten(new_state.v, new_state.spec, cast_back=False)))
        for i, path in enumerate(new_paths):
            if path in old and old[path][0].shape == m_leaves[i].shape:
                m_leaves[i], v_leaves[i] = old[path]
        treedef = new_state.spec.treedef
        m_tree = jax.tree_util.tree_unflatten(treedef, m_leaves)
        v_tree = jax.tree_util.tree_unflatten(treedef, v_leaves)
        return new_opt, FusedAdamState(
            step=state.step,
            m=flatten_like(m_tree, new_state.spec, dtype=jnp.float32,
                           pad_to=self.pad_to),
            v=flatten_like(v_tree, new_state.spec, dtype=jnp.float32,
                           pad_to=self.pad_to),
            spec=new_state.spec)

    def update(self, grads: Pytree, state: FusedAdamState,
               params: Optional[Pytree] = None, *, scale=1.0,
               grad_norm=None, skip=None):
        """optax-style: returns (updates, new_state) where
        ``new_params = params + updates``.  With ``skip`` (bool scalar)
        true, updates are zero and the state is unchanged — the
        skip-step select runs inside the fused kernel (zero extra HBM
        traffic) instead of over materialized trees."""
        with device_scope("optimizer"):
            if params is None:
                raise ValueError("FusedAdam.update requires params")
            if self.layout == "tree":
                p2, new_state = self._step_tree(params, grads, state, scale,
                                                grad_norm, skip=skip)
                updates = jax.tree_util.tree_map(
                    lambda n, p: (n - p.astype(n.dtype)).astype(p.dtype),
                    p2, params)
                return updates, new_state
            new_flat, new_state, old_flat = self._step_flat(
                params, grads, state, scale, grad_norm, skip=skip)
            # match param leaf dtypes (masters are fp32; O3 runs half params)
            dtypes = [p.dtype for p in jax.tree_util.tree_leaves(params)]
            updates = unflatten(new_flat - old_flat,
                                _with_dtypes(state.spec, dtypes))
            return updates, new_state

    # -- apex-style step --------------------------------------------------
    def step(self, params: Pytree, grads: Pytree, state: FusedAdamState,
             scale=1.0, grad_norm=None, output_params_dtype=None,
             skip=None):
        """Apply the update directly (reference ``step`` semantics with
        ``grads``/``scale``/``grad_norms`` args, ``fused_adam.py:50``).

        Returns ``(new_params, new_state)`` — with ``output_params_dtype``
        the returned params are also cast (the reference's fp16
        ``output_params`` copy-out, ``fused_adam_cuda_kernel.cu:82``).

        ``skip`` (bool scalar or None): amp's overflow->skip-step,
        selected INSIDE the fused kernel — see :func:`_adam_math`.
        """
        with device_scope("optimizer"):
            if self.layout == "tree":
                new_params, new_state = self._step_tree(
                    params, grads, state, scale, grad_norm, skip=skip)
                if output_params_dtype is not None:
                    new_params = jax.tree_util.tree_map(
                        lambda x: x.astype(output_params_dtype), new_params)
                return new_params, new_state
            new_flat, new_state, _ = self._step_flat(
                params, grads, state, scale, grad_norm, skip=skip)
            spec = state.spec
            if output_params_dtype is not None:
                spec = _with_dtypes(spec,
                                    [output_params_dtype] * len(spec.dtypes))
            return unflatten(new_flat, spec), new_state

    # -- core -------------------------------------------------------------
    def _step_group(self, p, m, v, g, hp, step, scale, grad_norm,
                    use_pallas, keep=None):
        """One (contiguous) group's fused update. ``keep`` (f32 1.0/0.0
        or None): in-kernel skip-step select, see :func:`_adam_math`."""
        beta1, beta2 = hp["betas"]

        combined_scale = jnp.asarray(scale, jnp.float32)
        if hp["max_grad_norm"] > 0:
            if grad_norm is None:
                grad_norm = jnp.sqrt(
                    jnp.sum(jnp.square(g)))  # this group's grads only
            # reference fused_adam.py:98-104
            clip = (grad_norm / jnp.asarray(scale, jnp.float32)) / \
                hp["max_grad_norm"]
            combined_scale = jnp.where(clip > 1,
                                       clip * scale, combined_scale)

        if self.bias_correction:
            # a skipped step does not advance ``step``, so the first
            # (skipped) step sees t=0 where 1-beta^0 = 0: clamp to 1 —
            # the produced step_size only feeds a result the keep-select
            # discards
            t = jnp.maximum(step, 1).astype(jnp.float32)
            bc1 = 1.0 - beta1 ** t
            bc2 = 1.0 - beta2 ** t
            step_size = hp["lr"] * jnp.sqrt(bc2) / bc1
        else:
            step_size = jnp.asarray(hp["lr"], jnp.float32)

        if use_pallas:
            scalars = jnp.stack([
                jnp.asarray(step_size, jnp.float32),
                jnp.asarray(beta1, jnp.float32),
                jnp.asarray(beta2, jnp.float32),
                jnp.asarray(hp["eps"], jnp.float32),
                combined_scale,
                jnp.asarray(hp["weight_decay"], jnp.float32),
                (jnp.asarray(1.0, jnp.float32) if keep is None
                 else jnp.asarray(keep, jnp.float32)),
            ])
            call = functools.partial(
                _adam_flat_pallas, eps_inside_sqrt=self.eps_inside_sqrt,
                interpret=not on_tpu())
            if self._zero is not None:
                mesh, ax, min_elems = self._zero
                nshard = mesh.shape[ax]
                # mirror shard_optimizer_state's min-size threshold: a
                # buffer it left replicated must not be force-sharded by
                # the kernel's out_specs (placement flip + recompile
                # under donation)
                if p.shape[0] % nshard == 0 and p.shape[0] >= min_elems:
                    # ZeRO composition: run the kernel shard-local over
                    # the axis the flat state is sharded on (with_zero);
                    # elementwise update, so no collectives inside
                    from jax.sharding import PartitionSpec as P
                    sharded = P(ax)
                    # check_vma=False: the update is shard-local
                    # elementwise, so there is no replication invariant
                    # to check
                    return jax.shard_map(
                        call, mesh=mesh,
                        in_specs=(sharded, sharded, sharded, sharded, P()),
                        out_specs=(sharded, sharded, sharded),
                        check_vma=False)(p, m, v, g, scalars)
                # a group slice that doesn't divide the axis (grouped
                # layouts pad only the total buffer), or a buffer small
                # enough that shard_optimizer_state left it replicated:
                # the jnp update follows the state's placement for free
                return _adam_math(
                    p, m, v, g, step_size, beta1, beta2, hp["eps"],
                    combined_scale, hp["weight_decay"],
                    self.eps_inside_sqrt, keep=keep)
            return call(p, m, v, g, scalars)
        return _adam_math(
            p, m, v, g, step_size, beta1, beta2, hp["eps"],
            combined_scale, hp["weight_decay"], self.eps_inside_sqrt,
            keep=keep)

    def _step_tree(self, params, grads, state: FusedAdamState, scale,
                   grad_norm, skip=None):
        """Per-leaf update (``layout="tree"``): same math as the flat
        kernel, one fused HBM pass per leaf, no gather and no cut.
        Returns ``(new_params_tree, new_state)``."""
        hps = group_hparams(self._defaults(), self.param_groups)
        ids = (resolve_group_ids(params, self.param_groups)
               if self.param_groups else None)
        if skip is None:
            keep = None
            step = state.step + 1
        else:
            keep = 1.0 - jnp.asarray(skip, jnp.float32)
            step = state.step + keep.astype(jnp.int32)

        g_leaves = jax.tree_util.tree_leaves(grads)

        def group_scalars(gid, hp):
            beta1, beta2 = hp["betas"]
            combined_scale = jnp.asarray(scale, jnp.float32)
            if hp["max_grad_norm"] > 0:
                gn = grad_norm
                if gn is None:  # this group's grads only (flat parity)
                    sq = jnp.asarray(0.0, jnp.float32)
                    for i, g in enumerate(g_leaves):
                        if ids is None or ids[i] == gid:
                            sq = sq + jnp.sum(
                                jnp.square(g.astype(jnp.float32)))
                    gn = jnp.sqrt(sq)
                clip = (gn / jnp.asarray(scale, jnp.float32)) / \
                    hp["max_grad_norm"]
                combined_scale = jnp.where(clip > 1, clip * scale,
                                           combined_scale)
            if self.bias_correction:
                t = jnp.maximum(step, 1).astype(jnp.float32)
                step_size = hp["lr"] * jnp.sqrt(1.0 - beta2 ** t) / \
                    (1.0 - beta1 ** t)
            else:
                step_size = jnp.asarray(hp["lr"], jnp.float32)
            return step_size, combined_scale

        scalars = [group_scalars(gid, hp) for gid, hp in enumerate(hps)]

        i = -1

        def leaf(p, m, v, g):
            nonlocal i
            i += 1
            gid = ids[i] if ids is not None else 0
            hp = hps[gid]
            step_size, combined_scale = scalars[gid]
            p_new, m_new, v_new = _adam_math(
                p.astype(jnp.float32), m, v, g.astype(jnp.float32),
                step_size, hp["betas"][0], hp["betas"][1], hp["eps"],
                combined_scale, hp["weight_decay"], self.eps_inside_sqrt,
                keep=keep)
            return p_new.astype(p.dtype), m_new, v_new

        out = jax.tree_util.tree_map(leaf, params, state.m, state.v, grads)
        # unzip the (p, m, v) leaf triples back into three trees
        treedef = jax.tree_util.tree_structure(params)
        triples = jax.tree_util.tree_leaves(
            out, is_leaf=lambda x: isinstance(x, tuple))
        p2 = jax.tree_util.tree_unflatten(treedef,
                                          [t[0] for t in triples])
        m2 = jax.tree_util.tree_unflatten(treedef,
                                          [t[1] for t in triples])
        v2 = jax.tree_util.tree_unflatten(treedef,
                                          [t[2] for t in triples])
        return p2, FusedAdamState(step=step, m=m2, v=v2, spec=None)

    def _step_flat(self, params, grads, state: FusedAdamState, scale,
                   grad_norm, skip=None):
        # gather p/g at the state buffers' length (the one multiple of it
        # that holds ``spec.total``), not self.pad_to: a state restored
        # from a checkpoint must keep ITS layout
        buf_len = state.m.shape[0]
        p = flatten_like(params, state.spec, dtype=jnp.float32,
                         pad_to=buf_len)
        g = flatten_like(grads, state.spec, dtype=jnp.float32,
                         pad_to=buf_len)
        if skip is None:
            keep = None
            step = state.step + 1
        else:
            keep = 1.0 - jnp.asarray(skip, jnp.float32)
            # a skipped step leaves the bias-correction clock alone too
            # (the reference's patched step is a full no-op on overflow,
            # handle.py:130-150)
            step = state.step + keep.astype(jnp.int32)
        # with_zero's kernel call sits inside its own fully-manual
        # shard_map (legal for Mosaic even when the enclosing trace has
        # GSPMD-automatic axes — nested binding under partial-manual
        # fails loudly on its own); only the bare kernel needs the
        # auto-axes gate
        use_pallas = self.use_pallas if self.use_pallas is not None \
            else (on_tpu() if self._zero is not None
                  else pallas_auto_gate())
        if use_pallas and self._zero is None:
            # eager-path guard: a sharded state meeting the un-configured
            # Pallas kernel would be silently re-gathered by GSPMD (no
            # partitioning rule on the custom call), defeating ZeRO's
            # memory win — fall back to the partitionable jnp update and
            # tell the user about with_zero.  (Inside jit the committed
            # input sharding is not visible on tracers; the same pairing
            # is then the caller's contract, parallel/zero.py.)
            try:
                sharding = (getattr(state.m, "sharding", None)
                            if jax.core.is_concrete(state.m) else None)
            except Exception:
                sharding = None
            if sharding is not None and not sharding.is_fully_replicated:
                warnings.warn(
                    "FusedAdam: optimizer state is sharded but the Pallas "
                    "kernel has no GSPMD partitioning rule; using the jnp "
                    "update instead. Configure the fused path with "
                    "optimizer.with_zero(mesh, axis) to run it "
                    "shard-local.", stacklevel=3)
                use_pallas = False

        bounds = state.spec.group_bounds or ((0, state.spec.total),)
        hps = group_hparams(self._defaults(), self.param_groups)
        if len(hps) == 1 and len(bounds) > 1:
            # state carries a grouped layout but this optimizer declares no
            # groups (e.g. layout-only restore): every group uses defaults
            hps = hps * len(bounds)
        elif len(hps) != len(bounds):
            raise ValueError(
                f"optimizer declares {len(hps)} groups but the state's "
                f"flat layout has {len(bounds)} — param_groups must match "
                "the specs the state was init'd (or add_param_group'd) "
                "with")
        if len(bounds) == 1:
            p2, m2, v2 = self._step_group(
                p, state.m, state.v, g, hps[0], step, scale, grad_norm,
                use_pallas, keep=keep)
        else:
            # write each group's slice back into the full buffers with
            # dynamic_update_slice (alias-friendly under donation) rather
            # than concatenating fresh full-size arrays
            p2, m2, v2 = p, state.m, state.v
            for (start, size), hp in zip(bounds, hps):
                if size == 0:
                    continue
                sl = slice(start, start + size)
                pp, mm, vv = self._step_group(
                    p[sl], state.m[sl], state.v[sl], g[sl], hp, step,
                    scale, grad_norm, use_pallas, keep=keep)
                p2 = jax.lax.dynamic_update_slice(p2, pp, (start,))
                m2 = jax.lax.dynamic_update_slice(m2, mm, (start,))
                v2 = jax.lax.dynamic_update_slice(v2, vv, (start,))
        return p2, FusedAdamState(step=step, m=m2, v=v2, spec=state.spec), p
