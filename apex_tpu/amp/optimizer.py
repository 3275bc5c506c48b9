"""AmpOptimizer — loss-scale-aware optimizer wrapping with skip-step.

The TPU re-design of the reference's optimizer surgery
(``apex/amp/_process_optimizer.py``): where the reference monkey-patches
``optimizer.step``/``zero_grad`` and stashes master params inside
``_amp_stash``, here the optimizer is an immutable wrapper around any
optax ``GradientTransformation`` and all bookkeeping is explicit state:

- canonical params given to ``step`` are already the fp32 masters (see
  ``apex_tpu/amp/model.py``), so the fp16<->fp32 group-splitting machinery
  (``_process_optimizer.py:13-75``) is unnecessary;
- the overflow -> skip-step protocol (reference ``handle.py:130-150``
  patches ``step`` to a one-shot no-op) becomes a branch-free
  ``jnp.where`` select between updated and stale params/optimizer state,
  fully inside jit;
- per-loss scalers (``num_losses``/``loss_id``, reference
  ``_initialize.py:232-236``) are a tuple of scaler states.
"""

from __future__ import annotations

from typing import Any, NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp

from apex_tpu.amp.scaler import LossScaler, LossScalerState
from apex_tpu.observability.scopes import device_scope

Pytree = Any


class AmpOptimizerState(NamedTuple):
    inner: Any                                   # wrapped optimizer's state
    loss_scalers: Tuple[LossScalerState, ...]    # one per loss
    applied_steps: jax.Array                     # i32, steps actually taken
    skipped_steps: jax.Array                     # i32, overflow-skipped steps


def _tree_select(pred, on_true, on_false):
    return jax.tree_util.tree_map(
        lambda t, f: jnp.where(pred, t, f), on_true, on_false)


class AmpOptimizer:
    """Wraps an optax-style optimizer with unscale/overflow/skip logic.

    ``inner`` needs ``init(params) -> state`` and
    ``update(grads, state, params) -> (updates, state)`` (the optax
    GradientTransformation protocol; apex_tpu fused optimizers satisfy it).
    """

    def __init__(self, inner, loss_scaler: LossScaler, num_losses: int = 1):
        self.inner = inner
        self.loss_scaler = loss_scaler
        self.num_losses = int(num_losses)

    def with_zero(self, mesh, axis: str = "data",
                  min_shard_elems: Optional[int] = None) -> "AmpOptimizer":
        """ZeRO-1 pairing passthrough: reconfigure the wrapped optimizer's
        fused path to run shard-local over ``axis`` (see
        ``FusedAdam.with_zero`` / ``parallel.shard_optimizer_state``)."""
        if not hasattr(self.inner, "with_zero"):
            return self  # per-leaf optimizers partition shard-local as-is
        return AmpOptimizer(self.inner.with_zero(mesh, axis,
                                                 min_shard_elems),
                            self.loss_scaler, self.num_losses)

    # -- state ------------------------------------------------------------
    def init(self, params: Pytree) -> AmpOptimizerState:
        return AmpOptimizerState(
            inner=self.inner.init(params),
            loss_scalers=tuple(self.loss_scaler.init()
                               for _ in range(self.num_losses)),
            applied_steps=jnp.asarray(0, jnp.int32),
            skipped_steps=jnp.asarray(0, jnp.int32),
        )

    # -- granular protocol (multi-loss / grad accumulation) ---------------
    def unscale_grads(self, grads: Pytree, state: AmpOptimizerState,
                      loss_id: int = 0, *, stashed: Optional[Pytree] = None,
                      update_scale: bool = True):
        """Unscale one loss's grads; returns (grads, overflow, new_state).

        With ``stashed`` accumulates into previously-unscaled grads
        (reference ``scaler.py:149-180``).  ``update_scale=False`` defers
        the dynamic-scale update — the grad-accumulation protocol: the
        reference updates the scale ONCE per optimizer step from the
        overflow state accumulated across every microbatch's unscale
        (``scaler.py:184-210``), so intermediate microbatches pass False
        and the step ends with :meth:`update_scale` on the ORed flag.
        """
        with device_scope("optimizer"):
            sstate = state.loss_scalers[loss_id]
            if stashed is None:
                g, overflow = self.loss_scaler.unscale(
                    grads, sstate, out_dtype=jnp.float32)
            else:
                g, overflow = self.loss_scaler.unscale_with_stashed(
                    grads, stashed, sstate)
            if not update_scale:
                return g, overflow, state
            return g, overflow, self.update_scale(state, overflow, loss_id)

    def update_scale(self, state: AmpOptimizerState, overflow,
                     loss_id: int = 0) -> AmpOptimizerState:
        """One dynamic-scale update from an (accumulated) overflow flag —
        the per-step half of the grad-accumulation protocol (see
        :meth:`unscale_grads`)."""
        with device_scope("optimizer"):
            new_sstate = self.loss_scaler.update(
                state.loss_scalers[loss_id], overflow)
            scalers = tuple(new_sstate if i == loss_id else s
                            for i, s in enumerate(state.loss_scalers))
            return state._replace(loss_scalers=scalers)

    def apply_gradients(self, params: Pytree, grads: Pytree,
                        state: AmpOptimizerState, overflow) -> Tuple[Pytree, AmpOptimizerState]:
        """Inner optimizer step with branch-free skip on overflow.

        Fused optimizers that accept ``skip`` (FusedAdam/FusedLAMB) run
        the select INSIDE their kernel: the wrapper-level tree-selects
        below re-read and re-write the full params + optimizer state
        (~0.9 GB/step at ResNet-50 scale, measured on v5e,
        BENCH_NOTES.md), and the update-diff protocol costs another
        subtract + apply round-trip on top."""
        with device_scope("optimizer"):
            keep = ~jnp.asarray(overflow)
            if getattr(self.inner, "supports_fused_skip", False):
                params_out, inner_out = self.inner.step(
                    params, grads, state.inner, skip=overflow)
            else:
                import optax
                updates, new_inner = self.inner.update(grads, state.inner,
                                                       params)
                new_params = optax.apply_updates(params, updates)
                params_out = _tree_select(keep, new_params, params)
                inner_out = _tree_select(keep, new_inner, state.inner)
            return params_out, state._replace(
                inner=inner_out,
                applied_steps=state.applied_steps
                + keep.astype(jnp.int32),
                skipped_steps=state.skipped_steps
                + (~keep).astype(jnp.int32),
            )

    # -- fused one-call step ---------------------------------------------
    def step(self, params: Pytree, grads: Pytree, state: AmpOptimizerState,
             loss_id: int = 0) -> Tuple[Pytree, AmpOptimizerState]:
        """unscale -> scaler update -> inner step with skip; one call.

        Equivalent of the reference per-iteration protocol: exit of
        ``scale_loss`` (unscale + ``update_scale``) followed by the patched
        ``optimizer.step`` (``handle.py:116-150``,
        ``_process_optimizer.py:287-294``).
        """
        g, overflow, state = self.unscale_grads(grads, state, loss_id)
        return self.apply_gradients(params, g, state, overflow)

    # -- introspection ----------------------------------------------------
    def loss_scale(self, state: AmpOptimizerState, loss_id: int = 0):
        return state.loss_scalers[loss_id].loss_scale
