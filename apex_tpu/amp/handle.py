"""Per-iteration amp protocol: scale_loss / disable_casts.

Port of reference ``apex/amp/handle.py``. The reference's ``scale_loss``
context manager does three jobs: scale the loss on entry, and on exit
unscale grads + update the scale + maybe patch ``optimizer.step`` into a
one-shot skip (``handle.py:16-150``). Under functional autodiff the
gradients don't exist inside the context, so the protocol splits cleanly:

- ``scale_loss`` (here) = the entry half: yields ``loss * current_scale``
  for use inside the loss function passed to ``jax.grad``;
- the exit half (unscale, update_scale, skip-step) lives in
  ``AmpOptimizer.step`` — see ``apex_tpu/amp/optimizer.py``.
"""

from __future__ import annotations

import contextlib

import jax.numpy as jnp

from apex_tpu.amp import _amp_state
from apex_tpu.amp.optimizer import AmpOptimizerState
from apex_tpu.amp.scaler import LossScalerState
from apex_tpu.observability.scopes import device_scope


def _resolve_scaler_state(state, loss_id: int) -> LossScalerState:
    if isinstance(state, LossScalerState):
        return state
    if isinstance(state, AmpOptimizerState):
        return state.loss_scalers[loss_id]
    if hasattr(state, "loss_scalers"):
        return state.loss_scalers[loss_id]
    raise TypeError(
        "scale_loss needs a LossScalerState or AmpOptimizerState (pass the "
        f"optimizer *state*, not the optimizer object); got {type(state)}")


@contextlib.contextmanager
def scale_loss(loss, state, loss_id: int = 0):
    """``with amp.scale_loss(loss, opt_state) as scaled_loss:``

    Yields ``loss.float() * loss_scale`` (reference ``handle.py:116``).
    Use inside the function being differentiated; return the scaled loss
    from it so gradients arrive scaled, then ``AmpOptimizer.step`` unscales.

    Unlike the reference, ``state`` is the *optimizer state pytree* (or a
    bare ``LossScalerState``), not the optimizer object — inside a jitted
    step the scale must be a traced value, not a captured constant.
    """
    if _amp_state._amp_state.opt_properties is not None and not \
            _amp_state._amp_state.opt_properties.enabled:
        yield loss
        return
    sstate = _resolve_scaler_state(state, loss_id)
    with device_scope("optimizer"):
        scaled = jnp.asarray(loss, jnp.float32) * sstate.loss_scale
    yield scaled


def scale(loss, state, loss_id: int = 0):
    """Function form of :func:`scale_loss` for non-context-manager use."""
    with scale_loss(loss, state, loss_id) as s:
        return s


@contextlib.contextmanager
def disable_casts():
    """Trace-time escape hatch: code under this context runs without amp
    input/param casting (reference ``handle.py:160``)."""
    old = _amp_state._amp_state.casts_disabled
    _amp_state._amp_state.casts_disabled = True
    try:
        yield
    finally:
        _amp_state._amp_state.casts_disabled = old
