"""Model-side casting machinery: the TPU re-design of apex's model surgery.

The reference casts models in two ways: O2/O3 call ``model.to(half)`` /
``convert_network`` (BN-safe) and monkey-patch ``model.forward`` to cast
inputs (``apex/amp/_initialize.py:183-208``); O1 monkey-patches torch
namespaces per an op whitelist (``apex/amp/amp.py:68-171``).

Here a model is a flax module (or bare apply_fn) over an immutable variable
pytree, so "casting the model" becomes a pure function of the variables at
apply time:

- the canonical (optimizer-side) variables stay fp32 for O0/O1/O2 — these
  ARE the master weights; O3 stores half canonically (no masters);
- ``AmpModel.apply`` casts params and float inputs to the compute layout for
  the chosen opt level before calling the wrapped module;
- parameters belonging to normalization layers are kept fp32 per a
  module-path policy (the equivalent of ``convert_network`` skipping
  ``_BatchNorm`` children, reference ``fp16_utils/fp16util.py:60-69``).

Because the cast sits inside the traced/jitted step, XLA fuses it into the
consuming matmuls; autodiff through the cast routes gradients back to the
fp32 canonical params — which is exactly the reference's master-gradient
flow (``_process_optimizer.py:13-75``) with zero bookkeeping.

The per-call weight-cast cache of the reference (``amp/utils.py:87-119``)
is unnecessary: within one traced step each cast is computed once by CSE;
across steps params change anyway.
"""

from __future__ import annotations

import contextlib
import re
from typing import Any, Callable, Optional, Sequence

import jax
import jax.numpy as jnp

from apex_tpu.amp import _amp_state
from apex_tpu.amp.properties import Properties
from apex_tpu.observability.scopes import device_scope
from apex_tpu.utils.paths import path_components

Pytree = Any

# Module-path components whose params stay fp32 under cast policies.
# BatchNorm matches the reference's keep_batchnorm_fp32; the other norm
# layers are kept fp32 under O1's op policy (the reference's FP32_FUNCS
# includes layer_norm/group_norm — functional_overrides.py:29-65).
# Patterns are matched against individual path components; the short names
# are anchored so e.g. "subnet"/"normal_init" don't accidentally match.
BATCHNORM_PATTERNS = (r"BatchNorm", r"SyncBatchNorm", r"^bn(_|\d|$)",
                      r"_bn$")
NORM_PATTERNS = BATCHNORM_PATTERNS + (r"LayerNorm", r"GroupNorm", r"RMSNorm",
                                      r"^norm(_|\d|$)", r"_norm$",
                                      r"^ln(_|\d|$)", r"_ln$")
# MoE router weights stay fp32 under the O1 and O2 policies too: top-1
# expert assignment is a DISCRETE function of the gate logits, so bf16
# rounding of the router kernel flips token->expert routing decisions
# (Switch Transformer keeps the router in fp32 — "selective precision",
# Fedus et al. 2021 sec 2.4).  models.MoEMlp names its gate Dense
# "router" to pair with this.
ROUTER_PATTERNS = (r"^router$",)


def _path_matches(path, patterns) -> bool:
    names = path_components(path)
    return any(re.search(pat, name) for pat in patterns for name in names)


def _module_matches(module, patterns) -> bool:
    """Does a flax module instance look like a kept-fp32 norm layer?
    Checked against both the class name (BatchNorm, SyncBatchNorm, ...)
    and the instance name (stem_bn, downsample_bn, ...) so it agrees with
    the param-path policy in ``_path_matches``."""
    names = [type(module).__name__]
    inst = getattr(module, "name", None)
    if inst:
        names.append(str(inst))
    return any(re.search(pat, name) for pat in patterns for name in names)


def cast_tree(tree: Pytree, dtype, *, except_patterns: Sequence[str] = ()):
    """Cast float leaves of ``tree`` to ``dtype``; leaves on paths matching
    ``except_patterns`` and all non-float leaves pass through unchanged."""

    def one(path, x):
        x = jnp.asarray(x)
        if not jnp.issubdtype(x.dtype, jnp.floating):
            return x
        if except_patterns and _path_matches(path, except_patterns):
            return x
        return x.astype(dtype)

    return jax.tree_util.tree_map_with_path(one, tree)


def applier(value, cast_fn: Callable):
    """Recursively apply ``cast_fn`` to arrays inside nested containers.

    Port of the reference's ``applier`` (``_initialize.py:36-58``): dives
    into dict/list/tuple (incl. namedtuple) containers, applies ``cast_fn``
    to float arrays, passes everything else through (strings, ints, None,
    non-float arrays such as integer label tensors).
    """
    if isinstance(value, (jax.Array,)) or hasattr(value, "dtype"):
        arr = jnp.asarray(value)
        if jnp.issubdtype(arr.dtype, jnp.floating):
            return cast_fn(arr)
        return value
    if isinstance(value, dict):
        return {k: applier(v, cast_fn) for k, v in value.items()}
    if isinstance(value, tuple) and hasattr(value, "_fields"):  # namedtuple
        return type(value)(*(applier(v, cast_fn) for v in value))
    if isinstance(value, (list, tuple)):
        return type(value)(applier(v, cast_fn) for v in value)
    return value


class AmpModel:
    """Casting wrapper around a flax module (or bare apply_fn).

    Returned by ``amp.initialize``; exposes ``init``/``apply`` with the same
    signatures as the wrapped flax module, inserting the opt-level's dtype
    policy. ``unwrapped`` gives back the original module.
    """

    def __init__(self, module, properties: Properties,
                 keep_fp32_patterns: Optional[Sequence[str]] = None):
        self.module = module
        self._properties = properties
        p = properties
        self.half_dtype = (p.cast_model_type
                           if p.cast_model_type not in (None, False)
                           else jnp.bfloat16)
        if keep_fp32_patterns is not None:
            self.keep_fp32_patterns = tuple(keep_fp32_patterns)
        elif p.cast_ops:  # O1: norm layers + MoE routers stay fp32
            self.keep_fp32_patterns = NORM_PATTERNS + ROUTER_PATTERNS
        elif p.keep_batchnorm_fp32:  # O2 (and O3 w/ override)
            self.keep_fp32_patterns = BATCHNORM_PATTERNS + ROUTER_PATTERNS
        else:
            self.keep_fp32_patterns = ()

    # -- layout helpers ---------------------------------------------------
    @property
    def properties(self) -> Properties:
        return self._properties

    @property
    def unwrapped(self):
        return self.module

    def _compute_cast_needed(self) -> bool:
        p = self._properties
        return bool(p.enabled) and (
            p.cast_ops or p.cast_model_type not in (None, False))

    def canonical_variables(self, variables: Pytree) -> Pytree:
        """Cast freshly-initialized variables to the canonical (optimizer-
        side) layout: fp32 masters for O0/O1/O2, half for O3."""
        p = self._properties
        if not p.enabled:
            return variables
        if p.opt_level == "O3" or (
                p.cast_model_type not in (None, False) and not p.master_weights
                and p.opt_level != "O0"):
            return cast_tree(variables, self.half_dtype,
                             except_patterns=self.keep_fp32_patterns)
        return cast_tree(variables, jnp.float32)

    def compute_variables(self, variables: Pytree) -> Pytree:
        """Cast canonical variables to the compute layout for apply."""
        p = self._properties
        if not p.enabled or _amp_state._amp_state.casts_disabled:
            return variables
        # the masters' cast, and in the backward pass their gradients'
        # cast back, are amp's side of the optimizer
        with device_scope("optimizer"):
            if p.opt_level == "O0":
                return cast_tree(variables, jnp.float32)
            if self._compute_cast_needed():
                return cast_tree(variables, self.half_dtype,
                                 except_patterns=self.keep_fp32_patterns)
        return variables

    def cast_inputs(self, args, kwargs):
        p = self._properties
        if not p.enabled or _amp_state._amp_state.casts_disabled:
            return args, kwargs
        if p.opt_level == "O0":
            cast = lambda x: x.astype(jnp.float32)
        elif self._compute_cast_needed():
            cast = lambda x: x.astype(self.half_dtype)
        else:
            return args, kwargs
        args = tuple(applier(a, cast) for a in args)
        kwargs = {k: applier(v, cast) for k, v in kwargs.items()}
        return args, kwargs

    def _norm_output_recast(self):
        """Context manager installing a flax method interceptor that casts
        kept-fp32 norm layers' *outputs* back to the half compute dtype.

        Without it, flax's dtype promotion silently drags everything
        downstream of a fp32 BatchNorm up to fp32 — including every conv —
        because ``bf16 x  op  f32 scale -> f32`` propagates.  The reference
        does not have this problem: torch's batch_norm with half input and
        fp32 weight emits *half* (``fp16_utils/fp16util.py:22-33`` keeps BN
        fp32 precisely because mixed-dtype BN works there).  The interceptor
        restores those semantics: statistics and affine params stay exactly
        fp32 (flax computes stats in fp32 internally regardless), only the
        returned activation is recast, so the convs stay on the MXU in
        bf16.  Perf-critical: without this, amp O2 ResNet runs its convs in
        fp32 and MFU collapses."""
        import flax.linen as nn

        half = self.half_dtype
        patterns = self.keep_fp32_patterns

        def recast(x):
            if hasattr(x, "dtype") and hasattr(x, "astype") and \
                    x.dtype == jnp.float32:
                return x.astype(half)
            return x

        def interceptor(next_fun, args, kwargs, context):
            out = next_fun(*args, **kwargs)
            # recast only modules that are kept fp32 AND look like norm
            # layers: a user-supplied keep_fp32_patterns entry (e.g. a
            # final classifier kept fp32 for logit accuracy) must keep
            # its fp32 output — the seam mend is for norms only
            if context.method_name == "__call__" and \
                    _module_matches(context.module, patterns) and \
                    _module_matches(context.module, NORM_PATTERNS):
                out = jax.tree.map(recast, out)
            return out

        return nn.intercept_methods(interceptor)

    def _apply_context(self):
        """Interceptor scope for ``apply``: active only when compute casting
        is on AND some params are deliberately kept fp32 (so there is a
        dtype seam to mend).  Installed regardless of whether the wrapped
        object is itself an ``nn.Module``: pipeline wrappers like
        ``models.PipelinedBert`` are plain classes whose INNER applies are
        flax modules, and ``nn.intercept_methods`` is a global trace-time
        context that reaches them; for bare apply_fns with no flax calls
        it is a no-op."""
        if (self._compute_cast_needed() and self.keep_fp32_patterns
                and not _amp_state._amp_state.casts_disabled):
            return self._norm_output_recast()
        return contextlib.nullcontext()

    # -- flax-like surface ------------------------------------------------
    def init(self, rngs, *args, **kwargs) -> Pytree:
        args, kwargs = self.cast_inputs(args, kwargs)
        variables = self.module.init(rngs, *args, **kwargs)
        return self.canonical_variables(variables)

    def apply(self, variables: Pytree, *args, **kwargs):
        variables = self.compute_variables(variables)
        args, kwargs = self.cast_inputs(args, kwargs)
        if hasattr(self.module, "apply"):
            with self._apply_context():
                return self.module.apply(variables, *args, **kwargs)
        return self.module(variables, *args, **kwargs)

    def __call__(self, variables: Pytree, *args, **kwargs):
        return self.apply(variables, *args, **kwargs)

    def loss_and_grad_1f1b(self, variables: Pytree, *args, **kwargs):
        """amp-composed passthrough to the wrapped model's 1F1B
        loss-and-grad (``models.PipelinedBert.loss_and_grad_1f1b``):
        params cast to the compute layout and the norm-seam interceptor
        active around the schedule's rematerialized applies, so grads
        come back in the half compute dtype — exactly how amp grads
        arrive on the autodiff path — for ``AmpOptimizer.step`` to
        unscale onto the fp32 masters."""
        if not hasattr(self.module, "loss_and_grad_1f1b"):
            raise AttributeError(
                f"{type(self.module).__name__} has no loss_and_grad_1f1b "
                "(only pipeline models with the 1F1B schedule do)")
        variables = self.compute_variables(variables)
        with self._apply_context():
            return self.module.loss_and_grad_1f1b(variables, *args,
                                                  **kwargs)
