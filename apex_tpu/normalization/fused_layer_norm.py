"""FusedLayerNorm — layer normalization with Pallas TPU kernels.

Re-design of reference ``apex/normalization/fused_layer_norm.py`` and its
CUDA kernels (``csrc/layer_norm_cuda_kernel.cu``): input viewed as
(n1, n2) with n2 = prod(normalized_shape); forward computes per-row
mean/invvar (Welford in the reference; masked two-pass sums here — same
fp32 statistics) and saves them for backward
(``cuApplyLayerNorm`` :280 returns (output, mean, invvar)); backward
computes grad_input in-kernel and reduces grad_gamma/grad_beta across rows
(``cuComputeGradInput`` :524, ``cuComputePartGradGammaBeta`` :405 — the
cross-row reduction is left to XLA here, which emits an efficient
column-sum).

The Pallas path runs rows per grid step with fp32 math whatever the input
dtype (matching the kernel's accumulation dtype); a pure-jnp path is the
CPU fallback and parity oracle, exactly like the reference's CPU fallback
(``fused_layer_norm.py:148-150``).
"""

from __future__ import annotations

import functools
import numbers
from typing import Any, Optional, Sequence, Tuple, Union

import flax.linen as nn
import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl

from apex_tpu.ops.pallas_utils import (LANES, on_tpu, pallas_auto_gate,
                                       union_vma)

Shape = Union[int, Sequence[int]]


def _norm_shape(normalized_shape: Shape) -> Tuple[int, ...]:
    if isinstance(normalized_shape, numbers.Integral):
        return (int(normalized_shape),)
    return tuple(int(d) for d in normalized_shape)


# ---------------------------------------------------------------------------
# jnp reference path
# ---------------------------------------------------------------------------

def _ln_stats(x2: jax.Array, eps: float):
    mean = jnp.mean(x2, axis=-1)
    var = jnp.mean(jnp.square(x2), axis=-1) - jnp.square(mean)
    invvar = jax.lax.rsqrt(var + eps)
    return mean, invvar


def _ln_forward_jnp(x2: jax.Array, eps: float):
    mean, invvar = _ln_stats(x2, eps)
    y = (x2 - mean[:, None]) * invvar[:, None]
    return y, mean, invvar


# ---------------------------------------------------------------------------
# Pallas kernels
# ---------------------------------------------------------------------------

def _ln_fwd_kernel(x_ref, y_ref, mean_ref, invvar_ref, *, n2: int,
                   eps: float):
    x = x_ref[:].astype(jnp.float32)
    cols = jax.lax.broadcasted_iota(jnp.int32, x.shape, 1)
    mask = cols < n2
    xm = jnp.where(mask, x, 0.0)
    mean = jnp.sum(xm, axis=1, keepdims=True) / n2
    d = jnp.where(mask, x - mean, 0.0)
    var = jnp.sum(d * d, axis=1, keepdims=True) / n2
    invvar = jax.lax.rsqrt(var + eps)
    y_ref[:] = (d * invvar).astype(y_ref.dtype)
    mean_ref[:] = jnp.broadcast_to(mean, mean_ref.shape)
    invvar_ref[:] = jnp.broadcast_to(invvar, invvar_ref.shape)


def _ln_bwd_kernel(dy_ref, xhat_ref, invvar_ref, dx_ref, *, n2: int):
    # dy here is already gamma-scaled (dy * gamma) by the caller
    dy = dy_ref[:].astype(jnp.float32)
    xhat = xhat_ref[:].astype(jnp.float32)
    invvar = invvar_ref[:, 0:1]
    cols = jax.lax.broadcasted_iota(jnp.int32, dy.shape, 1)
    mask = cols < n2
    dy = jnp.where(mask, dy, 0.0)
    xhat = jnp.where(mask, xhat, 0.0)
    sum1 = jnp.sum(dy, axis=1, keepdims=True)
    sum2 = jnp.sum(dy * xhat, axis=1, keepdims=True)
    dx = invvar * (dy - (sum1 + xhat * sum2) / n2)
    dx_ref[:] = jnp.where(mask, dx, 0.0).astype(dx_ref.dtype)


def _pad_cols(x2: jax.Array) -> Tuple[jax.Array, int]:
    n2 = x2.shape[1]
    n2p = max(LANES, ((n2 + LANES - 1) // LANES) * LANES)
    if n2p != n2:
        x2 = jnp.pad(x2, ((0, 0), (0, n2p - n2)))
    return x2, n2


def _row_block(n2p: int, itemsize: int = 4) -> int:
    # keep each VMEM operand block <= ~2 MiB
    rows = max(8, min(512, (2 * 1024 * 1024) // (n2p * itemsize)))
    return (rows // 8) * 8 or 8


@functools.partial(jax.jit, static_argnames=("eps", "interpret"))
def _ln_fwd_pallas(x2: jax.Array, eps: float, interpret: bool):
    n1 = x2.shape[0]
    xp, n2 = _pad_cols(x2)
    rows = _row_block(xp.shape[1])
    n1p = ((n1 + rows - 1) // rows) * rows
    if n1p != n1:
        xp = jnp.pad(xp, ((0, n1p - n1), (0, 0)))
    grid = (n1p // rows,)
    row_spec = pl.BlockSpec((rows, xp.shape[1]), lambda i: (i, 0))
    stat_spec = pl.BlockSpec((rows, LANES), lambda i: (i, 0))
    vma = union_vma(x2)
    y, mean, invvar = pl.pallas_call(
        functools.partial(_ln_fwd_kernel, n2=n2, eps=eps),
        grid=grid,
        in_specs=[row_spec],
        out_specs=[row_spec, stat_spec, stat_spec],
        out_shape=[
            # xhat stays fp32: it is the backward residual and feeds the
            # affine scale — rounding it to a half dtype here would inject
            # O(eps_bf16) error that the dweight row-sum amplifies (the
            # reference keeps fp32 stats for the same reason,
            # layer_norm_cuda_kernel.cu accumulation dtype)
            jax.ShapeDtypeStruct(xp.shape, jnp.float32, vma=vma),
            jax.ShapeDtypeStruct((n1p, LANES), jnp.float32, vma=vma),
            jax.ShapeDtypeStruct((n1p, LANES), jnp.float32, vma=vma),
        ],
        interpret=interpret,
        name="_ln_fwd_kernel",
    )(xp)
    return y[:n1, :n2], mean[:n1, 0], invvar[:n1, 0]


@functools.partial(jax.jit, static_argnames=("interpret",))
def _ln_bwd_pallas(dy2: jax.Array, xhat2: jax.Array, invvar: jax.Array,
                   interpret: bool):
    n1 = dy2.shape[0]
    dyp, n2 = _pad_cols(dy2)
    xhp, _ = _pad_cols(xhat2)
    rows = _row_block(dyp.shape[1])
    n1p = ((n1 + rows - 1) // rows) * rows
    if n1p != n1:
        dyp = jnp.pad(dyp, ((0, n1p - n1), (0, 0)))
        xhp = jnp.pad(xhp, ((0, n1p - n1), (0, 0)))
    iv = jnp.pad(invvar, (0, n1p - n1))[:, None] * jnp.ones((1, LANES),
                                                            jnp.float32)
    grid = (n1p // rows,)
    row_spec = pl.BlockSpec((rows, dyp.shape[1]), lambda i: (i, 0))
    stat_spec = pl.BlockSpec((rows, LANES), lambda i: (i, 0))
    dx = pl.pallas_call(
        functools.partial(_ln_bwd_kernel, n2=n2),
        grid=grid,
        in_specs=[row_spec, row_spec, stat_spec],
        out_specs=row_spec,
        out_shape=jax.ShapeDtypeStruct(dyp.shape, dy2.dtype,
                                       vma=union_vma(dy2, xhat2, invvar)),
        interpret=interpret,
        name="_ln_bwd_kernel",
    )(dyp, xhp, iv)
    return dx[:n1, :n2]


# ---------------------------------------------------------------------------
# custom-vjp functional API
# ---------------------------------------------------------------------------

def _use_pallas(flag: Optional[bool]) -> bool:
    # partial-manual shard_map regions (pipelined TP) auto-partition
    # every op — Mosaic calls are rejected there, jnp path instead
    return pallas_auto_gate(flag)


def _match_vma(cotangent, primal):
    """Reduce a cotangent over the mesh axes it varies on but its primal
    does not. Under shard_map, JAX's transpose rules automatically psum
    cotangents of replicated (invariant) inputs; a custom_vjp must do the
    same by hand or the vma check rejects the bwd output. No-op outside
    shard_map (both vma sets empty)."""
    extra = jax.typeof(cotangent).vma - jax.typeof(primal).vma
    if extra:
        cotangent = jax.lax.psum(cotangent, tuple(sorted(extra)))
    return cotangent


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5))
def fused_layer_norm_affine(x, weight, bias, normalized_shape,
                            eps: float = 1e-5,
                            use_pallas: Optional[bool] = None):
    """y = LN(x) * weight + bias over trailing ``normalized_shape`` dims
    (reference ``fused_layer_norm_affine``, ``fused_layer_norm.py:58``)."""
    out, _ = _fla_fwd(x, weight, bias, normalized_shape, eps, use_pallas)
    return out


def _fla_fwd(x, weight, bias, normalized_shape, eps, use_pallas):
    ns = _norm_shape(normalized_shape)
    n2 = int(np.prod(ns))
    lead = x.shape[:x.ndim - len(ns)]
    x2 = x.reshape(-1, n2)
    if _use_pallas(use_pallas):
        xhat2, mean, invvar = _ln_fwd_pallas(x2, eps, not on_tpu())
    else:
        x32 = x2.astype(jnp.float32)
        xhat2, mean, invvar = _ln_forward_jnp(x32, eps)
    w2 = weight.reshape(-1).astype(jnp.float32)
    b2 = bias.reshape(-1).astype(jnp.float32)
    y = (xhat2 * w2[None, :] + b2[None, :]).astype(x.dtype)
    out = y.reshape(lead + ns)
    return out, (xhat2, invvar, weight)


def _fla_bwd(normalized_shape, eps, use_pallas, res, dy):
    xhat2, invvar, weight = res
    in_dtype = dy.dtype  # output dtype == input dtype
    ns = _norm_shape(normalized_shape)
    n2 = int(np.prod(ns))
    dy2 = dy.reshape(-1, n2).astype(jnp.float32)
    w2 = weight.reshape(-1).astype(jnp.float32)
    dyw = dy2 * w2[None, :]
    if _use_pallas(use_pallas):
        dx2 = _ln_bwd_pallas(dyw, xhat2, invvar, not on_tpu())
    else:
        sum1 = jnp.sum(dyw, axis=1, keepdims=True)
        sum2 = jnp.sum(dyw * xhat2, axis=1, keepdims=True)
        dx2 = invvar[:, None] * (dyw - (sum1 + xhat2 * sum2) / n2)
    dweight = jnp.sum(dy2 * xhat2, axis=0).reshape(ns).astype(weight.dtype)
    dbias = jnp.sum(dy2, axis=0).reshape(ns).astype(weight.dtype)
    dweight = _match_vma(dweight, weight)
    dbias = _match_vma(dbias, weight)
    dx = dx2.astype(in_dtype).reshape(dy.shape)
    return dx, dweight, dbias


fused_layer_norm_affine.defvjp(
    lambda x, w, b, ns, eps, up: _fla_fwd(x, w, b, ns, eps, up),
    _fla_bwd)


@functools.partial(jax.custom_vjp, nondiff_argnums=(1, 2, 3))
def fused_layer_norm(x, normalized_shape, eps: float = 1e-5,
                     use_pallas: Optional[bool] = None):
    """Non-affine LN (reference ``fused_layer_norm``, :60)."""
    out, _ = _fl_fwd(x, normalized_shape, eps, use_pallas)
    return out


def _fl_fwd(x, normalized_shape, eps, use_pallas):
    ns = _norm_shape(normalized_shape)
    n2 = int(np.prod(ns))
    lead = x.shape[:x.ndim - len(ns)]
    x2 = x.reshape(-1, n2)
    if _use_pallas(use_pallas):
        xhat2, mean, invvar = _ln_fwd_pallas(x2, eps, not on_tpu())
    else:
        xhat2, mean, invvar = _ln_forward_jnp(x2.astype(jnp.float32), eps)
    return xhat2.astype(x.dtype).reshape(lead + ns), (xhat2, invvar)


def _fl_bwd(normalized_shape, eps, use_pallas, res, dy):
    xhat2, invvar = res
    in_dtype = dy.dtype  # output dtype == input dtype
    ns = _norm_shape(normalized_shape)
    n2 = int(np.prod(ns))
    dy2 = dy.reshape(-1, n2).astype(jnp.float32)
    if _use_pallas(use_pallas):
        dx2 = _ln_bwd_pallas(dy2, xhat2, invvar, not on_tpu())
    else:
        sum1 = jnp.sum(dy2, axis=1, keepdims=True)
        sum2 = jnp.sum(dy2 * xhat2, axis=1, keepdims=True)
        dx2 = invvar[:, None] * (dy2 - (sum1 + xhat2 * sum2) / n2)
    return (dx2.astype(in_dtype).reshape(dy.shape),)


fused_layer_norm.defvjp(
    lambda x, ns, eps, up: _fl_fwd(x, ns, eps, up), _fl_bwd)


# ---------------------------------------------------------------------------
# flax module
# ---------------------------------------------------------------------------

class FusedLayerNorm(nn.Module):
    """Module form (reference ``FusedLayerNorm``, ``fused_layer_norm.py:64``).

    ``normalized_shape`` may be an int or shape tuple; ``elementwise_affine``
    adds weight/bias params (named scale/bias for flax ecosystem interop).
    """

    normalized_shape: Any
    eps: float = 1e-5
    elementwise_affine: bool = True
    param_dtype: Any = jnp.float32
    use_pallas: Optional[bool] = None

    @nn.compact
    def __call__(self, x):
        ns = _norm_shape(self.normalized_shape)
        if tuple(x.shape[-len(ns):]) != ns:
            raise ValueError(
                f"input trailing dims {x.shape[-len(ns):]} != "
                f"normalized_shape {ns}")
        if self.elementwise_affine:
            weight = self.param("scale", nn.initializers.ones, ns,
                                self.param_dtype)
            bias = self.param("bias", nn.initializers.zeros, ns,
                              self.param_dtype)
            return fused_layer_norm_affine(x, weight, bias, ns, self.eps,
                                           self.use_pallas)
        return fused_layer_norm(x, ns, self.eps, self.use_pallas)
