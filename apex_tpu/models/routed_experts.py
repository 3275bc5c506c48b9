"""What the decoder families with RMSNorm, rotary positions, gated
feed-forwards and routed experts share: ``models.deepseek`` (the
``deepseek_v3`` type), ``models.exaone_moe`` (the ``exaone_moe`` type)
and ``models.lfm2_moe`` (the ``lfm2_moe`` type) build their blocks from
these, and none imports another.

- :class:`RMSNorm`, float32 inside.
- :func:`rotary_angles` / :func:`apply_rotary`: rotation of pairs, from
  the interleaved or the half-split order.
- :class:`GatedMLP`: ``W_down(silu(W_gate x) * (W_up x))``.
- :func:`route` and :class:`RoutedExperts`: sigmoid scores in float32,
  the ``top_k`` largest of ``score + e_score_correction_bias`` chosen
  (the bias selects and does not weigh), their scores normalised and
  scaled, the chosen experts' gated feed-forwards summed with those
  weights beside the shared experts (one gated feed-forward of
  ``shared_width``, none where it is 0).  No capacity and no dropped
  token: the (token, expert) pairs are sorted by expert and go through
  ``ops.grouped_matmul`` three times.  The layer is told which experts
  it holds (``ExpertsSpec.held``): it routes over all ``router_width``
  and computes its own experts' part, which is what expert parallelism
  asks of it; the exchange is not here.  Rows that are no tokens (idle
  slots, padding: ``CacheView.live``) are routed nowhere.

A family says what its expert layer is with an :class:`ExpertsSpec`
made from its own published keys.
"""

from __future__ import annotations

import dataclasses
from typing import Tuple

import flax.linen as nn
import jax
import jax.numpy as jnp
from jax import lax

from apex_tpu.ops.grouped_matmul import grouped_matmul


class RMSNorm(nn.Module):
    eps: float

    @nn.compact
    def __call__(self, x):
        scale = self.param("scale", nn.initializers.ones, (x.shape[-1],))
        xf = x.astype(jnp.float32)
        y = xf * lax.rsqrt(jnp.mean(xf * xf, -1, keepdims=True) + self.eps)
        return (y * scale.astype(jnp.float32)).astype(x.dtype)


def rotary_angles(positions, dim: int, theta: float):
    """``(cos, sin)`` (..., dim // 2) in float32 for ``positions``
    (...,): pair ``i`` turns by ``position * theta ** (-2i / dim)``."""
    inv = theta ** (-jnp.arange(0, dim, 2, dtype=jnp.float32) / dim)
    ang = positions.astype(jnp.float32)[..., None] * inv
    return jnp.cos(ang), jnp.sin(ang)


def apply_rotary(x, cos, sin, interleave: bool):
    """Rotate the pairs of ``x`` (..., dim) by ``cos``/``sin``
    (..., dim // 2).  ``interleave``: the pairs are ``(2i, 2i + 1)``
    and are first brought to the half-split order ``(i, i + dim/2)``,
    in which the result stays (queries and keys alike, so their
    products do not notice)."""
    xf = x.astype(jnp.float32)
    half = x.shape[-1] // 2
    if interleave:
        x1, x2 = xf[..., 0::2], xf[..., 1::2]
    else:
        x1, x2 = xf[..., :half], xf[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin],
                           -1).astype(x.dtype)


class GatedMLP(nn.Module):
    """``W_down(silu(W_gate x) * (W_up x))``."""

    width: int
    init_range: float

    @nn.compact
    def __call__(self, x):
        init = nn.initializers.normal(self.init_range)
        h = x.shape[-1]
        gate = self.param("gate_proj", init, (h, self.width))
        up = self.param("up_proj", init, (h, self.width))
        down = self.param("down_proj", init, (self.width, h))
        return (nn.silu(x @ gate) * (x @ up)) @ down


def route(scores, bias, k: int, scaling: float, normalise: bool,
          eps: float = 1e-20):
    """``scores`` (T, E) float32 sigmoid scores, ``bias`` (E,) the
    selection bias: the ``k`` experts with the largest ``score + bias``
    and their weights ``score / (sum(chosen scores) + eps) * scaling``.
    The bias selects and does not weigh."""
    _, chosen = lax.top_k(scores + bias, k)
    picked = jnp.take_along_axis(scores, chosen, axis=-1)
    if normalise:
        picked = picked / (jnp.sum(picked, -1, keepdims=True) + eps)
    return chosen, picked * scaling


@dataclasses.dataclass(frozen=True)
class ExpertsSpec:
    """One expert layer by its sizes: the router's ``router_width``
    scores of which ``top_k`` are chosen, routed experts ``width`` wide
    of which this layer holds ``held`` = ``(first, count)``, the shared
    experts as one gated feed-forward ``shared_width`` wide (0: none),
    and ``norm_eps`` the normaliser's epsilon (:func:`route`)."""

    router_width: int
    top_k: int
    width: int
    held: Tuple[int, int]
    shared_width: int
    scaling: float
    normalise: bool
    init_range: float
    norm_eps: float = 1e-20


def check_held(held, router_width: int):
    """``held`` as a range of the ``router_width`` routed experts, or a
    ``ValueError``."""
    first, count = held
    if first < 0 or count < 1 or first + count > router_width:
        raise ValueError(f"experts_held={held} is no range of the "
                         f"{router_width} routed experts")


class RoutedExperts(nn.Module):
    spec: ExpertsSpec

    @nn.compact
    def __call__(self, x, live=None):
        """``x`` (B, S, hidden) after its norm; ``live`` (B, S) which
        rows are tokens (None: all).  Returns the layer's output and
        the rows each expert held here was given (held,)."""
        sp = self.spec
        b, s, h = x.shape
        e, k, f = sp.router_width, sp.top_k, sp.width
        first, held = sp.held
        init = nn.initializers.normal(sp.init_range)
        router = self.param("router", init, (h, e))
        bias = self.param("e_score_correction_bias",
                          nn.initializers.zeros, (e,))
        w_gate = self.param("experts_gate_proj", init, (held, h, f))
        w_up = self.param("experts_up_proj", init, (held, h, f))
        w_down = self.param("experts_down_proj", init, (held, f, h))
        xt = x.reshape(b * s, h)

        with jax.named_scope("moe_router"):
            scores = jax.nn.sigmoid(jnp.dot(
                xt.astype(jnp.float32), router.astype(jnp.float32),
                precision=lax.Precision.HIGHEST))
            chosen, weights = route(scores, bias.astype(jnp.float32), k,
                                    sp.scaling, sp.normalise, sp.norm_eps)
            # for whoever asks (``mutable=["intermediates"]``): tests
            # bound how often a precision picks another expert
            self.sow("intermediates", "chosen", chosen.reshape(b, s, k))

        with jax.named_scope("moe_experts"):
            mine = (chosen >= first) & (chosen < first + held)
            if live is not None:
                mine = mine & live.reshape(b * s, 1)
            # pairs sorted by expert; those that are nobody's here go
            # last, past every group, where nothing is computed
            key = jnp.where(mine, chosen - first, held).reshape(-1)
            order = jnp.argsort(key, stable=True)
            sizes = jnp.bincount(key, length=held + 1)[:held].astype(
                jnp.int32)
            rows = xt[order // k]                           # (T * k, h)
            act = nn.silu(grouped_matmul(rows, w_gate, sizes)) \
                * grouped_matmul(rows, w_up, sizes)
            out = grouped_matmul(act, w_down, sizes)        # (T * k, h)
            back = jnp.zeros_like(order).at[order].set(
                jnp.arange(order.shape[0], dtype=order.dtype))
            out = out[back].reshape(b * s, k, h).astype(jnp.float32)
            routed = jnp.sum(
                out * jnp.where(mine, weights, 0.0)[..., None], axis=1)

        if sp.shared_width:
            with jax.named_scope("moe_shared"):
                routed = routed + GatedMLP(
                    sp.shared_width, sp.init_range,
                    name="shared_experts")(xt).astype(jnp.float32)
        y = routed.astype(x.dtype)
        return y.reshape(b, s, h), sizes
