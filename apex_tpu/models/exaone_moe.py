"""Decoder with window and full attention layers mixed, grouped
key-value heads and routed experts (the published ``exaone_moe`` model
type: LG AI Research's K-EXAONE).

What differs from ``models.deepseek``, with which it shares its norms,
rotation, gated feed-forwards and expert layer
(``models.routed_experts``):

- **Grouped key-value heads.**  ``num_attention_heads`` query heads
  over ``num_key_value_heads`` key-value heads of ``head_dim``: query
  head ``i`` reads key-value head ``i // (heads / kv heads)``.  The
  cache keeps every key-value head's ``K_h | V_h`` a token and layer
  (``cache_row()``), an eighth of what the query heads would.
- **A norm a head.**  Queries and keys are each normalised over a
  head's ``head_dim`` values with a weight of their own (``q_norm``,
  ``k_norm``), float32 inside, before the rotation.
- **Layers of two kinds** (``layer_types``, the published ``"LLLG"``
  repeated).  A ``sliding_attention`` layer rotates queries and keys
  (all ``head_dim`` values of a head, half-split, base ``rope_theta``)
  and its row at position ``p`` attends ``p - sliding_window + 1 .. p``;
  a ``full_attention`` layer takes NO rotation and attends everything
  before it.  ``layer_windows()`` tells the serving engine which is
  which; the model hands ``CacheView.attend`` its queries and fresh
  keys and values and never learns where either kind's rows live.
- **The expert layer** is ``deepseek_v3``'s (sigmoid scores, a
  selection bias, normalised top-k weights times
  ``routed_scaling_factor``, one shared expert), held by
  ``experts_held`` as there: the router keeps its published width
  ``num_experts``.

Pre-norm blocks (``x + Attn(RMS(x))``; ``x + FF(RMS(x))``), a final
RMSNorm and an untied head.  The per-head norms, the rotation on the
sliding layers alone and the place of the two layer norms follow the
family's published ``exaone4`` modelling code and the ``deepseek_v3``
block whose expert layer the type reuses; the configuration's keys do
not state them (``benchmarks/configs/k-exaone-236b-a23b.json``,
``assumed``).  The multi-token prediction module
(``num_nextn_predict_layers``) is not here: it drafts for speculation
and is worth nothing without the published weights.  Served, not
trained here: the grouped product has no backward pass yet.
"""

from __future__ import annotations

import dataclasses
from typing import Optional, Tuple

import flax.linen as nn
import jax
import jax.numpy as jnp

from apex_tpu.models.family import CacheRow
from apex_tpu.models.routed_experts import (
    ExpertsSpec,
    GatedMLP,
    RMSNorm,
    RoutedExperts,
    apply_rotary,
    check_held,
    rotary_angles,
)
from apex_tpu.observability.scopes import device_scope

NEG_INF = -1e9
_KINDS = {"L": "sliding_attention", "G": "full_attention"}


@dataclasses.dataclass(frozen=True)
class ExaoneMoeConfig:
    """The published keys under their published names."""

    vocab_size: int = 153600
    hidden_size: int = 6144
    num_hidden_layers: int = 48
    num_attention_heads: int = 64
    num_key_value_heads: int = 8
    head_dim: int = 128
    intermediate_size: int = 18432
    moe_intermediate_size: int = 2048
    num_experts: int = 128
    num_shared_experts: int = 1
    num_experts_per_tok: int = 8
    first_k_dense_replace: int = 1
    sliding_window: int = 128
    sliding_window_pattern: str = "LLLG"
    # one of "sliding_attention" / "full_attention" a layer; None: the
    # pattern repeated
    layer_types: Optional[Tuple[str, ...]] = None
    rope_theta: float = 1e6
    rms_norm_eps: float = 1e-5
    routed_scaling_factor: float = 2.5
    norm_topk_prob: bool = True
    max_position_embeddings: int = 262144
    initializer_range: float = 0.02
    # (first, count) of the routed experts whose weights this layer
    # holds; None = all of them.  The router keeps its published width.
    experts_held: Optional[Tuple[int, int]] = None

    def __post_init__(self):
        check_held(self.held, self.num_experts)
        kinds = self.kinds
        if len(kinds) != self.num_hidden_layers \
                or set(kinds) - set(_KINDS.values()):
            raise ValueError(
                f"layer_types has to name sliding_attention or "
                f"full_attention for each of {self.num_hidden_layers} "
                f"layers; got {kinds}")

    @property
    def held(self) -> Tuple[int, int]:
        return self.experts_held or (0, self.num_experts)

    @property
    def kinds(self) -> Tuple[str, ...]:
        if self.layer_types is not None:
            return tuple(self.layer_types)
        pattern = self.sliding_window_pattern
        return tuple(_KINDS[pattern[i % len(pattern)]]
                     for i in range(self.num_hidden_layers))

    @property
    def num_expert_layers(self) -> int:
        return max(0, self.num_hidden_layers - self.first_k_dense_replace)

    def experts_spec(self) -> ExpertsSpec:
        return ExpertsSpec(
            router_width=self.num_experts, top_k=self.num_experts_per_tok,
            width=self.moe_intermediate_size, held=self.held,
            shared_width=self.num_shared_experts
            * self.moe_intermediate_size,
            scaling=self.routed_scaling_factor,
            normalise=self.norm_topk_prob,
            init_range=self.initializer_range)

    # -- what the serving engine asks a family (models/family.py) ---------

    def build_model(self, kv_quant: bool = False):
        if kv_quant:
            raise NotImplementedError(
                "an int8 pool keeps one scale a query head; this row is "
                "laid out by key-value heads (ROADMAP.md Reach)")
        return ExaoneMoeLMHeadModel(self)

    def cache_row(self) -> CacheRow:
        """Every key-value head's ``K_h | V_h`` a token and layer, each
        read by ``heads / kv heads`` query heads."""
        return CacheRow.kv(self.num_attention_heads, self.head_dim,
                           self.num_key_value_heads)

    def layer_windows(self) -> Tuple[Optional[int], ...]:
        """``sliding_window`` for a sliding layer, None for a full
        one."""
        return tuple(self.sliding_window if k == "sliding_attention"
                     else None for k in self.kinds)

    def serving_counters(self):
        """Tokens routed to each expert held, by expert layer."""
        if not self.num_expert_layers:
            return {}
        return {"routed": (self.num_expert_layers, self.held[1])}


def _init(cfg):
    return nn.initializers.normal(cfg.initializer_range)


class ExaoneMoeAttention(nn.Module):
    cfg: ExaoneMoeConfig

    @nn.compact
    def __call__(self, x, positions, cache_view=None, layer: int = 0):
        """``x`` (B, S, hidden) after its norm, ``positions`` (B, S).
        Returns ``(out, view)``: the view after this layer's write of
        the new keys and values, None without one."""
        cfg = self.cfg
        h, nh, nkv, d = cfg.hidden_size, cfg.num_attention_heads, \
            cfg.num_key_value_heads, cfg.head_dim
        window = cfg.layer_windows()[layer]
        init = _init(cfg)
        wq = self.param("q_proj", init, (h, nh, d))
        wk = self.param("k_proj", init, (h, nkv, d))
        wv = self.param("v_proj", init, (h, nkv, d))
        wo = self.param("o_proj", init, (nh, d, h))

        q = RMSNorm(cfg.rms_norm_eps, name="q_norm")(
            jnp.einsum("bsh,hnd->bsnd", x, wq))
        k = RMSNorm(cfg.rms_norm_eps, name="k_norm")(
            jnp.einsum("bsh,hnd->bsnd", x, wk))
        v = jnp.einsum("bsh,hnd->bsnd", x, wv)
        if window is not None:
            # the sliding layers rotate; the full ones take no position
            cos, sin = rotary_angles(positions, d, cfg.rope_theta)
            q = apply_rotary(q, cos[:, :, None], sin[:, :, None], False)
            k = apply_rotary(k, cos[:, :, None], sin[:, :, None], False)

        if cache_view is not None:
            o, cache_view = cache_view.attend(layer, q, (k, v))
        else:
            # the full forward pass over whole rows of tokens
            b, t = x.shape[:2]
            s = jnp.einsum(
                "bqgpd,bkgd->bgpqk", q.reshape(b, t, nkv, nh // nkv, d),
                k).astype(jnp.float32) * float(d) ** -0.5
            ahead = jnp.arange(t)[:, None] - jnp.arange(t)[None, :]
            seen = ahead >= 0
            if window is not None:
                seen = seen & (ahead < window)
            p = jax.nn.softmax(jnp.where(seen, s, NEG_INF), axis=-1)
            o = jnp.einsum("bgpqk,bkgd->bqgpd", p.astype(x.dtype),
                           v).reshape(b, t, nh, d)
        return jnp.einsum("bsnd,ndh->bsh", o, wo), cache_view


class ExaoneMoeBlock(nn.Module):
    """Pre-norm: ``x + Attn(RMS(x))``; ``x + FF(RMS(x))``, ``FF`` the
    dense gated feed-forward in the leading layers and the expert layer
    after them."""

    cfg: ExaoneMoeConfig
    layer: int

    @nn.compact
    def __call__(self, x, positions, cache_view=None):
        cfg = self.cfg
        live = cache_view.live if cache_view is not None else None
        with device_scope("attention"):
            a, kept = ExaoneMoeAttention(cfg, name="attention")(
                RMSNorm(cfg.rms_norm_eps, name="input_layernorm")(x),
                positions, cache_view, self.layer)
            x = x + a
        h = RMSNorm(cfg.rms_norm_eps, name="post_attention_layernorm")(x)
        if self.layer < cfg.first_k_dense_replace:
            h = GatedMLP(cfg.intermediate_size, cfg.initializer_range,
                         name="mlp")(h)
            with device_scope("mlp"):
                return x + h, kept
        y, sizes = RoutedExperts(cfg.experts_spec(), name="moe")(h, live)
        if cache_view is not None and "routed" in kept.cache:
            kept = kept.count(
                "routed", self.layer - cfg.first_k_dense_replace, sizes)
        with device_scope("moe_experts"):
            return x + y, kept


class ExaoneMoeLMHeadModel(nn.Module):
    """Token embedding -> blocks -> final RMSNorm -> untied head.
    Returns (B, S, V) float32 logits.  The serving hooks are
    ``models.gpt.GPTLMHeadModel``'s (``positions``, ``cache_views``,
    ``return_kv``); without a view the call is the plain causal forward
    over whole rows of tokens."""

    cfg: ExaoneMoeConfig

    @nn.compact
    def __call__(self, input_ids, deterministic: bool = True,
                 positions=None, cache_views=None,
                 return_kv: bool = False):
        del deterministic                    # no dropout in this family
        cfg = self.cfg
        init = _init(cfg)
        embed = self.param("embed_tokens", init,
                           (cfg.vocab_size, cfg.hidden_size))
        with device_scope("embed"):
            x = jnp.take(embed, input_ids, axis=0)
            if positions is None:
                positions = jnp.broadcast_to(
                    jnp.arange(input_ids.shape[1], dtype=jnp.int32)[None],
                    input_ids.shape)
        view = cache_views
        for i in range(cfg.num_hidden_layers):
            x, view = ExaoneMoeBlock(cfg, i, name=f"block_{i}")(
                x, positions, view)
        x = RMSNorm(cfg.rms_norm_eps, block="head", name="norm")(x)
        head = self.param("lm_head", init,
                          (cfg.hidden_size, cfg.vocab_size))
        with device_scope("head"):
            logits = jnp.einsum("bsh,hv->bsv", x, head,
                                preferred_element_type=jnp.float32)
        if return_kv:
            return logits, view
        return logits
