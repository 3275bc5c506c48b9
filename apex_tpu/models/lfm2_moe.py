"""Decoder with gated short-convolution layers among grouped-query
attention layers and routed experts (the published ``lfm2_moe`` model
type: Liquid AI's LFM2 mixtures of experts).

What differs from ``models.exaone_moe``, with which it shares its norms,
rotation, gated feed-forwards and expert layer
(``models.routed_experts``):

- **Layers of two kinds** (``layer_types``).  A ``conv`` layer mixes
  tokens with no attention: ``B | C | x = in_proj(h)``,
  ``u = B * x``, a depthwise causal convolution of ``conv_L_cache`` taps
  along the sequence (``z_t = sum_i w_i * u_{t - K + 1 + i}``, ``u``
  before position 0 is 0), ``out_proj(C * z)``.  What a sequence
  carries through such a layer is its last ``conv_L_cache - 1`` rows of
  ``u``, whatever its length: ``layer_states()`` tells the serving
  engine so, and the model hands ``CacheView.convolve`` its ``B | C |
  x`` rows and never learns where the state lives.  A
  ``full_attention`` layer: ``num_attention_heads`` query heads over
  ``num_key_value_heads`` key-value heads of ``hidden / heads`` values,
  queries and keys each RMS-normalised over a head with a weight of
  their own, rotated (half-split, base ``rope_theta``), causal softmax.
- **The feed-forward**: a gated one of ``intermediate_size`` in the
  leading ``num_dense_layers`` layers, then ``num_experts`` routed
  experts of ``moe_intermediate_size``, ``num_experts_per_tok`` a token
  by a sigmoid with a selection bias (``use_expert_bias``), the chosen
  scores normalised to sum to 1 (``norm_topk_prob``; epsilon 1e-6)
  times ``routed_scaling_factor``, and NO shared expert.
- **A tied head**: the embedding table, after a final RMSNorm.

Pre-norm blocks (``x + Mix(RMS(x))``; ``x + FF(RMS(x))``).  The short
convolution, the attention with its per-head norms and the place of the
norms follow the family's dense modelling code (``lfm2``: transformers'
``Lfm2ShortConv``, ``Lfm2Attention``, ``Lfm2DecoderLayer``); the
sigmoid scoring, the normaliser's epsilon and the tie are the family's
published ``lfm2_moe`` code and defaults, which the configuration's keys
do not state (``benchmarks/configs/lfm2-24b-a2b.json``, ``assumed``).
Served, not trained here: the grouped product has no backward pass yet.
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
    rotary_angles,
)
from apex_tpu.observability.scopes import device_scope

NEG_INF = -1e9
_KINDS = ("conv", "full_attention")
# the top-k normaliser's epsilon in the family's published code
TOPK_EPS = 1e-6


def _default_rope():
    return {"rope_theta": 1e6, "rope_type": "default"}


@dataclasses.dataclass(frozen=True)
class Lfm2MoeConfig:
    """The published keys under their published names (LFM2-24B-A2B's
    values by default)."""

    vocab_size: int = 65536
    hidden_size: int = 2048
    num_hidden_layers: int = 40
    num_attention_heads: int = 32
    num_key_value_heads: int = 8
    intermediate_size: int = 11776
    moe_intermediate_size: int = 1536
    num_experts: int = 64
    num_experts_per_tok: int = 4
    num_dense_layers: int = 2
    # one of "conv" / "full_attention" a layer
    layer_types: Tuple[str, ...] = (
        ("conv", "conv", "full_attention") + ("conv",) * 3
        + ("full_attention",) + (("conv",) * 3 + ("full_attention",)) * 8
        + ("conv",))
    conv_L_cache: int = 3
    conv_bias: bool = False
    use_expert_bias: bool = True
    norm_topk_prob: bool = True
    routed_scaling_factor: float = 1.0
    rope_parameters: dict = dataclasses.field(default_factory=_default_rope,
                                              hash=False)
    norm_eps: float = 1e-5
    max_position_embeddings: int = 128000
    initializer_range: float = 0.02

    def __post_init__(self):
        kinds = tuple(self.layer_types)
        object.__setattr__(self, "layer_types", kinds)
        if len(kinds) != self.num_hidden_layers or set(kinds) - set(_KINDS):
            raise ValueError(
                f"layer_types has to name conv or full_attention for each "
                f"of {self.num_hidden_layers} layers; got {kinds}")
        if self.hidden_size % self.num_attention_heads:
            raise ValueError(f"hidden_size {self.hidden_size} is no whole "
                             f"number of {self.num_attention_heads} heads")
        # what the published lfm2_moe models are, and all this family
        # computes
        other = {k: v for k, v in (
            ("conv_bias", self.conv_bias),
            ("use_expert_bias", not self.use_expert_bias),
            ("rope_type", self.rope_parameters.get("rope_type", "default")
             != "default")) if v}
        if other:
            raise ValueError(f"this lfm2_moe family computes no {other}")

    @property
    def head_dim(self) -> int:
        return self.hidden_size // self.num_attention_heads

    @property
    def rope_theta(self) -> float:
        return float(self.rope_parameters["rope_theta"])

    @property
    def num_expert_layers(self) -> int:
        return max(0, self.num_hidden_layers - self.num_dense_layers)

    def experts_spec(self) -> ExpertsSpec:
        return ExpertsSpec(
            router_width=self.num_experts, top_k=self.num_experts_per_tok,
            width=self.moe_intermediate_size, held=(0, self.num_experts),
            shared_width=0, scaling=self.routed_scaling_factor,
            normalise=self.norm_topk_prob,
            init_range=self.initializer_range, norm_eps=TOPK_EPS)

    # -- what the serving engine asks a family (models/family.py) ---------

    def build_model(self, kv_quant: bool = False):
        if kv_quant:
            raise NotImplementedError(
                "an int8 pool keeps one scale a query head; this row is "
                "laid out by key-value heads (ROADMAP.md Reach)")
        return Lfm2MoeLMHeadModel(self)

    def cache_row(self) -> CacheRow:
        """Every key-value head's ``K_h | V_h`` a token and attention
        layer, each read by ``heads / kv heads`` query heads."""
        return CacheRow.kv(self.num_attention_heads, self.head_dim,
                           self.num_key_value_heads)

    def layer_states(self) -> Tuple[Optional[Tuple[int, int]], ...]:
        """A conv layer keeps ``conv_L_cache - 1`` rows of ``u`` of
        ``hidden_size`` values; an attention layer keeps tokens."""
        state = (self.conv_L_cache - 1, self.hidden_size)
        return tuple(state if k == "conv" else None
                     for k in self.layer_types)

    def serving_counters(self):
        """Tokens routed to each expert, by expert layer."""
        if not self.num_expert_layers:
            return {}
        return {"routed": (self.num_expert_layers, self.num_experts)}


def _init(cfg):
    return nn.initializers.normal(cfg.initializer_range)


def causal_short_conv(bcx, taps):
    """The gated short convolution over whole rows from position 0:
    ``bcx`` (B, S, 3W), ``taps`` (K, W) -> ``C * z`` (B, S, W), float32
    inside as ``ops.short_conv`` computes it."""
    w = bcx.shape[-1] // 3
    f = bcx.astype(jnp.float32)
    u = f[..., :w] * f[..., 2 * w:]
    keep, s = taps.shape[0] - 1, bcx.shape[1]
    ext = jnp.pad(u, ((0, 0), (keep, 0), (0, 0)))
    t = taps.astype(jnp.float32)
    z = t[0] * ext[:, :s]
    for i in range(1, keep + 1):
        z = z + t[i] * ext[:, i:i + s]
    return (f[..., w:2 * w] * z).astype(bcx.dtype)


class Lfm2ShortConv(nn.Module):
    cfg: Lfm2MoeConfig

    @nn.compact
    def __call__(self, x, cache_view=None, layer: int = 0):
        """``x`` (B, S, hidden) after its norm.  Returns ``(out,
        view)``: the view after this layer's state was read and
        written, None without one."""
        cfg = self.cfg
        h = cfg.hidden_size
        init = _init(cfg)
        w_in = self.param("in_proj", init, (h, 3 * h))
        taps = self.param("conv", init, (cfg.conv_L_cache, h))
        w_out = self.param("out_proj", init, (h, h))
        bcx = x @ w_in
        if cache_view is not None:
            y, cache_view = cache_view.convolve(layer, bcx, taps)
        else:
            y = causal_short_conv(bcx, taps)
        return y @ w_out, cache_view


class Lfm2Attention(nn.Module):
    cfg: Lfm2MoeConfig

    @nn.compact
    def __call__(self, x, positions, cache_view=None, layer: int = 0):
        """``x`` (B, S, hidden) after its norm, ``positions`` (B, S).
        Returns ``(out, view)``: the view after this layer's write of
        the new keys and values, None without one."""
        cfg = self.cfg
        h, nh, nkv, d = cfg.hidden_size, cfg.num_attention_heads, \
            cfg.num_key_value_heads, cfg.head_dim
        init = _init(cfg)
        wq = self.param("q_proj", init, (h, nh, d))
        wk = self.param("k_proj", init, (h, nkv, d))
        wv = self.param("v_proj", init, (h, nkv, d))
        wo = self.param("out_proj", init, (nh, d, h))

        q = RMSNorm(cfg.norm_eps, name="q_layernorm")(
            jnp.einsum("bsh,hnd->bsnd", x, wq))
        k = RMSNorm(cfg.norm_eps, name="k_layernorm")(
            jnp.einsum("bsh,hnd->bsnd", x, wk))
        v = jnp.einsum("bsh,hnd->bsnd", x, wv)
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
            seen = jnp.arange(t)[:, None] >= jnp.arange(t)[None, :]
            p = jax.nn.softmax(jnp.where(seen, s, NEG_INF), axis=-1)
            o = jnp.einsum("bgpqk,bkgd->bqgpd", p.astype(x.dtype),
                           v).reshape(b, t, nh, d)
        return jnp.einsum("bsnd,ndh->bsh", o, wo), cache_view


class Lfm2MoeBlock(nn.Module):
    """Pre-norm: ``x + Mix(RMS(x))``, ``Mix`` the short convolution or
    attention; ``x + FF(RMS(x))``, ``FF`` the dense gated feed-forward in
    the leading layers and the expert layer after them."""

    cfg: Lfm2MoeConfig
    layer: int

    @nn.compact
    def __call__(self, x, positions, cache_view=None):
        cfg = self.cfg
        live = cache_view.live if cache_view is not None else None
        h = RMSNorm(cfg.norm_eps, name="operator_norm")(x)
        if cfg.layer_types[self.layer] == "conv":
            with device_scope("short_conv"):
                a, kept = Lfm2ShortConv(cfg, name="conv")(h, cache_view,
                                                          self.layer)
                x = x + a
        else:
            with device_scope("attention"):
                a, kept = Lfm2Attention(cfg, name="self_attn")(
                    h, positions, cache_view, self.layer)
                x = x + a
        h = RMSNorm(cfg.norm_eps, name="ffn_norm")(x)
        if self.layer < cfg.num_dense_layers:
            h = GatedMLP(cfg.intermediate_size, cfg.initializer_range,
                         name="feed_forward")(h)
            with device_scope("mlp"):
                return x + h, kept
        y, sizes = RoutedExperts(cfg.experts_spec(), name="moe")(h, live)
        if cache_view is not None and "routed" in kept.cache:
            kept = kept.count("routed", self.layer - cfg.num_dense_layers,
                              sizes)
        with device_scope("moe_experts"):
            return x + y, kept


class Lfm2MoeLMHeadModel(nn.Module):
    """Token embedding -> blocks -> final RMSNorm -> the head tied to
    the embedding.  Returns (B, S, V) float32 logits.  The serving hooks
    are ``models.gpt.GPTLMHeadModel``'s (``positions``, ``cache_views``,
    ``return_kv``); without a view the call is the plain causal forward
    over whole rows of tokens."""

    cfg: Lfm2MoeConfig

    @nn.compact
    def __call__(self, input_ids, deterministic: bool = True,
                 positions=None, cache_views=None,
                 return_kv: bool = False):
        del deterministic                    # no dropout in this family
        cfg = self.cfg
        embed = self.param("embed_tokens", _init(cfg),
                           (cfg.vocab_size, cfg.hidden_size))
        with device_scope("embed"):
            x = jnp.take(embed, input_ids, axis=0)
            if positions is None:
                positions = jnp.broadcast_to(
                    jnp.arange(input_ids.shape[1], dtype=jnp.int32)[None],
                    input_ids.shape)
        view = cache_views
        for i in range(cfg.num_hidden_layers):
            # ``layer_<i>``: a weight maker that stacks the leaves of
            # ``block_<i>`` alike (the benchmark's) would draw 8 layers of
            # 64 experts in one array, 6 GiB of random bits at once
            x, view = Lfm2MoeBlock(cfg, i, name=f"layer_{i}")(
                x, positions, view)
        x = RMSNorm(cfg.norm_eps, block="head", name="embedding_norm")(x)
        with device_scope("head"):
            logits = jnp.einsum("bsh,vh->bsv", x, embed,
                                preferred_element_type=jnp.float32)
        if return_kv:
            return logits, view
        return logits
