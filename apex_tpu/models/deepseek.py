"""Decoder with latent attention and routed experts (the published
``deepseek_v3`` family: DeepSeek-V3 and the models that reuse its
modelling code under other names).

What differs from ``models.gpt``, block by block:

- **RMSNorm** in place of LayerNorm, float32 inside.
- **Rotary positions on a part of each head.**  A query head is
  ``q_nope`` (``qk_nope_head_dim``) beside ``q_pe``
  (``qk_rope_head_dim``); only ``q_pe`` is rotated, and the key's
  rotated part ``k_pe`` is ONE row shared by all heads.  With
  ``rope_interleave`` the pairs ``(2i, 2i + 1)`` of the projection's
  output are the rotated pairs; they are brought to the half-split
  order (evens, then odds) and stay there, as the published modelling
  code does (``apply_rotary_pos_emb_interleave``).
- **Latent attention.**  Keys and values of all heads are expanded
  from one compressed row ``c = RMS(h W_kva[:rank])`` of
  ``kv_lora_rank`` values by ``W_kvb``.  The cache keeps ``c | k_pe``
  a token and layer (``cache_row()``), not the heads.  Without a cache
  view the model computes the expanded form (the full forward pass);
  with one it hands the view its *absorbed* queries
  ``q_lat = q_nope W_kvb[k]^T`` and the new rows, the view attends in
  the latent space (scores ``(q_lat . c + q_pe . k_pe) / sqrt(192)``,
  context ``softmax . c``) and the model expands the context by
  ``W_kvb[v]``.  The two agree to rounding.
- **Gated feed-forward** ``W_down(silu(W_gate h) * (W_up h))`` in the
  leading ``first_k_dense_replace`` layers.
- **Expert layers** after them: sigmoid scores in float32, the
  ``num_experts_per_tok`` largest of ``score + e_score_correction_bias``
  chosen (the bias selects and does not weigh), their scores
  normalised and scaled by ``routed_scaling_factor``, the chosen
  experts' gated feed-forwards summed with those weights beside the
  shared experts (one gated feed-forward of ``n_shared_experts`` times
  the width).  No capacity and no dropped token: the (token, expert)
  pairs are sorted by expert and go through
  ``ops.grouped_matmul`` three times
  (``models.routed_experts.RoutedExperts``, which the ``exaone_moe``
  family shares).  The layer is told which experts
  it holds (``experts_held``, default all): it routes over all
  ``n_routed_experts`` and computes its own experts' part, which is
  what expert parallelism asks of it; the exchange is not here.
  Rows that are no tokens (idle slots, padding: ``CacheView.live``)
  are routed nowhere.

``models.moe.MoEMlp`` (Switch top-1 with a capacity, for training) is
another layer and stays as it is.  This family is served, not trained
here: the grouped product has no backward pass yet.

One group, no query compression, no rope scaling: ``n_group`` and
``topk_group`` of 1, ``q_lora_rank`` null, ``rope_scaling`` null are
what ``DeepseekV3Config`` can express (a configuration file with
others is refused where it is read).
"""

from __future__ import annotations

import dataclasses
from typing import Optional, Tuple

import flax.linen as nn
import jax
import jax.numpy as jnp
from jax import lax

from apex_tpu.models.family import CacheRow
# what this family shares with ``models.exaone_moe``; the names stay
# importable from here
from apex_tpu.models.routed_experts import (  # noqa: F401  (re-export)
    ExpertsSpec,
    GatedMLP,
    RMSNorm,
    RoutedExperts,
    apply_rotary,
    check_held,
    rotary_angles,
    route,
)
from apex_tpu.observability.scopes import device_scope

NEG_INF = -1e9


@dataclasses.dataclass(frozen=True)
class DeepseekV3Config:
    """The published keys under their published names."""

    vocab_size: int = 128256
    hidden_size: int = 2048
    num_hidden_layers: int = 48
    num_attention_heads: int = 32
    intermediate_size: int = 6144
    moe_intermediate_size: int = 768
    n_routed_experts: int = 128
    n_shared_experts: int = 2
    num_experts_per_tok: int = 6
    first_k_dense_replace: int = 1
    kv_lora_rank: int = 512
    qk_nope_head_dim: int = 128
    qk_rope_head_dim: int = 64
    v_head_dim: int = 128
    rope_theta: float = 1e6
    rope_interleave: bool = True
    rms_norm_eps: float = 1e-6
    routed_scaling_factor: float = 2.448
    norm_topk_prob: bool = True
    max_position_embeddings: int = 32768
    initializer_range: float = 0.02
    # (first, count) of the routed experts whose weights this layer
    # holds; None = all of them.  The router keeps its published width.
    experts_held: Optional[Tuple[int, int]] = None

    def __post_init__(self):
        check_held(self.held, self.n_routed_experts)

    @property
    def held(self) -> Tuple[int, int]:
        return self.experts_held or (0, self.n_routed_experts)

    @property
    def num_expert_layers(self) -> int:
        return max(0, self.num_hidden_layers - self.first_k_dense_replace)

    def experts_spec(self) -> ExpertsSpec:
        return ExpertsSpec(
            router_width=self.n_routed_experts,
            top_k=self.num_experts_per_tok,
            width=self.moe_intermediate_size, held=self.held,
            shared_width=self.n_shared_experts * self.moe_intermediate_size,
            scaling=self.routed_scaling_factor,
            normalise=self.norm_topk_prob,
            init_range=self.initializer_range)

    # -- what the serving engine asks a family (models/family.py) ---------

    def build_model(self, kv_quant: bool = False):
        if kv_quant:
            raise NotImplementedError(
                "an int8 pool keeps one scale a head for a K|V pair; a "
                "latent row has no heads to scale by (ROADMAP.md Reach)")
        return DeepseekV3LMHeadModel(self)

    def cache_row(self) -> CacheRow:
        """``c | k_pe`` a token and layer, shared by all heads."""
        return CacheRow.latent(self.kv_lora_rank, self.qk_rope_head_dim,
                               self.num_attention_heads)

    def serving_counters(self):
        """Tokens routed to each expert held, by expert layer."""
        if not self.num_expert_layers:
            return {}
        return {"routed": (self.num_expert_layers, self.held[1])}


def _init(cfg):
    return nn.initializers.normal(cfg.initializer_range)


class DeepseekV3Attention(nn.Module):
    cfg: DeepseekV3Config

    @nn.compact
    def __call__(self, x, positions, cache_view=None, layer: int = 0):
        """``x`` (B, S, hidden) after its norm, ``positions`` (B, S).
        Returns ``(out, view)``: the view after this layer's write of
        the new rows ``c | k_pe`` (B, S, rank + rope), None without
        one."""
        cfg = self.cfg
        h, nh, rank = cfg.hidden_size, cfg.num_attention_heads, \
            cfg.kv_lora_rank
        dn, dr, dv = cfg.qk_nope_head_dim, cfg.qk_rope_head_dim, \
            cfg.v_head_dim
        init = _init(cfg)
        wq = self.param("q_proj", init, (h, nh, dn + dr))
        wkva = self.param("kv_a_proj_with_mqa", init, (h, rank + dr))
        wkvb = self.param("kv_b_proj", init, (rank, nh, dn + dv))
        wo = self.param("o_proj", init, (nh, dv, h))

        q = jnp.einsum("bsh,hnd->bsnd", x, wq)
        q_nope, q_pe = q[..., :dn], q[..., dn:]
        kva = jnp.einsum("bsh,hr->bsr", x, wkva)
        c = RMSNorm(cfg.rms_norm_eps, name="kv_a_layernorm")(
            kva[..., :rank])
        cos, sin = rotary_angles(positions, dr, cfg.rope_theta)
        q_pe = apply_rotary(q_pe, cos[:, :, None], sin[:, :, None],
                            cfg.rope_interleave)
        k_pe = apply_rotary(kva[..., rank:], cos, sin, cfg.rope_interleave)
        scale = float(dn + dr) ** -0.5

        if cache_view is not None:
            # absorbed: attend in the latent space, expand the context
            q_lat = jnp.einsum("bsnd,rnd->bsnr", q_nope, wkvb[..., :dn])
            ctx, cache_view = cache_view.attend(
                layer, jnp.concatenate([q_lat, q_pe], -1),
                jnp.concatenate([c, k_pe], -1),  # (B, S, rank + dr)
                scale=scale)
            o = jnp.einsum("bsnr,rnd->bsnd", ctx, wkvb[..., dn:])
        else:
            # expanded: the published form, for the full forward pass
            kv = jnp.einsum("bsr,rnd->bsnd", c, wkvb)
            s = (jnp.einsum("bqnd,bknd->bnqk", q_nope, kv[..., :dn])
                 + jnp.einsum("bqnd,bkd->bnqk", q_pe, k_pe)
                 ).astype(jnp.float32) * scale
            t = x.shape[1]
            causal = jnp.arange(t)[:, None] >= jnp.arange(t)[None, :]
            s = jnp.where(causal[None, None], s, NEG_INF)
            p = jax.nn.softmax(s, axis=-1)
            o = jnp.einsum("bnqk,bknd->bqnd", p.astype(x.dtype),
                           kv[..., dn:])
        return jnp.einsum("bsnd,ndh->bsh", o, wo), cache_view


def DeepseekV3MoE(cfg: DeepseekV3Config, name=None):
    """The family's expert layer: the shared
    ``models.routed_experts.RoutedExperts`` at this configuration's
    sizes."""
    return RoutedExperts(cfg.experts_spec(), name=name)


class DeepseekV3Block(nn.Module):
    """Pre-norm: ``x + Attn(RMS(x))``; ``x + FF(RMS(x))``, ``FF`` the
    dense gated feed-forward in the leading layers and the expert layer
    after them."""

    cfg: DeepseekV3Config
    layer: int

    @nn.compact
    def __call__(self, x, positions, cache_view=None):
        cfg = self.cfg
        live = cache_view.live if cache_view is not None else None
        with device_scope("attention"):
            a, kept = DeepseekV3Attention(cfg, name="attention")(
                RMSNorm(cfg.rms_norm_eps, name="input_layernorm")(x),
                positions, cache_view, self.layer)
            x = x + a
        h = RMSNorm(cfg.rms_norm_eps, name="post_attention_layernorm")(x)
        if self.layer < cfg.first_k_dense_replace:
            h = GatedMLP(cfg.intermediate_size, cfg.initializer_range,
                         name="mlp")(h)
            with device_scope("mlp"):
                return x + h, kept
        y, sizes = DeepseekV3MoE(cfg, name="moe")(h, live)
        if cache_view is not None and "routed" in kept.cache:
            kept = kept.count(
                "routed", self.layer - cfg.first_k_dense_replace, sizes)
        with device_scope("moe_experts"):
            return x + y, kept


class DeepseekV3LMHeadModel(nn.Module):
    """Token embedding -> blocks -> final RMSNorm -> untied head.
    Returns (B, S, V) float32 logits.

    The serving hooks are ``models.gpt.GPTLMHeadModel``'s:
    ``positions`` (B, S) explicit positions (default ``arange``);
    ``cache_views`` the launch's ``serving.kv_cache.CacheView``,
    threaded through the blocks and, with ``return_kv=True``, returned
    after the last beside the logits.  Without a view the call is the
    plain causal forward over whole rows of tokens."""

    cfg: DeepseekV3Config

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
            x, view = DeepseekV3Block(cfg, i, name=f"block_{i}")(
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
