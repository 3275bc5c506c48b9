"""Decoder-only causal language model (GPT-style) — the long-context
flagship of the model zoo.

The reference (apex) ships no models; this family exists because the
framework's long-context machinery — causal flash attention
(``ops.flash_attention``, O(S) memory), ring/Ulysses sequence
parallelism (``parallel.sequence``), per-layer remat — needs a model
whose workload is actually causal and long, the way BERT is the
workload for FusedLAMB/FusedLayerNorm (BASELINE config 4). TPU-first
choices:

- pre-LN blocks (``FusedLayerNorm``, Pallas on TPU) — the stable-at-
  depth variant every modern decoder uses;
- attention as batched einsum -> fp32 softmax -> einsum on the default
  path, with the same pluggable ``attention_fn`` seam as
  ``models.bert`` — ``make_flash_attention(causal=True)`` swaps the
  whole stack onto the fused kernel, ``make_ulysses_attention`` /
  ``make_ring_attention`` shard the sequence axis;
- learned positional embeddings (static shapes; no data-dependent
  control flow under jit);
- weight-tied LM head (embedding transpose) — half the embedding HBM
  of an untied head at vocab scale;
- ``remat=True`` rematerializes each block in backward
  (``jax.checkpoint``) for long sequences.

Causality is enforced in-model (the causal mask/bias is built from
static positions), so callers never thread masks for plain LM
training; padding masks compose additively when given.
"""

from __future__ import annotations

import dataclasses
from typing import Callable, Optional

import flax.linen as nn
import jax
import jax.numpy as jnp

from apex_tpu.models.family import CacheRow
from apex_tpu.models.pipelined_common import PipelinedCommon
from apex_tpu.normalization import FusedLayerNorm
from apex_tpu.observability.scopes import device_scope

NEG_INF = -1e9


@dataclasses.dataclass(frozen=True)
class GPTConfig:
    vocab_size: int = 50257
    hidden_size: int = 768
    num_hidden_layers: int = 12
    num_attention_heads: int = 12
    intermediate_size: int = 3072
    max_position_embeddings: int = 1024
    hidden_dropout_prob: float = 0.1
    attention_probs_dropout_prob: float = 0.1
    layer_norm_eps: float = 1e-5
    initializer_range: float = 0.02
    # rematerialize each block in backward: the long-sequence lever
    remat: bool = False

    # -- what the serving engine asks a family (models/family.py) ---------

    def build_model(self, kv_quant: bool = False):
        return GPTLMHeadModel(self, kv_quant=kv_quant)

    def cache_row(self) -> CacheRow:
        """Every head's ``K_h | V_h`` pair a token and layer."""
        return CacheRow.kv(self.num_attention_heads,
                           self.hidden_size // self.num_attention_heads)


def gpt_small() -> "GPTConfig":
    """The 124M 12x768 configuration."""
    return GPTConfig()


def gpt_medium() -> "GPTConfig":
    return GPTConfig(hidden_size=1024, num_hidden_layers=24,
                     num_attention_heads=16, intermediate_size=4096)


def _init(cfg):
    return nn.initializers.normal(cfg.initializer_range)


def _embed_block(cfg, input_ids, deterministic, positions=None):
    """Token + position embeddings + dropout, shared by
    :class:`GPTLMHeadModel` and :class:`GPTEmbed` so the param names
    and math cannot drift (same discipline as ``bert._embed_block``;
    must be called inside an ``@nn.compact`` body).  Returns
    ``(x, wte)`` — the wte module for the tied LM head.

    ``positions``: optional (B, S) explicit position indices — the
    serving decode step feeds a single token per sequence at its OWN
    position (each request sits at a different depth), where the
    default ``arange`` would embed everything at position 0."""
    init = _init(cfg)
    s = input_ids.shape[1]
    wte = nn.Embed(cfg.vocab_size, cfg.hidden_size,
                   embedding_init=init, name="wte")
    x = wte(input_ids)
    if positions is None:
        positions = jnp.arange(s)[None, :]
    x = x + nn.Embed(cfg.max_position_embeddings, cfg.hidden_size,
                     embedding_init=init, name="wpe")(positions)
    x = nn.Dropout(cfg.hidden_dropout_prob,
                   deterministic=deterministic)(x)
    return x, wte


def causal_dot_product_attention(q, k, v, bias=None, dropout_fn=None):
    """Default path: (B, S, H, D) -> (B, S, H, D). The causal mask is
    built from static positions and folded into the additive bias;
    everything else (scaling, fp32 softmax, dropout hook) DELEGATES to
    ``models.bert.dot_product_attention`` so the numeric policy cannot
    drift between the encoder and decoder families."""
    from apex_tpu.models.bert import dot_product_attention

    sq, sk = q.shape[1], k.shape[1]
    cmask = jnp.where(jnp.arange(sq)[:, None] >= jnp.arange(sk)[None, :],
                      0.0, NEG_INF)
    bias = (cmask[None, None] if bias is None
            else bias + cmask[None, None])
    return dot_product_attention(q, k, v, bias=bias,
                                 dropout_fn=dropout_fn)


class GPTSelfAttention(nn.Module):
    cfg: GPTConfig
    attention_fn: Optional[Callable] = None
    kv_quant: bool = False

    @nn.compact
    def __call__(self, x, attn_bias, deterministic: bool = True,
                 cache_view=None, layer: int = 0):
        """``cache_view``: serving mode — the launch's view of the KV
        pool (``serving.kv_cache.CacheView``), ``layer`` this block's
        index in it.  The view's ``attend`` takes the queries and the
        freshly projected K/V, returns the context the fed rows attend
        (their cached past through the block table plus themselves,
        causally) and the view with this layer's rows written; how the
        pool is laid out and whether it is read in place or gathered is
        the view's business.  ``attention_fn`` (a causal full-sequence
        kernel) is deliberately bypassed there.  With a view the call
        returns ``(out, view after the write)``; without one, ``out``
        — the training path is byte-identical to before.

        ``kv_quant`` (a field, set where the model is built:
        ``GPTConfig.build_model``): int8-quantized-pool serving (``docs/serving.md``,
        "Quantized KV cache").  The freshly projected K/V quantize AT
        THE SOURCE (:func:`ops.kv_quant.quantize_kv`, per token per
        head) and attention operates on the QUANTIZED grid — the view
        hands the int8 context with its scale sidecar to ops that
        widen at read, and the fed rows' own K/V join it as int8 with
        their fresh scales.  That uniformity is the bit-stability
        argument: a (query, key) pair's score is identical whether the
        key is fresh this call, fresh earlier in the same chunk, or
        read back from the pool — so chunking boundaries, preemption
        re-prefill, COW, and speculation cannot move a logit.  The
        fresh K/V are then ``((k_q, k_scale), (v_q, v_scale))`` —
        byte-for-byte what attention uses."""
        cfg = self.cfg
        h, nh = cfg.hidden_size, cfg.num_attention_heads
        init = _init(cfg)

        def proj(name):
            return nn.DenseGeneral((nh, h // nh), kernel_init=init,
                                   name=name)(x)

        q, k, v = proj("query"), proj("key"), proj("value")
        if cache_view is not None:
            kv = (k, v)
            if self.kv_quant:
                from apex_tpu.ops.kv_quant import quantize_kv

                kv = (quantize_kv(k), quantize_kv(v))
            ctx, cache_view = cache_view.attend(layer, q, kv)
        else:
            dropout_fn = None
            if cfg.attention_probs_dropout_prob > 0 and not deterministic:
                drop = nn.Dropout(cfg.attention_probs_dropout_prob,
                                  deterministic=False)
                dropout_fn = lambda p: drop(p)
                if self.attention_fn is not None:
                    # same (rate, seed) annotation contract as BERT so
                    # the fused kernels run dropout in-kernel
                    # (ops.flash_attention.dropout_params)
                    dropout_fn.rate = cfg.attention_probs_dropout_prob
                    dropout_fn.seed = jax.random.randint(
                        self.make_rng("dropout"), (), 0,
                        jnp.iinfo(jnp.int32).max, dtype=jnp.int32)
            attn = self.attention_fn or causal_dot_product_attention
            ctx = attn(q, k, v, bias=attn_bias, dropout_fn=dropout_fn)
        out = nn.DenseGeneral(h, axis=(-2, -1), kernel_init=init,
                              name="output")(ctx)
        if cache_view is not None:
            return out, cache_view
        return out


class GPTBlock(nn.Module):
    """Pre-LN: x + Attn(LN(x)); x + MLP(LN(x)).

    ``cache_view``/``layer`` thread straight through to
    :class:`GPTSelfAttention` (serving: the call then returns
    ``(x, view)``); the training call sites never pass them."""

    cfg: GPTConfig
    attention_fn: Optional[Callable] = None
    kv_quant: bool = False

    @nn.compact
    def __call__(self, x, attn_bias, deterministic: bool = True,
                 cache_view=None, layer: int = 0):
        cfg = self.cfg
        init = _init(cfg)
        drop = nn.Dropout(cfg.hidden_dropout_prob,
                          deterministic=deterministic)
        with device_scope("norm"):
            h = FusedLayerNorm(cfg.hidden_size, eps=cfg.layer_norm_eps,
                               name="attn_ln")(x)
        with device_scope("attention"):
            h = GPTSelfAttention(cfg, self.attention_fn, self.kv_quant,
                                 name="attention")(h, attn_bias,
                                                   deterministic,
                                                   cache_view=cache_view,
                                                   layer=layer)
            if cache_view is not None:
                h, cache_view = h
            x = x + drop(h)
        with device_scope("norm"):
            h = FusedLayerNorm(cfg.hidden_size, eps=cfg.layer_norm_eps,
                               name="mlp_ln")(x)
        with device_scope("mlp"):
            h = nn.Dense(cfg.intermediate_size, kernel_init=init,
                         name="mlp_in")(h)
            h = nn.gelu(h, approximate=True)
            h = nn.Dense(cfg.hidden_size, kernel_init=init,
                         name="mlp_out")(h)
            x = x + drop(h)
        if cache_view is not None:
            return x, cache_view
        return x


class GPTLMHeadModel(nn.Module):
    """Token + position embeddings -> pre-LN blocks -> final LN ->
    weight-tied LM head. Returns (B, S, V) fp32 logits.

    ``attention_fn``: optional fused/sequence-parallel attention with
    the ``models.bert`` adapter signature. The DEFAULT path and the
    flash path are both causal; adapters must be built causal
    (``make_flash_attention(causal=True)``,
    ``make_ring_attention("sp", causal=True)``) — there is no way to
    express a non-causal LM here.
    ``attention_mask``: optional (B, S) 1/0 padding mask, additive on
    key positions on top of causality.

    Serving hooks (``apex_tpu.serving.engine`` is the caller; training
    code never passes them):

    - ``positions``: explicit (B, S) position-embedding indices
      (decode feeds one token per sequence at its own depth);
    - ``cache_views``: serving mode — the launch's
      ``serving.kv_cache.CacheView`` of the KV pool, threaded through
      the blocks: each attends its cached context plus the fed rows
      through it (decode, S == 1; verify and chunked prefill, S > 1,
      causal among the rows) and hands on the view with its rows
      written;
    - ``return_kv``: passed with ``cache_views``; the call then
      returns ``(logits, view after the last block)``;
    - ``kv_quant`` (a field): int8-quantized-pool serving — under a
      view fresh K/V quantize at projection and attention runs on the
      quantized grid (``docs/serving.md``, "Quantized KV cache").
    """

    cfg: GPTConfig
    attention_fn: Optional[Callable] = None
    kv_quant: bool = False

    @nn.compact
    def __call__(self, input_ids, attention_mask=None,
                 deterministic: bool = True,
                 return_hidden: bool = False,
                 positions=None, cache_views=None,
                 return_kv: bool = False):
        cfg = self.cfg
        with device_scope("embed"):
            x, wte = _embed_block(cfg, input_ids, deterministic, positions)
        bias = None
        if attention_mask is not None:
            with device_scope("attention"):
                bias = jnp.where(attention_mask[:, None, None, :] > 0,
                                 0.0, NEG_INF).astype(jnp.float32)
        block = GPTBlock
        view = cache_views
        if cfg.remat and view is None:
            # deterministic (argnum 3; self=0) is the static arg — the
            # bias is a traced array (same as models.bert). Serving
            # (a view) never remats: there is no backward to save
            # memory for, and the view's pytree confuses the policy.
            block = nn.remat(GPTBlock, static_argnums=(3,))
        for i in range(cfg.num_hidden_layers):
            if view is not None:
                x, view = block(cfg, self.attention_fn, self.kv_quant,
                                name=f"block_{i}")(
                    x, bias, deterministic, cache_view=view, layer=i)
            else:
                x = block(cfg, self.attention_fn, name=f"block_{i}")(
                    x, bias, deterministic)
        with device_scope("head"):
            x = FusedLayerNorm(cfg.hidden_size, eps=cfg.layer_norm_eps,
                               name="final_ln")(x)
            if return_hidden:
                # for ops.vocab_parallel_lm_loss: under TP the (B, S, V)
                # logits should never be materialized — hand back the
                # pre-head hidden instead and let the vocab-parallel
                # loss consume it with the sharded wte
                return x
            # weight-tied head: logits = x @ wte^T
            logits = wte.attend(x).astype(jnp.float32)
        if return_kv:
            return logits, view
        return logits


def _lm_masked_sum(logits, input_ids, attention_mask):
    """Masked SUM of next-token cross entropy (no normalization) — the
    microbatch-side half of the exact masked mean: each 1F1B microbatch
    contributes its sum and the precomputed global denominator turns
    the schedule's mean-over-microbatches into the exact global masked
    mean, independent of padding skew (see PipelinedGPT)."""
    import optax

    with device_scope("head"):
        per_tok = optax.softmax_cross_entropy_with_integer_labels(
            logits[:, :-1], input_ids[:, 1:])
        return (per_tok * attention_mask[:, 1:].astype(
            per_tok.dtype)).sum()


def lm_loss(logits, input_ids, attention_mask=None):
    """Next-token cross entropy: predict token t+1 from prefix <= t.
    Position S-1 has no target and is dropped; with a padding mask,
    positions whose TARGET is padding are dropped too. Mean over kept
    positions."""
    import optax

    with device_scope("head"):
        if attention_mask is None:
            return optax.softmax_cross_entropy_with_integer_labels(
                logits[:, :-1], input_ids[:, 1:]).mean()
        # one definition of the shift-and-mask numerator (shared with
        # the 1F1B per-microbatch contribution) so the conventions
        # cannot drift
        keep = attention_mask[:, 1:].sum().astype(logits.dtype)
        return (_lm_masked_sum(logits, input_ids, attention_mask)
                / jnp.maximum(keep, 1.0))


class GPTStage(nn.Module):
    """``n_layers`` consecutive pre-LN blocks — one pipeline stage."""

    cfg: GPTConfig
    n_layers: int
    attention_fn: Optional[Callable] = None

    @nn.compact
    def __call__(self, x, attn_bias, deterministic: bool = True):
        block = GPTBlock
        if self.cfg.remat:
            block = nn.remat(GPTBlock, static_argnums=(3,))
        for i in range(self.n_layers):
            x = block(self.cfg, self.attention_fn, name=f"block_{i}")(
                x, attn_bias, deterministic)
        return x


class GPTEmbed(nn.Module):
    cfg: GPTConfig

    @nn.compact
    def __call__(self, input_ids, deterministic: bool = True):
        with device_scope("embed"):
            x, _ = _embed_block(self.cfg, input_ids, deterministic)
        return x


class PipelinedGPT(PipelinedCommon):
    """GPT over a ``pipe`` mesh axis — the decoder counterpart of
    :class:`models.PipelinedBert` (same schedules,
    ``parallel.pipeline``; same variables convention so
    ``amp.initialize`` wraps it).

    Param groups: ``embed`` (wte/wpe, replicated), ``stages`` (blocks
    stacked ``(pp, ...)`` and pipe-sharded), ``head`` (the final LN;
    the LM projection is TIED to ``embed/wte``). The tied head makes
    the 1F1B grad flow the interesting part: ``wte``'s gradient has an
    input-side contribution (token lookup, via the pipeline's input
    cotangent) and a head-side contribution (the logits projection,
    via the schedule's differentiated ``loss_params``) — they come
    back on separate paths and are SUMMED, which is exactly the tied
    parameter's chain rule.

    ``batch_axis`` composes (DDP mean semantics), and ``seq_axis``
    shards the sequence inside the pipeline (dp x sp x pp) when paired
    with a sequence-parallel ``attention_fn`` for the same axis —
    under 1F1B the attention must be scan-free
    (``make_ulysses_attention``; the ring is fenced, see
    tools/repro_ring_1f1b.py).

    ``tp_axis`` layers Megatron tensor parallelism on top
    (``parallel.gpt_tp_rules``): stage weights take
    ``P(pipe, ...model...)`` placement and the TP axis stays
    GSPMD-automatic inside the pipeline's ``shard_map``
    (partial-manual mode) — same machinery as ``PipelinedBert``.  The
    TIED ``wte`` shards its vocab dim, so the LM-head einsum runs
    column-parallel (each device computes its vocab slice of the
    logits) instead of replicating the whole-vocab matmul.  Same KNOWN
    LIMITATION as PipelinedBert: amp O2/O3 compute inside the
    partial-manual region trips this jax build's XLA CPU backend;
    ``tp_axis`` is tested fp32 (bf16 on the TPU backend is open:
    ``CHANGES.md`` PR 21, ``ROADMAP.md`` Speed item 10).

    Dropout composes like PipelinedBert: ``deterministic=False`` +
    ``rngs={"dropout": key}``; each (microbatch, stage[, shard]) folds
    its coordinates into the key inside the pipeline body.
    """

    def __init__(self, cfg: GPTConfig, mesh, pp: int,
                 num_microbatches: int, pipe_axis: str = "pipe",
                 batch_axis: Optional[str] = None,
                 seq_axis: Optional[str] = None,
                 tp_axis: Optional[str] = None,
                 attention_fn: Optional[Callable] = None):
        if cfg.num_hidden_layers % pp:
            raise ValueError(
                f"num_hidden_layers={cfg.num_hidden_layers} must divide "
                f"into pp={pp} equal stages")
        if seq_axis is not None and attention_fn is None:
            raise ValueError(
                "seq_axis requires a sequence-parallel attention_fn for "
                "the same axis (parallel.make_ulysses_attention(seq_axis, "
                "causal=True)) — plain attention would silently attend "
                "only within each sequence shard")
        self.cfg = cfg
        self.mesh = mesh
        self.pp = pp
        self.num_microbatches = num_microbatches
        self.pipe_axis = pipe_axis
        self.batch_axis = batch_axis
        self.seq_axis = seq_axis
        self.tp_axis = tp_axis
        self.attention_fn = attention_fn
        self.embed = GPTEmbed(cfg)
        self.stage = GPTStage(cfg, cfg.num_hidden_layers // pp,
                              attention_fn)
        self._stage_init = GPTStage(cfg, cfg.num_hidden_layers // pp,
                                    None)
        self.final_ln = FusedLayerNorm(cfg.hidden_size,
                                       eps=cfg.layer_norm_eps)

    def init(self, rng, input_ids):
        r_embed, r_stage, r_head = jax.random.split(rng, 3)
        embed_p = self.embed.init(r_embed, input_ids, True)["params"]
        x0 = self.embed.apply({"params": embed_p}, input_ids, True)
        bias0 = self._bias(input_ids, None)
        stage_p = jax.vmap(
            lambda r: self._stage_init.init(r, x0, bias0, True)["params"])(
            jax.random.split(r_stage, self.pp))
        head_p = self.final_ln.init(r_head, x0)["params"]
        return {"params": {"embed": embed_p, "stages": stage_p,
                           "head": head_p}}

    # param_spec_tree / shard_variables / constrain_grads /
    # _partial_manual_kwargs / _dropout_setup come from PipelinedCommon
    tp_rules_name = "gpt_tp_rules"

    def _schedule_input(self, h, b, needs_rng):
        """Activation tuple both schedules feed their stage_fn:
        ``(hidden, bias[, mb_ids])`` — mb ids carry one microbatch id
        per row (contiguous groups, matching how the schedules split
        the local batch) for per-(microbatch, stage) dropout keys.
        No MoE aux leaf here: GPTConfig has no expert knobs."""
        if needs_rng:
            return (h, b, self._microbatch_ids(h))
        return (h, b)

    def _build_stage_fn(self, needs_rng, base_key, deterministic):
        """The per-stage body both schedules share — the decoder port
        of ``PipelinedBert._build_stage_fn`` (per-(microbatch, stage
        [, shard]) dropout keys derived inside the pipeline body so
        1F1B's rematerialized backward draws the same masks as the
        GPipe forward)."""

        def stage_fn(sp, xb):
            h, b, mb = xb if needs_rng else (xb[0], xb[1], None)
            stage_rngs = None
            if needs_rng:
                stage_rngs = {
                    "dropout": self._stage_dropout_key(base_key, mb)}
            out = self.stage.apply(
                {"params": sp}, h, b,
                deterministic if stage_rngs is None else False,
                rngs=stage_rngs)
            if needs_rng:
                return (out, b, mb)
            return (out, b)

        return stage_fn

    def _bias(self, input_ids, attention_mask):
        b, s = input_ids.shape
        if attention_mask is None:
            return jnp.zeros((b, 1, 1, s), jnp.float32)
        return jnp.where(attention_mask[:, None, None, :] > 0,
                         0.0, NEG_INF).astype(jnp.float32)

    def _head(self, h, head_p, wte):
        x = self.final_ln.apply({"params": head_p}, h)
        return jnp.einsum("bsh,vh->bsv", x, wte).astype(jnp.float32)

    def apply(self, variables, input_ids, attention_mask=None,
              deterministic: bool = True, rngs=None):
        from jax.sharding import PartitionSpec as P

        from apex_tpu.parallel.pipeline import gpipe_spmd

        needs_rng, base_key, embed_rngs = self._dropout_setup(
            deterministic, rngs, "PipelinedGPT.apply")

        p = variables["params"]
        x = self.embed.apply({"params": p["embed"]}, input_ids,
                             deterministic, rngs=embed_rngs)
        bias = self._bias(input_ids, attention_mask)

        stage_fn = self._build_stage_fn(needs_rng, base_key,
                                        deterministic)
        run = gpipe_spmd(stage_fn, self.pipe_axis, self.num_microbatches)

        def run_wrapped(sp, xb):
            return run(sp, self._schedule_input(*xb, needs_rng))[0]

        hspec = P(self.batch_axis, self.seq_axis)
        bspec = P(self.batch_axis, None, None, self.seq_axis)
        f = jax.shard_map(
            run_wrapped, mesh=self.mesh,
            in_specs=(jax.tree_util.tree_map(lambda _: P(self.pipe_axis),
                                             p["stages"]),
                      (hspec, bspec)),
            out_specs=hspec, **self._partial_manual_kwargs())
        h = f(p["stages"], (x, bias))
        return self._head(h, p["head"],
                          p["embed"]["wte"]["embedding"])

    def loss_and_grad_1f1b(self, variables, input_ids, targets,
                           attention_mask=None,
                           deterministic: bool = True, rngs=None):
        """1F1B training step: ``targets`` are the (B, S) token ids the
        loss shifts against (usually ``input_ids`` itself).  Returns
        ``(loss, grads)`` with grads matching ``variables["params"]``;
        the tied ``wte`` grad sums its embedding-lookup and LM-head
        contributions.

        ``attention_mask`` reaches both the attention bias and the
        loss (pad targets dropped).  The masked loss is EXACT under
        arbitrary padding skew: each microbatch contributes its masked
        SUM over a precomputed global denominator (total valid targets
        / microbatch-shard units), so the schedule's mean over
        microbatches — and the dp pmean — reconstruct the monolithic
        global masked mean regardless of how valid counts distribute
        across microbatches or data shards (the naive mean of
        per-microbatch masked means silently drifts; pinned by
        ``test_pipelined_gpt_1f1b_mask_skewed_padding_exact``).
        """
        from jax import lax
        from jax.sharding import PartitionSpec as P

        from apex_tpu.parallel.pipeline import onef1b_spmd

        if self.seq_axis is not None and not getattr(
                self.attention_fn, "onef1b_compatible", False):
            # same fail-closed rule as PipelinedBert: only scan-free
            # attention may run inside the schedule's cond branches
            # (the ring's scan-carried collective miscompiles there —
            # tools/repro_ring_1f1b.py)
            raise NotImplementedError(
                "seq_axis under 1F1B needs an attention_fn marked "
                "onef1b_compatible=True (make_ulysses_attention is; "
                "ring attention is NOT). Use the GPipe apply() path "
                "for ring-SP")

        needs_rng, base_key, embed_rngs = self._dropout_setup(
            deterministic, rngs, "loss_and_grad_1f1b")

        p = variables["params"]

        def embed_f(ep):
            return self.embed.apply({"params": ep}, input_ids,
                                    deterministic, rngs=embed_rngs)

        x, embed_vjp = jax.vjp(embed_f, p["embed"])
        bias = self._bias(input_ids, attention_mask)

        stage_fn = self._build_stage_fn(needs_rng, base_key,
                                        deterministic)

        def pl_loss(y, tgt_mb, lp):
            h = y[0]
            if self.seq_axis is not None:
                # gather the microbatch's sequence shards so the loss
                # shift sees the full sequence (runs on every sp shard
                # of the last stage — uniform branch, mb-sized)
                h = lax.all_gather(h, self.seq_axis, axis=1, tiled=True)
            logits = self._head(h, lp["head"], lp["wte"])
            mask = tgt_mb.get("mask")
            if mask is not None:
                # EXACT masked mean under arbitrary padding skew: the
                # microbatch contributes its masked SUM over the global
                # denominator (rides tgt as a per-row constant); the
                # schedule's mean over microbatches and run_wrapped's
                # dp pmean then reconstruct sum(all)/keep(all) exactly
                # — a per-microbatch masked MEAN would silently drift
                # whenever microbatches carry unequal valid counts
                return (_lm_masked_sum(logits, tgt_mb["ids"], mask)
                        / tgt_mb["denom"][0])
            return lm_loss(logits, tgt_mb["ids"])

        run = onef1b_spmd(stage_fn, pl_loss, self.pipe_axis,
                          self.num_microbatches)
        loss_params = {"head": p["head"],
                       "wte": p["embed"]["wte"]["embedding"]}
        tgt_tree = {"ids": targets}
        if attention_mask is not None:
            tgt_tree["mask"] = attention_mask
            # global denominator D = total_keep / (microbatch-shard
            # units): per-mb loss sum/D, meaned over M units per shard
            # and pmean'd over n_dp shards, equals the monolithic
            # global masked mean bit-for-bit in exact arithmetic
            n_dp = (self.mesh.shape[self.batch_axis]
                    if self.batch_axis else 1)
            total_keep = jnp.maximum(
                attention_mask[:, 1:].sum().astype(jnp.float32), 1.0)
            tgt_tree["denom"] = jnp.full(
                (targets.shape[0],),
                total_keep / (self.num_microbatches * n_dp),
                jnp.float32)

        def run_wrapped(sp, xb, tgt, lp):
            loss, g, dxb, dlp = run(
                sp, self._schedule_input(*xb, needs_rng), tgt, lp)
            dh = dxb[0]
            if self.seq_axis:
                # the tail's all_gather REPLICATES the loss per sp
                # shard and its transpose SUMS the identical cotangent
                # copies, so stage partials / head grads / dh carry an
                # extra n_sp factor (same algebra as PipelinedBert)
                n_sp = lax.axis_size(self.seq_axis)
                loss = lax.pmean(loss, self.seq_axis)
                g = jax.tree_util.tree_map(
                    lambda a: lax.pmean(a, self.seq_axis), g)
                dlp = jax.tree_util.tree_map(
                    lambda a: lax.pmean(a, self.seq_axis), dlp)
                dh = dh / n_sp
            if self.batch_axis:
                n = lax.axis_size(self.batch_axis)
                loss = lax.pmean(loss, self.batch_axis)
                g = jax.tree_util.tree_map(
                    lambda a: lax.pmean(a, self.batch_axis), g)
                dlp = jax.tree_util.tree_map(
                    lambda a: lax.pmean(a, self.batch_axis), dlp)
                dh = dh / n
            return loss, g, dh, dlp

        hspec = P(self.batch_axis, self.seq_axis)
        bspec = P(self.batch_axis, None, None, self.seq_axis)
        f = jax.shard_map(
            run_wrapped, mesh=self.mesh,
            in_specs=(jax.tree_util.tree_map(lambda _: P(self.pipe_axis),
                                             p["stages"]),
                      (hspec, bspec),
                      jax.tree_util.tree_map(lambda _: P(self.batch_axis),
                                             tgt_tree),
                      jax.tree_util.tree_map(lambda _: P(), loss_params)),
            out_specs=(P(),
                       jax.tree_util.tree_map(
                           lambda _: P(self.pipe_axis), p["stages"]),
                       hspec,
                       jax.tree_util.tree_map(lambda _: P(),
                                              loss_params)),
            **self._partial_manual_kwargs())
        loss, stage_grads, dh, lp_grads = f(p["stages"], (x, bias),
                                            tgt_tree, loss_params)
        (embed_grads,) = embed_vjp(dh)
        # tied wte: embedding-lookup grad + LM-head grad, summed (the
        # vjp's cotangent tree is fresh, so shallow-copying the two
        # dicts we touch keeps the mutation local and explicit)
        embed_grads = {**embed_grads, "wte": dict(embed_grads["wte"])}
        embed_grads["wte"]["embedding"] = (
            embed_grads["wte"]["embedding"] + lp_grads["wte"])
        # constrain_grads: without it the grads exit the partial-manual
        # shard_map with unspecified tp-axis sharding and one optimizer
        # step strips the Megatron placement (PipelinedCommon)
        return loss, self.constrain_grads(
            {"embed": embed_grads, "stages": stage_grads,
             "head": lp_grads["head"]})
