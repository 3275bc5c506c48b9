"""The ``lfm2_moe`` decoder (Liquid AI's LFM2 mixtures of experts) in
plain ``jax.numpy``: the yardstick the served path is compared with.

The equations, for a layer's input ``x`` (rows, T, hidden), positions
``p``, ``RMS(x; w) = x / sqrt(mean(x^2) + eps) * w``, ``h = RMS(x;
w_operator)``:

- a ``conv`` layer: ``B | C | xc = h W_in`` (hidden -> 3 hidden, no
  bias), ``u = B * xc``, ``z_t = sum_i w_i * u_{t - K + 1 + i}`` with
  ``K = conv_L_cache`` taps of ``hidden`` weights each and ``u`` nought
  before position 0 (a depthwise causal convolution), output ``(C * z)
  W_out``;
- a ``full_attention`` layer: ``q = h W_q`` as (T, heads, d), ``k = h
  W_k``, ``v = h W_v`` as (T, kv heads, d), ``d = hidden / heads``, no
  bias; ``q`` and ``k`` each normalised over a head's ``d`` values with
  a weight of their own, then rotated at ``p`` (half-split: ``x * cos +
  rotate_half(x) * sin``, angles ``p * theta^(-2i / d)`` over both
  halves, base ``rope_parameters.rope_theta``); query head ``i`` reads
  key-value head ``i // (heads / kv heads)``; scores ``q . k / sqrt(d)``,
  key ``j`` seen by query ``i`` iff ``p_j <= p_i``; softmax; output
  ``concat(o) W_o``;
- ``x = x + mixer``; ``h2 = RMS(x; w_ffn)``; the leading
  ``num_dense_layers`` layers add ``W2(silu(W1 h2) * (W3 h2))`` of
  ``intermediate_size``; the others the expert layer: ``s =
  sigmoid(h2 W_r)`` over the ``num_experts``, chosen = the
  ``num_experts_per_tok`` largest of ``s + expert_bias``
  (``use_expert_bias``), weights ``s[chosen] / (sum s[chosen] + 1e-6) *
  routed_scaling_factor``, output ``sum_i weight_i * expert_i(h2)``, no
  shared expert;
- a final ``RMS``, then the head tied to the embedding: ``logits = x
  E^T``.

Assumed where the published keys are silent (the configuration's
``assumed``): the tie (``Lfm2Config``'s default is
``tie_word_embeddings=True`` and the config carries no such key), the
sigmoid scoring and the normaliser's epsilon of 1e-6 (the family's
published ``lfm2_moe`` modelling code).  The short convolution, the
per-head norms and the place of the norms are the family's dense
modelling code (transformers' ``Lfm2ShortConv``, ``Lfm2Attention``,
``Lfm2DecoderLayer``).

Departures from the description, each because the sizes ask for it and
none changing a value: the weights stay in the dtype they were drawn in
and are widened to float32 one layer, and within an expert layer one
expert, at a time; attention takes its query rows in blocks, projecting
each block's queries and output as it goes; the dense feed-forward is
summed over slices of its width; the head's logits are made and reduced
a block of positions at a time.  No kernel, no cache, no batching trick,
and nothing imported from the program under test.

**Which positions are judged**: as ``reference/deepseek_v3.py`` sets
out.  A router that keeps 4 of 64 sigmoid scores is not continuous:
where the fourth and fifth lie within an arithmetic's rounding, that
arithmetic keeps another expert and a logit moves by whole units.
``routing_weights`` returns each choice's margin and, where the
configuration's file states ``decided_margin``, ``token_gaps`` and
``logit_at`` report a gap of nought at positions whose margin in some
expert layer is not above it.

``precision`` selects the arithmetic as in ``reference/gpt2.py``.
"""

import jax
import jax.numpy as jnp

from benchmarks.reference.exaone_moe import (
    _block,
    _f32,
    gated,
    gated_in_slices,
    rms_norm,
    rotary,
)
from benchmarks.reference.gpt2 import PRECISIONS, _mm, _rounder

__all__ = ["PRECISIONS", "logit_and_margin_at", "logit_at", "logits",
           "longest_row", "mass_above", "param_table", "routing_choices",
           "routing_margins", "stacked", "token_gaps", "vocab",
           "weight_std"]

# query rows an attention block takes, positions a block of logits
_QUERY_BLOCK = 128
_LOGIT_BLOCK = 512
# the top-k normaliser's epsilon (assumed: the family's published code)
TOPK_EPS = 1e-6


def vocab(sizes):
    """How many ids there are; the traffic draws from ``range(vocab)``."""
    return sizes["vocab_size"]


def longest_row(sizes):
    """The longest row of ids the comparison pads to: the traffic's
    ``max_total`` (rotary positions need no table)."""
    return sizes["reference_longest_row"]


def weight_std(sizes):
    return sizes["assumed"]["initializer_range"]


def kinds(sizes):
    return list(sizes["layer_types"][:sizes["num_hidden_layers"]])


def head_dim(sizes):
    return sizes["hidden_size"] // sizes["num_attention_heads"]


def param_table(sizes):
    """``{path: (shape, kind)}`` for every parameter, under the names
    the program's module gives them.  A layer's leaves lie under
    ``layer_<i>``, not ``block_<i>``: ``harness/weights.py`` draws the
    ``block_<i>`` leaves of one name as one stacked array, and 8 layers
    of 64 experts in one draw need 9 GiB of temporaries beside the 9.6 GiB
    of weights (compiled for a described v5e), more than the chip has;
    drawn one leaf at a time they need 0.76."""
    h, nh, nkv, v = sizes["hidden_size"], sizes["num_attention_heads"], \
        sizes["num_key_value_heads"], sizes["vocab_size"]
    d, e, f = head_dim(sizes), sizes["num_experts"], \
        sizes["moe_intermediate_size"]
    table = {"embed_tokens": ((v, h), "normal"),
             "embedding_norm/scale": ((h,), "ones")}
    for i, kind in enumerate(kinds(sizes)):
        b = f"layer_{i}/"
        table[b + "operator_norm/scale"] = ((h,), "ones")
        table[b + "ffn_norm/scale"] = ((h,), "ones")
        if kind == "conv":
            table[b + "conv/in_proj"] = ((h, 3 * h), "normal")
            table[b + "conv/conv"] = ((sizes["conv_L_cache"], h), "normal")
            table[b + "conv/out_proj"] = ((h, h), "normal")
        else:
            a = b + "self_attn/"
            table[a + "q_proj"] = ((h, nh, d), "normal")
            table[a + "k_proj"] = ((h, nkv, d), "normal")
            table[a + "v_proj"] = ((h, nkv, d), "normal")
            table[a + "out_proj"] = ((nh, d, h), "normal")
            table[a + "q_layernorm/scale"] = ((d,), "ones")
            table[a + "k_layernorm/scale"] = ((d,), "ones")
        if i < sizes["num_dense_layers"]:
            w = sizes["intermediate_size"]
            table[b + "feed_forward/gate_proj"] = ((h, w), "normal")
            table[b + "feed_forward/up_proj"] = ((h, w), "normal")
            table[b + "feed_forward/down_proj"] = ((w, h), "normal")
            continue
        m = b + "moe/"
        table[m + "router"] = ((h, e), "normal")
        # the selection bias starts at nought
        table[m + "e_score_correction_bias"] = ((e,), "zeros")
        table[m + "experts_gate_proj"] = ((e, h, f), "normal")
        table[m + "experts_up_proj"] = ((e, h, f), "normal")
        table[m + "experts_down_proj"] = ((e, f, h), "normal")
    return table


def stacked(params, sizes):
    """The parameter tree as the comparison takes it: as drawn; the
    functions below walk ``layer_<i>`` by name."""
    del sizes
    return params


def short_conv(x, p, sizes, q):
    """A conv layer's mixer on ``x`` (rows, T, hidden) after its norm;
    ``p`` its weights in float32."""
    h = sizes["hidden_size"]
    bcx = _mm("bth,hc->btc", x, p["in_proj"], q)
    u = bcx[..., :h] * bcx[..., 2 * h:]
    t, taps = x.shape[1], p["conv"]
    keep = taps.shape[0] - 1
    ext = jnp.pad(u, ((0, 0), (keep, 0), (0, 0)))
    z = sum(taps[i] * ext[:, i:i + t] for i in range(keep + 1))
    return _mm("bth,hc->btc", bcx[..., h:2 * h] * z, p["out_proj"], q)


def attention(x, p, sizes, q):
    """A ``full_attention`` layer on ``x`` (rows, T, hidden) after its
    norm; ``p`` its weights in float32."""
    rows, t, _ = x.shape
    nh, nkv, d = sizes["num_attention_heads"], \
        sizes["num_key_value_heads"], head_dim(sizes)
    eps = sizes["norm_eps"]
    theta = float(sizes["rope_parameters"]["rope_theta"])
    pos = jnp.arange(t)
    k = rotary(rms_norm(_mm("bth,hnd->btnd", x, p["k_proj"], q),
                        p["k_layernorm"]["scale"], eps), pos, theta)
    v = _mm("bth,hnd->btnd", x, p["v_proj"], q)
    scale = 1.0 / jnp.sqrt(jnp.float32(d))
    qb = _block(t, _QUERY_BLOCK)

    def block(start):
        """Query rows ``start .. start + qb`` against all the keys."""
        xq = jax.lax.dynamic_slice_in_dim(x, start, qb, 1)
        q_pos = start + jnp.arange(qb)
        qh = rotary(rms_norm(_mm("bth,hnd->btnd", xq, p["q_proj"], q),
                             p["q_layernorm"]["scale"], eps), q_pos, theta)
        s = _mm("bqgpd,bkgd->bgpqk",
                qh.reshape(rows, qb, nkv, nh // nkv, d), k, q) * scale
        seen = q_pos[:, None] >= pos[None, :]
        w = jax.nn.softmax(jnp.where(seen, s, -jnp.inf), -1)
        o = _mm("bgpqk,bkgd->bqgpd", w, v, q).reshape(rows, qb, nh, d)
        return _mm("bqnd,ndh->bqh", o, p["out_proj"], q)

    o = jax.lax.map(block, jnp.arange(0, t, qb))     # (T/qb, rows, qb, h)
    return jnp.moveaxis(o, 0, 1).reshape(rows, t, -1)


def routing_weights(x, p, sizes, q):
    """(rows, T, experts) each token's weight for every expert: nought
    where the expert was not chosen.  Also the chosen ids (rows, T, k)
    and the choice's margin (rows, T): how far the last score chosen
    lies above the first one left out."""
    k = sizes["num_experts_per_tok"]
    s = jax.nn.sigmoid(_mm("bth,he->bte", x, p["router"], q))
    top, order = jax.lax.top_k(s + p["e_score_correction_bias"], k + 1)
    chosen, margin = order[..., :k], top[..., k - 1] - top[..., k]
    picked = jnp.take_along_axis(s, chosen, -1)
    if sizes["norm_topk_prob"]:
        picked = picked / (jnp.sum(picked, -1, keepdims=True) + TOPK_EPS)
    picked = picked * sizes["routed_scaling_factor"]
    onehot = jax.nn.one_hot(chosen, s.shape[-1], dtype=jnp.float32)
    return jnp.einsum("btk,btke->bte", picked, onehot), chosen, margin


def experts(x, p, sizes, q):
    """The expert layer on ``x`` (rows, T, hidden) after its norm: every
    expert over every token, weighted (nought where not chosen); ``p``
    the layer's ``moe`` weights as drawn (each expert widened when its
    turn comes)."""
    small = _f32({k: p[k] for k in ("router", "e_score_correction_bias")})
    weights, chosen, margin = routing_weights(x, small, sizes, q)

    def one(e, acc):
        pe = _f32({"gate_proj": p["experts_gate_proj"][e],
                   "up_proj": p["experts_up_proj"][e],
                   "down_proj": p["experts_down_proj"][e]})
        w = jax.lax.dynamic_slice_in_dim(weights, e, 1, 2)
        return acc + w * gated(x, pe, q)

    routed = jax.lax.fori_loop(0, sizes["num_experts"], one,
                               jnp.zeros_like(x))
    return routed, chosen, margin


def hidden(params, ids, sizes, precision="float32", choices=None,
           margins=None):
    """``ids`` (rows, T) -> the final norm's output (rows, T, hidden),
    float32.  ``choices`` / ``margins``: lists that are given each
    expert layer's chosen experts (rows, T, k) and margins (rows, T)."""
    q = _rounder(precision)
    eps = sizes["norm_eps"]
    x = params["embed_tokens"].astype(jnp.float32)[ids]
    for i, kind in enumerate(kinds(sizes)):
        p = params[f"layer_{i}"]
        h = rms_norm(x, p["operator_norm"]["scale"].astype(jnp.float32),
                     eps)
        if kind == "conv":
            x = x + short_conv(h, _f32(p["conv"]), sizes, q)
        else:
            x = x + attention(h, _f32(p["self_attn"]), sizes, q)
        h = rms_norm(x, p["ffn_norm"]["scale"].astype(jnp.float32), eps)
        if i < sizes["num_dense_layers"]:
            x = x + gated_in_slices(h, p["feed_forward"], q)
            continue
        y, chosen, margin = experts(h, p["moe"], sizes, q)
        x = x + y
        if choices is not None:
            choices.append(chosen)
        if margins is not None:
            margins.append(margin)
    return rms_norm(x, params["embedding_norm"]["scale"].astype(
        jnp.float32), eps)


def _head(params):
    """The head tied to the embedding, (hidden, vocab) float32."""
    return params["embed_tokens"].astype(jnp.float32).T


def logits(params, ids, sizes, precision="float32"):
    """``ids`` (rows, T) -> float32 logits (rows, T, vocab), whole: for
    rows short enough to hold them."""
    return _mm("bth,hv->btv", hidden(params, ids, sizes, precision),
               _head(params), _rounder(precision))


def routing_choices(params, ids, sizes, precision="float32"):
    """The experts every token chose in every expert layer: (expert
    layers, rows, T, k)."""
    choices = []
    hidden(params, ids, sizes, precision, choices)
    return jnp.stack(choices)


def routing_margins(params, ids, sizes, precision="float32"):
    """Every token's margin in every expert layer: (expert layers, rows,
    T)."""
    margins = []
    hidden(params, ids, sizes, precision, margins=margins)
    return jnp.stack(margins)


def _by_position(params, ids, sizes, precision, reduce, *per_position):
    """``reduce(logits block (rows, n, vocab), *blocks of per_position)``
    over the positions ``0 .. T-2`` in blocks: the head's logits are
    never whole.  Returns what ``reduce`` returns, (rows, T-1) each, and
    the positions' least routing margin over the expert layers."""
    q = _rounder(precision)
    margins = []
    x = hidden(params, ids, sizes, precision, margins=margins)[:, :-1]
    least = jnp.min(jnp.stack(margins), 0)[:, :-1] if margins \
        else jnp.full(x.shape[:2], jnp.inf)
    head = _head(params)
    rows, t, _ = x.shape
    n = min(t, _LOGIT_BLOCK)
    pad = -t % n

    def padded(a):
        return jnp.pad(a, ((0, 0), (0, pad)) + ((0, 0),) * (a.ndim - 2))

    def blocks(a):      # (rows, T, ...) -> (T/n, rows, n, ...)
        a = padded(a)
        return jnp.moveaxis(a.reshape(rows, -1, n, *a.shape[2:]), 1, 0)

    out = jax.lax.map(
        lambda args: reduce(_mm("bth,hv->btv", args[0], head, q),
                            *args[1:]),
        (blocks(x),) + tuple(blocks(a) for a in per_position))
    return jax.tree.map(
        lambda o: jnp.moveaxis(o, 0, 1).reshape(rows, -1)[:, :t], out), least


def _where_decided(gap, least_margin, sizes):
    """``gap`` at the positions whose routing is decided, nought at the
    others."""
    floor = sizes.get("decided_margin")
    return gap if floor is None else jnp.where(least_margin > floor, gap, 0.0)


def token_gaps(params, ids, lengths, sizes, precision="float32"):
    """``(best, gap_of_next, argmax)``, each (rows, T-1), as
    ``reference/gpt2.py`` defines them; the gap is nought where the
    position's routing is not decided."""
    del lengths

    def reduce(lg, nxt):
        best = jnp.max(lg, -1)
        return (best, best - jnp.take_along_axis(lg, nxt[..., None],
                                                 -1)[..., 0],
                jnp.argmax(lg, -1))

    (best, gap, first), least = _by_position(params, ids, sizes, precision,
                                             reduce, ids[:, 1:])
    return best, _where_decided(gap, least, sizes), first


def mass_above(params, ids, sizes, temperature, precision="float32"):
    """At every position (rows, T-1): the probability, at
    ``temperature``, of all the tokens whose logit exceeds that of the
    token that really follows."""
    def reduce(lg, nxt):
        mine = jnp.take_along_axis(lg, nxt[..., None], -1)
        p = jax.nn.softmax(lg / temperature, axis=-1)
        return jnp.sum(jnp.where(lg > mine, p, 0.0), -1)

    return _by_position(params, ids, sizes, precision, reduce,
                        ids[:, 1:])[0]


def logit_and_margin_at(params, ids, tokens, sizes, precision="float32"):
    """``(gap, least margin)``, each (rows, T-1): how far the logits of
    chosen ``tokens`` lie below each position's best, with NO position
    left out, and the position's least routing margin over the expert
    layers: what a ``decided_margin`` is set from
    (``tests/benchmark/control_readings.py``)."""
    def reduce(lg, tok):
        return jnp.max(lg, -1) - jnp.take_along_axis(
            lg, tok[..., None], -1)[..., 0]

    return _by_position(params, ids, sizes, precision, reduce, tokens)


def logit_at(params, ids, tokens, sizes, precision="float32"):
    """How far the logits of chosen ``tokens`` (rows, T-1) lie below
    each position's best; nought where the position's routing is not
    decided, as in ``token_gaps``."""
    gap, least = logit_and_margin_at(params, ids, tokens, sizes, precision)
    return _where_decided(gap, least, sizes)
