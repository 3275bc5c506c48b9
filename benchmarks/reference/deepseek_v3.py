"""The ``deepseek_v3`` decoder as published, in plain ``jax.numpy``: the
yardstick the served path is compared with.

The equations are those of the published modelling code of the
``deepseek_v3`` model type (DeepSeek-AI 2024, "DeepSeek-V3 Technical
Report", sections 2.1.1 and 2.1.2, and the reference implementation its
checkpoints run under), with no query compression (``q_lora_rank``
null), one group (``n_group`` 1, ``topk_group`` 1) and no rope scaling,
which is what the configurations here state.  For a layer's input ``x``
(rows, T, hidden), positions ``p``, ``RMS(x) = x / sqrt(mean(x^2) + eps)
* w``:

- attention, on ``h = RMS(x)``: ``q = h W_q`` split per head into
  ``q_nope | q_pe``; ``[c_raw | k_pe_raw] = h W_kva``; ``c = RMS(c_raw)``
  with its own weight; ``q_pe`` and ``k_pe`` rotated at ``p`` with base
  ``rope_theta``; ``k_pe`` one row for all heads; ``[k_nope | v]_head =
  c W_kvb``; scores ``(q_nope . k_nope + q_pe . k_pe) / sqrt(qk_nope +
  qk_rope)``, causal softmax, ``o = softmax v``, output ``concat(o) W_o``.
  Only this expanded form is here: no absorbed product, no cache.
- **Rotary on interleaved pairs** (``rope_interleave`` true), the
  reading implemented: the pairs ``(2i, 2i + 1)`` of the projection's
  output are the rotated pairs.  As the published code's
  ``apply_rotary_pos_emb_interleave`` does, the vector is first viewed
  as (dim/2, 2), transposed and flattened, which brings pair ``i`` to
  the places ``(i, i + dim/2)``; then ``x * cos + rotate_half(x) * sin``
  with ``rotate_half(x) = [-x2 | x1]`` and the angles ``p * theta^(-2i /
  dim)`` repeated over both halves.  The result stays in the half-split
  order, for queries and keys alike.
- dense feed-forward (the leading ``first_k_dense_replace`` layers):
  ``W_down(silu(W_gate h2) * (W_up h2))``, ``h2 = RMS(x + attention)``.
- expert layers: ``s = sigmoid(h2 W_r)``; chosen = the
  ``num_experts_per_tok`` largest of ``s + e_score_correction_bias``;
  weights ``s[chosen] / (sum s[chosen] + 1e-20) * routed_scaling_factor``
  (the bias selects and does not weigh); output ``sum_i weight_i *
  expert_i(h2) + shared(h2)``.  **The experts are the plainest loop:
  every expert over every token, its output multiplied by the token's
  weight for it, which is nought where it was not chosen.**  No token
  is dropped, nothing is sorted or grouped.
- final ``RMS``, then the untied head.

Departures from the published description, each because the sizes ask
for it and none changing a value: the weights stay in the dtype they
were drawn in and are widened to float32 one layer, and within an expert
layer one expert, at a time (4.43B float32 values would not fit beside
the bfloat16 ones); attention takes its query rows in blocks, so that no
(heads, T, T) block of scores exists at T = 16,640; the head's logits are
made and reduced a block of positions at a time in ``token_gaps``,
``mass_above`` and ``logit_at`` (``logits`` itself returns them whole,
for short rows).  No kernel, no cache, no batching trick, and nothing
imported from the program under test.

**Which positions are judged.**  A router keeps the k largest of its
scores, so its output is not continuous in its input: where the k-th
score and the next lie closer than an arithmetic's rounding of the
router's input, that arithmetic keeps the other expert, the layer's
output moves by a whole expert's share, and a logit by whole units,
where rounding moves it by hundredths everywhere else.  There float32's
own choice is one of two that are equal to rounding, and its logits are
no yardstick for a program in the precision the configuration states.
So ``routing_weights`` also returns each choice's margin (the k-th
score less the next, bias included), and where the configuration's file
states ``decided_margin``, ``token_gaps`` and ``logit_at`` report a gap
of nought at every position whose margin in some expert layer is not
above it: the comparison's widest gap is then over the positions whose
routing float32 decides by more than that, for the program and for the
controls alike.  The floor is set from readings, as a limit is
(``PERF.md`` section 2: the margins at which the program chose other
experts, the share of positions left out, and that the fp8 control
still reads far over the limit at the positions kept).  A file
without the key has every position judged.

``precision`` selects the arithmetic as in ``reference/gpt2.py``:
``"float32"`` is the reference (float32 everywhere, matrix products at
``Precision.HIGHEST``); ``"bfloat16"`` and ``"fp8"`` round the operands of
every matrix product (the router's too) and are the controls.
"""

import jax
import jax.numpy as jnp

from benchmarks.reference.gpt2 import PRECISIONS, _mm, _rounder

__all__ = ["PRECISIONS", "logit_at", "logits", "longest_row", "mass_above",
           "param_table", "routing_choices", "routing_margins", "stacked",
           "token_gaps", "vocab", "weight_std"]

# query rows an attention block takes, positions a block of logits
_QUERY_BLOCK = 512
_LOGIT_BLOCK = 512


def vocab(sizes):
    """How many ids there are: the traffic draws from ``range(vocab)``."""
    return sizes["vocab_size"]


def longest_row(sizes):
    """The longest row of ids the comparison pads to: the traffic's
    ``max_total``, which the configuration's file states, and not the
    published 32,768 positions (rotary positions need no table)."""
    return sizes["reference_longest_row"]


def weight_std(sizes):
    return sizes["assumed"]["initializer_range"]


def _layers(sizes):
    return sizes["num_hidden_layers"], sizes["first_k_dense_replace"]


def param_table(sizes):
    """``{path: (shape, kind)}`` for every parameter, under the names
    the program's module gives them."""
    h, nh, v = sizes["hidden_size"], sizes["num_attention_heads"], \
        sizes["vocab_size"]
    rank, dn, dr, dv = sizes["kv_lora_rank"], sizes["qk_nope_head_dim"], \
        sizes["qk_rope_head_dim"], sizes["v_head_dim"]
    e, f = sizes["n_routed_experts"], sizes["moe_intermediate_size"]
    table = {"embed_tokens": ((v, h), "normal"),
             "lm_head": ((h, v), "normal"),
             "norm/scale": ((h,), "ones")}
    n_layers, n_dense = _layers(sizes)

    def gated(prefix, width):
        table[prefix + "gate_proj"] = ((h, width), "normal")
        table[prefix + "up_proj"] = ((h, width), "normal")
        table[prefix + "down_proj"] = ((width, h), "normal")

    for i in range(n_layers):
        b = f"block_{i}/"
        table[b + "input_layernorm/scale"] = ((h,), "ones")
        table[b + "post_attention_layernorm/scale"] = ((h,), "ones")
        a = b + "attention/"
        table[a + "q_proj"] = ((h, nh, dn + dr), "normal")
        table[a + "kv_a_proj_with_mqa"] = ((h, rank + dr), "normal")
        table[a + "kv_a_layernorm/scale"] = ((rank,), "ones")
        table[a + "kv_b_proj"] = ((rank, nh, dn + dv), "normal")
        table[a + "o_proj"] = ((nh, dv, h), "normal")
        if i < n_dense:
            gated(b + "mlp/", sizes["intermediate_size"])
            continue
        m = b + "moe/"
        table[m + "router"] = ((h, e), "normal")
        # the selection bias starts at nought, as the published code
        # initialises it
        table[m + "e_score_correction_bias"] = ((e,), "zeros")
        table[m + "experts_gate_proj"] = ((e, h, f), "normal")
        table[m + "experts_up_proj"] = ((e, h, f), "normal")
        table[m + "experts_down_proj"] = ((e, f, h), "normal")
        gated(m + "shared_experts/", sizes["n_shared_experts"] * f)
    return table


def stacked(params, sizes):
    """The parameter tree as the comparison takes it: as drawn.  The
    layers are not stacked (a second copy of 8.9 GB would not fit); the
    functions below walk ``block_<i>`` by name."""
    del sizes
    return params


def _f32(tree):
    return jax.tree.map(lambda a: a.astype(jnp.float32), tree)


def rms_norm(x, scale, eps):
    return x * jax.lax.rsqrt(jnp.mean(jnp.square(x), -1, keepdims=True)
                             + eps) * scale


def rotate_half(x):
    half = x.shape[-1] // 2
    return jnp.concatenate([-x[..., half:], x[..., :half]], -1)


def rotary_interleaved(x, positions, theta):
    """``x`` (..., T, dim) with rotated pairs ``(2i, 2i + 1)``,
    ``positions`` (T,): the published ``apply_rotary_pos_emb_interleave``
    (see the module's docstring)."""
    dim = x.shape[-1]
    x = jnp.swapaxes(x.reshape(*x.shape[:-1], dim // 2, 2), -1, -2)
    x = x.reshape(*x.shape[:-2], dim)
    inv_freq = 1.0 / theta ** (jnp.arange(0, dim, 2, dtype=jnp.float32)
                               / dim)
    freqs = positions.astype(jnp.float32)[:, None] * inv_freq[None, :]
    emb = jnp.concatenate([freqs, freqs], -1)               # (T, dim)
    return x * jnp.cos(emb) + rotate_half(x) * jnp.sin(emb)


def _query_block(t):
    return next(b for b in range(min(t, _QUERY_BLOCK), 0, -1) if t % b == 0)


def attention(x, p, sizes, q):
    """Expanded latent attention on ``x`` (rows, T, hidden) after its
    norm; ``p`` the layer's attention weights in float32."""
    rows, t, _ = x.shape
    dn, rank = sizes["qk_nope_head_dim"], sizes["kv_lora_rank"]
    theta = float(sizes["rope_theta"])
    pos = jnp.arange(t)
    qh = _mm("bth,hnd->bntd", x, p["q_proj"], q)
    q_nope, q_pe = qh[..., :dn], rotary_interleaved(qh[..., dn:], pos,
                                                    theta)
    kva = _mm("bth,hr->btr", x, p["kv_a_proj_with_mqa"], q)
    c = rms_norm(kva[..., :rank], p["kv_a_layernorm"]["scale"],
                 sizes["rms_norm_eps"])
    k_pe = rotary_interleaved(kva[..., rank:], pos, theta)  # (rows, T, dr)
    kv = _mm("btr,rnd->bntd", c, p["kv_b_proj"], q)
    k_nope, v = kv[..., :dn], kv[..., dn:]
    scale = 1.0 / jnp.sqrt(jnp.float32(dn + q_pe.shape[-1]))

    qb = _query_block(t)

    def block(start):
        """Query rows ``start .. start + qb`` against every key."""
        qn = jax.lax.dynamic_slice_in_dim(q_nope, start, qb, 2)
        qp = jax.lax.dynamic_slice_in_dim(q_pe, start, qb, 2)
        s = (_mm("bnqd,bnkd->bnqk", qn, k_nope, q)
             + _mm("bnqd,bkd->bnqk", qp, k_pe, q)) * scale
        causal = (start + jnp.arange(qb))[:, None] >= pos[None, :]
        w = jax.nn.softmax(jnp.where(causal[None, None], s, -jnp.inf), -1)
        return _mm("bnqk,bnkd->bqnd", w, v, q)       # (rows, qb, nh, dv)

    o = jax.lax.map(block, jnp.arange(0, t, qb))     # (T/qb, rows, qb, ..)
    o = jnp.moveaxis(o, 0, 1).reshape(rows, t, *o.shape[3:])
    return _mm("bqnd,ndh->bqh", o, p["o_proj"], q)


def gated(x, p, q):
    return _mm("bti,ih->bth",
               jax.nn.silu(_mm("bth,hi->bti", x, p["gate_proj"], q))
               * _mm("bth,hi->bti", x, p["up_proj"], q), p["down_proj"], q)


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
        picked = picked / (jnp.sum(picked, -1, keepdims=True) + 1e-20)
    picked = picked * sizes["routed_scaling_factor"]
    onehot = jax.nn.one_hot(chosen, s.shape[-1], dtype=jnp.float32)
    return jnp.einsum("btk,btke->bte", picked, onehot), chosen, margin


def experts(x, p, sizes, q):
    """The expert layer on ``x`` (rows, T, hidden) after its norm:
    every expert over every token, weighted; ``p`` the layer's ``moe``
    weights as drawn (each expert widened when its turn comes)."""
    small = _f32({k: p[k] for k in ("router", "e_score_correction_bias",
                                    "shared_experts")})
    weights, chosen, margin = routing_weights(x, small, sizes, q)

    def one(e, acc):
        pe = _f32({"gate_proj": p["experts_gate_proj"][e],
                   "up_proj": p["experts_up_proj"][e],
                   "down_proj": p["experts_down_proj"][e]})
        w = jax.lax.dynamic_slice_in_dim(weights, e, 1, 2)   # (rows, T, 1)
        return acc + w * gated(x, pe, q)

    routed = jax.lax.fori_loop(0, p["experts_gate_proj"].shape[0], one,
                               jnp.zeros_like(x))
    return routed + gated(x, small["shared_experts"], q), chosen, margin


def hidden(params, ids, sizes, precision="float32", choices=None,
           margins=None):
    """``ids`` (rows, T) -> the final norm's output (rows, T, hidden),
    float32.  ``choices``: a list that is given each expert layer's
    chosen experts (rows, T, k), in order; ``margins``: one that is
    given each expert layer's margins (rows, T)."""
    q = _rounder(precision)
    eps = sizes["rms_norm_eps"]
    n_layers, n_dense = _layers(sizes)
    x = params["embed_tokens"].astype(jnp.float32)[ids]
    for i in range(n_layers):
        p = params[f"block_{i}"]
        h = rms_norm(x, p["input_layernorm"]["scale"].astype(jnp.float32),
                     eps)
        x = x + attention(h, _f32(p["attention"]), sizes, q)
        h = rms_norm(x, p["post_attention_layernorm"]["scale"].astype(
            jnp.float32), eps)
        if i < n_dense:
            x = x + gated(h, _f32(p["mlp"]), q)
        else:
            y, chosen, margin = experts(h, p["moe"], sizes, q)
            x = x + y
            if choices is not None:
                choices.append(chosen)
            if margins is not None:
                margins.append(margin)
    return rms_norm(x, params["norm"]["scale"].astype(jnp.float32), eps)


def logits(params, ids, sizes, precision="float32"):
    """``ids`` (rows, T) -> float32 logits (rows, T, vocab), whole: for
    rows short enough to hold them."""
    return _mm("bth,hv->btv", hidden(params, ids, sizes, precision),
               params["lm_head"].astype(jnp.float32), _rounder(precision))


def routing_choices(params, ids, sizes, precision="float32"):
    """The experts every token chose in every expert layer: (expert
    layers, rows, T, k).  A router picks the k largest of 128 scores,
    and where the k-th and the next lie within a precision's rounding
    that precision picks the other: the share of (token, layer) choices
    that differ from float32's says how often, which is what moves a
    logit by whole units now and then (``PERF.md`` section 2)."""
    choices = []
    hidden(params, ids, sizes, precision, choices)
    return jnp.stack(choices)


def routing_margins(params, ids, sizes, precision="float32"):
    """Every token's margin in every expert layer: (expert layers, rows,
    T), how far the last of the k scores chosen lies above the first one
    left out."""
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
    head = params["lm_head"].astype(jnp.float32)
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
    others (the module's docstring, "Which positions are judged")."""
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


def logit_at(params, ids, tokens, sizes, precision="float32"):
    """How far the logits of chosen ``tokens`` (rows, T-1) lie below
    each position's best; nought where the position's routing is not
    decided, as in ``token_gaps``."""
    def reduce(lg, tok):
        return jnp.max(lg, -1) - jnp.take_along_axis(
            lg, tok[..., None], -1)[..., 0]

    gap, least = _by_position(params, ids, sizes, precision, reduce, tokens)
    return _where_decided(gap, least, sizes)
