"""The ``exaone_moe`` decoder (K-EXAONE) in plain ``jax.numpy``: the
yardstick the served path is compared with.

The equations, for a layer's input ``x`` (rows, T, hidden), positions
``p``, ``RMS(x; w) = x / sqrt(mean(x^2) + eps) * w``:

- attention, on ``h = RMS(x; w_in)``: ``q = h W_q`` as (T, heads,
  head_dim); ``k = h W_k``, ``v = h W_v`` as (T, kv heads, head_dim);
  no bias.  ``q`` and ``k`` are each normalised over a head's
  ``head_dim`` values with a weight of their own.  On a
  ``sliding_attention`` layer both are rotated at ``p``: all ``head_dim``
  values of a head, half-split (``x * cos + rotate_half(x) * sin``, the
  angles ``p * theta^(-2i / head_dim)`` repeated over both halves), base
  ``rope_theta``; a ``full_attention`` layer takes no rotation.  Query
  head ``i`` reads key-value head ``i // (heads / kv heads)``.  Scores
  ``q . k / sqrt(head_dim)`` in float32; key ``j`` is seen by query ``i``
  iff ``0 <= p_i - p_j`` and, on a sliding layer, ``p_i - p_j <
  sliding_window``; softmax, ``o = softmax v``, output ``concat(o) W_o``.
- dense feed-forward (the leading ``first_k_dense_replace`` layers):
  ``W_down(silu(W_gate h2) * (W_up h2))``, ``h2 = RMS(x + attention)``.
- expert layers: ``s = sigmoid(h2 W_r)`` over ALL the published experts
  (``published.num_experts``: the router keeps its width); chosen = the
  ``num_experts_per_tok`` largest of ``s + e_score_correction_bias``;
  weights ``s[chosen] / (sum s[chosen] + 1e-20) * routed_scaling_factor``;
  output ``sum_i weight_i * expert_i(h2) + shared(h2)``.  **The chip
  holds ``num_experts`` of them (``reduced``): the experts HELD run over
  every token, each output multiplied by the token's weight for that
  expert, which is nought where it was not chosen; what the absent
  experts would have added is left out, here as in the program, and the
  partial sum goes on to the next layer** (the ``model-configs`` guide,
  section 4).  Nothing is sorted, grouped or dropped.
- final ``RMS``, then the untied head over the ``vocab_size`` rows held.

Assumed where the published keys are silent (the configuration's
``assumed``): the two per-head norms and the rotation on sliding layers
alone are the convention of the family's published ``exaone4`` modelling
code; the two layer norms stand before attention and before the
feed-forward, as in the ``deepseek_v3`` block whose expert layer the type
reuses.  The multi-token prediction module is left out (``departures``).

Departures from the description, each because the sizes ask for it and
none changing a value: the weights stay in the dtype they were drawn in
and are widened to float32 one layer, and within an expert layer one
expert, at a time; attention takes its query rows in blocks, projecting
each block's queries and output as it goes, and a sliding layer's block
is given the keys its rows can see and no others (a slice of
``sliding_window + block - 1`` keys: the mask is applied all the same);
the dense feed-forward is summed over slices of its width (18,432 wide
over 32,768 tokens is 2.4 GB an activation, its float32 weights 1.4 GB);
the head's logits are made and
reduced a block of positions at a time.  No kernel, no cache, no
batching trick, and nothing imported from the program under test.

**Which positions are judged**: as ``reference/deepseek_v3.py`` sets
out.  A router that keeps 8 of 128 scores is no more continuous than
one that keeps 6: where the eighth score and the ninth lie within an
arithmetic's rounding, that arithmetic keeps another expert and a logit
moves by whole units.  ``routing_weights`` returns each choice's margin
and, where the configuration's file states ``decided_margin``,
``token_gaps`` and ``logit_at`` report a gap of nought at positions
whose margin in some expert layer is not above it.

``precision`` selects the arithmetic as in ``reference/gpt2.py``.
"""

import jax
import jax.numpy as jnp

from benchmarks.reference.gpt2 import PRECISIONS, _mm, _rounder

__all__ = ["PRECISIONS", "logit_and_margin_at", "logit_at", "logits", "longest_row", "mass_above",
           "param_table", "routing_choices", "routing_margins", "stacked",
           "token_gaps", "vocab", "weight_std"]

# query rows an attention block takes, the width of a slice of the dense
# feed-forward, positions a block of logits
_QUERY_BLOCK = 128
_WIDTH_BLOCK = 2048
_LOGIT_BLOCK = 512


def vocab(sizes):
    """How many ids there are: the rows of the vocabulary held here; the
    traffic draws from ``range(vocab)``."""
    return sizes["vocab_size"]


def longest_row(sizes):
    """The longest row of ids the comparison pads to: the traffic's
    ``max_total`` (rotary positions need no table)."""
    return sizes["reference_longest_row"]


def weight_std(sizes):
    return sizes["assumed"]["initializer_range"]


def router_width(sizes):
    """The experts the router scores: the published count, of which
    ``num_experts`` are held here."""
    return sizes.get("published", {}).get("num_experts",
                                          sizes["num_experts"])


def experts_held(sizes):
    """``(first, count)`` of the routed experts held here."""
    return sizes.get("experts_held_first", 0), sizes["num_experts"]


def windows(sizes):
    """For each layer run, ``sliding_window`` or None."""
    return [sizes["sliding_window"] if kind == "sliding_attention" else None
            for kind in sizes["layer_types"][:sizes["num_hidden_layers"]]]


def param_table(sizes):
    """``{path: (shape, kind)}`` for every parameter, under the names
    the program's module gives them."""
    h, nh, nkv, d, v = sizes["hidden_size"], sizes["num_attention_heads"], \
        sizes["num_key_value_heads"], sizes["head_dim"], sizes["vocab_size"]
    e, f = router_width(sizes), sizes["moe_intermediate_size"]
    held = experts_held(sizes)[1]
    table = {"embed_tokens": ((v, h), "normal"),
             "lm_head": ((h, v), "normal"),
             "norm/scale": ((h,), "ones")}

    def gated(prefix, width):
        table[prefix + "gate_proj"] = ((h, width), "normal")
        table[prefix + "up_proj"] = ((h, width), "normal")
        table[prefix + "down_proj"] = ((width, h), "normal")

    for i in range(sizes["num_hidden_layers"]):
        b = f"block_{i}/"
        table[b + "input_layernorm/scale"] = ((h,), "ones")
        table[b + "post_attention_layernorm/scale"] = ((h,), "ones")
        a = b + "attention/"
        table[a + "q_proj"] = ((h, nh, d), "normal")
        table[a + "k_proj"] = ((h, nkv, d), "normal")
        table[a + "v_proj"] = ((h, nkv, d), "normal")
        table[a + "o_proj"] = ((nh, d, h), "normal")
        table[a + "q_norm/scale"] = ((d,), "ones")
        table[a + "k_norm/scale"] = ((d,), "ones")
        if i < sizes["first_k_dense_replace"]:
            gated(b + "mlp/", sizes["intermediate_size"])
            continue
        m = b + "moe/"
        table[m + "router"] = ((h, e), "normal")
        # the selection bias starts at nought
        table[m + "e_score_correction_bias"] = ((e,), "zeros")
        table[m + "experts_gate_proj"] = ((held, h, f), "normal")
        table[m + "experts_up_proj"] = ((held, h, f), "normal")
        table[m + "experts_down_proj"] = ((held, f, h), "normal")
        gated(m + "shared_experts/", sizes["num_shared_experts"] * f)
    return table


def stacked(params, sizes):
    """The parameter tree as the comparison takes it: as drawn; the
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


def rotary(x, positions, theta):
    """``x`` (rows, T, heads, dim) rotated at ``positions`` (T,): all
    ``dim`` values of a head, half-split."""
    dim = x.shape[-1]
    inv_freq = 1.0 / theta ** (jnp.arange(0, dim, 2, dtype=jnp.float32)
                               / dim)
    freqs = positions.astype(jnp.float32)[:, None] * inv_freq[None, :]
    emb = jnp.concatenate([freqs, freqs], -1)[:, None, :]    # (T, 1, dim)
    return x * jnp.cos(emb) + rotate_half(x) * jnp.sin(emb)


def _block(t, most):
    return next(b for b in range(min(t, most), 0, -1) if t % b == 0)


def attention(x, p, sizes, q, window):
    """Attention on ``x`` (rows, T, hidden) after its norm; ``p`` the
    layer's attention weights in float32; ``window`` None for a full
    layer."""
    rows, t, _ = x.shape
    nh, nkv, d = sizes["num_attention_heads"], \
        sizes["num_key_value_heads"], sizes["head_dim"]
    eps = sizes["rms_norm_eps"]
    theta = float(sizes["rope_parameters"]["rope_theta"])
    pos = jnp.arange(t)
    k = rms_norm(_mm("bth,hnd->btnd", x, p["k_proj"], q),
                 p["k_norm"]["scale"], eps)
    v = _mm("bth,hnd->btnd", x, p["v_proj"], q)
    if window is not None:
        k = rotary(k, pos, theta)
    scale = 1.0 / jnp.sqrt(jnp.float32(d))
    qb = _block(t, _QUERY_BLOCK)
    # the keys a block of query rows is given: all of them, or those a
    # sliding layer's rows can see (the mask is applied either way)
    nk = t if window is None else min(t, window + qb - 1)

    def block(start):
        """Query rows ``start .. start + qb`` against their keys."""
        xq = jax.lax.dynamic_slice_in_dim(x, start, qb, 1)
        qh = rms_norm(_mm("bth,hnd->btnd", xq, p["q_proj"], q),
                      p["q_norm"]["scale"], eps)
        q_pos = start + jnp.arange(qb)
        if window is not None:
            qh = rotary(qh, q_pos, theta)
        first = 0 if window is None else jnp.clip(
            start - (window - 1), 0, t - nk)
        kb = jax.lax.dynamic_slice_in_dim(k, first, nk, 1)
        vb = jax.lax.dynamic_slice_in_dim(v, first, nk, 1)
        k_pos = first + jnp.arange(nk)
        s = _mm("bqgpd,bkgd->bgpqk",
                qh.reshape(rows, qb, nkv, nh // nkv, d), kb, q) * scale
        ahead = q_pos[:, None] - k_pos[None, :]
        seen = ahead >= 0
        if window is not None:
            seen = seen & (ahead < window)
        w = jax.nn.softmax(jnp.where(seen, s, -jnp.inf), -1)
        o = _mm("bgpqk,bkgd->bqgpd", w, vb, q).reshape(rows, qb, nh, d)
        return _mm("bqnd,ndh->bqh", o, p["o_proj"], q)

    o = jax.lax.map(block, jnp.arange(0, t, qb))     # (T/qb, rows, qb, h)
    return jnp.moveaxis(o, 0, 1).reshape(rows, t, -1)


def gated(x, p, q):
    return _mm("bti,ih->bth",
               jax.nn.silu(_mm("bth,hi->bti", x, p["gate_proj"], q))
               * _mm("bth,hi->bti", x, p["up_proj"], q), p["down_proj"], q)


def gated_in_slices(x, p, q):
    """``gated`` with weights as drawn, a slice of its width at a time:
    ``sum_c W_down[c](silu(W_gate[:, c] x) * (W_up[:, c] x))``, each
    slice widened when its turn comes, as an expert is."""
    width = p["gate_proj"].shape[1]
    n = _block(width, _WIDTH_BLOCK)

    def one(c, acc):
        cut = {"gate_proj": jax.lax.dynamic_slice_in_dim(
                   p["gate_proj"], c * n, n, 1),
               "up_proj": jax.lax.dynamic_slice_in_dim(
                   p["up_proj"], c * n, n, 1),
               "down_proj": jax.lax.dynamic_slice_in_dim(
                   p["down_proj"], c * n, n, 0)}
        return acc + gated(x, _f32(cut), q)

    return jax.lax.fori_loop(0, width // n, one, jnp.zeros_like(x))


def routing_weights(x, p, sizes, q):
    """(rows, T, experts) each token's weight for every published
    expert: nought where the expert was not chosen.  Also the chosen ids
    (rows, T, k) and the choice's margin (rows, T): how far the last
    score chosen lies above the first one left out."""
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
    """The expert layer on ``x`` (rows, T, hidden) after its norm: every
    expert HELD over every token, weighted (nought where not chosen);
    ``p`` the layer's ``moe`` weights as drawn (each expert widened when
    its turn comes)."""
    small = _f32({k: p[k] for k in ("router", "e_score_correction_bias",
                                    "shared_experts")})
    weights, chosen, margin = routing_weights(x, small, sizes, q)
    first, held = experts_held(sizes)

    def one(e, acc):
        pe = _f32({"gate_proj": p["experts_gate_proj"][e],
                   "up_proj": p["experts_up_proj"][e],
                   "down_proj": p["experts_down_proj"][e]})
        w = jax.lax.dynamic_slice_in_dim(weights, first + e, 1, 2)
        return acc + w * gated(x, pe, q)

    routed = jax.lax.fori_loop(0, held, one, jnp.zeros_like(x))
    return routed + gated(x, small["shared_experts"], q), chosen, margin


def hidden(params, ids, sizes, precision="float32", choices=None,
           margins=None):
    """``ids`` (rows, T) -> the final norm's output (rows, T, hidden),
    float32.  ``choices`` / ``margins``: lists that are given each
    expert layer's chosen experts (rows, T, k) and margins (rows, T)."""
    q = _rounder(precision)
    eps = sizes["rms_norm_eps"]
    x = params["embed_tokens"].astype(jnp.float32)[ids]
    for i, window in enumerate(windows(sizes)):
        p = params[f"block_{i}"]
        h = rms_norm(x, p["input_layernorm"]["scale"].astype(jnp.float32),
                     eps)
        x = x + attention(h, _f32(p["attention"]), sizes, q, window)
        h = rms_norm(x, p["post_attention_layernorm"]["scale"].astype(
            jnp.float32), eps)
        if i < sizes["first_k_dense_replace"]:
            x = x + gated_in_slices(h, p["mlp"], q)
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
