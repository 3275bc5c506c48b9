"""Operations and bytes that the ``exaone_moe`` decoder's work needs,
from the shapes alone: the counts of the reference beside this file
(``exaone_moe.py``).

Everything here follows the equations and the tokens that were
materialised, never what the program happens to do, and counts what the
SHARE HELD HERE does, not the whole model's: a query row of a sliding
layer meets the ``sliding_window`` keys that end with its own and no
page beside them; a token goes through the shared expert and through
those of the ``num_experts`` routed experts held here that it chose, in
expectation ``num_experts_per_tok * num_experts /
published.num_experts`` of them (half an expert at 8 of 128 held and 8
chosen: the selection bias is nought and the weights random, so the
router is even); the head has the ``vocab_size`` rows held and is
counted once for a span of tokens, since only a row that is sampled
needs its logits.  A later PR that changes a kernel must not change a
count.  A multiply-add is two operations.  ``sizes`` is a
configuration's file.
"""

BF16 = 2


def _router_width(s):
    return s.get("published", {}).get("num_experts", s["num_experts"])


def _kinds(s):
    """``(full layers, sliding layers)`` among those run."""
    kinds = s["layer_types"][:s["num_hidden_layers"]]
    full = sum(k == "full_attention" for k in kinds)
    return full, len(kinds) - full


def _attention_params(s):
    h, d = s["hidden_size"], s["head_dim"]
    return h * d * 2 * (s["num_attention_heads"]
                        + s["num_key_value_heads"])


def _gated(h, width):
    return 3 * h * width


def params_by_part(s):
    """Parameters of each part as held here, the norms' weights left
    out: one layer's attention, the dense feed-forward, one routed
    expert, the shared expert with the router and its bias, an expert
    layer with the ``num_experts`` held, embedding and head over the
    ``vocab_size`` rows held, and everything at ``num_hidden_layers``."""
    h, f = s["hidden_size"], s["moe_intermediate_size"]
    attention = _attention_params(s)
    dense = _gated(h, s["intermediate_size"])
    expert = _gated(h, f)
    width = _router_width(s)
    shared_router = _gated(h, s["num_shared_experts"] * f) + h * width \
        + width
    expert_layer = attention + s["num_experts"] * expert + shared_router
    n_dense = s["first_k_dense_replace"]
    n_expert = s["num_hidden_layers"] - n_dense
    ends = 2 * s["vocab_size"] * h
    return {"attention": attention, "dense_ff": dense, "expert": expert,
            "shared_and_router": shared_router,
            "expert_layer": expert_layer, "embedding_and_head": ends,
            "total": n_dense * (attention + dense)
            + n_expert * expert_layer + ends}


def total_params(s):
    return params_by_part(s)["total"]


def held_experts_a_token(s):
    """The routed experts held here that one token chooses, in
    expectation over an even router."""
    return s["num_experts_per_tok"] * s["num_experts"] / _router_width(s)


def active_matmul_params(s):
    """Parameters in the matrix products one token goes through here,
    all layers, the head left out: attention, the dense feed-forward or
    the shared expert, the router and the held experts it chose."""
    p = params_by_part(s)
    n_dense = s["first_k_dense_replace"]
    n_expert = s["num_hidden_layers"] - n_dense
    return (s["num_hidden_layers"] * p["attention"]
            + n_dense * p["dense_ff"]
            + n_expert * (held_experts_a_token(s) * p["expert"]
                          + p["shared_and_router"] - _router_width(s)))


def _attention_per_key(s):
    """Operations of one query token against one key, one layer: every
    query head's score over ``head_dim`` values and its value row."""
    return 2 * s["num_attention_heads"] * 2 * s["head_dim"]


def _keys(first, last):
    """Keys the tokens at positions ``first .. last - 1`` attend in a
    full layer, each itself and all before it: the sum of ``p + 1``."""
    return (first + 1 + last) * (last - first) // 2


def _window_keys(first, last, window):
    """... in a sliding layer: the sum of ``min(p + 1, window)``."""
    ramp = min(last, max(first, window - 1))      # positions under it
    return _keys(first, ramp) + (last - ramp) * window


def attention_flops(s, first, last):
    """``(full layers', sliding layers')`` attention operations for the
    tokens at ``first .. last - 1`` of one sequence."""
    if last <= first:
        return 0, 0
    full, sliding = _kinds(s)
    per_key = _attention_per_key(s)
    return (full * per_key * _keys(first, last),
            sliding * per_key * _window_keys(first, last,
                                             s["sliding_window"]))


def forward_flops_at(s, first, last):
    """Forward operations for the tokens at positions ``first`` ..
    ``last - 1`` of one sequence: what prefill chunks and decode steps
    have to compute here.  The head is counted once for the span."""
    n = last - first
    if n <= 0:
        return 0
    return (2 * active_matmul_params(s) * n
            + sum(attention_flops(s, first, last))
            + 2 * s["hidden_size"] * s["vocab_size"])


def row_bytes(s):
    """What the cache keeps of one token in one layer, bfloat16: every
    key-value head's ``K | V``."""
    return s["num_key_value_heads"] * 2 * s["head_dim"] * BF16


# -- the kernels ----------------------------------------------------------------

def full_attention_flops_bytes(s, spans, decode_live_tokens):
    """The full layers' attention for the tokens materialised: ``spans``
    the ``(first, last)`` positions each sequence advanced over;
    ``decode_live_tokens`` the cached tokens of the sequences each
    decode launch served, summed over launches, whose rows such a launch
    has to read (a chunk's reads are not counted: the operations bound
    it)."""
    flops = sum(attention_flops(s, a, b)[0] for a, b in spans)
    return flops, _kinds(s)[0] * decode_live_tokens * row_bytes(s)


def window_attention_flops_bytes(s, spans, decode_window_rows):
    """The sliding layers' attention: ``decode_window_rows`` the live
    rows the decode launches' queries could see, at most
    ``sliding_window`` a query, summed over launches."""
    flops = sum(attention_flops(s, a, b)[1] for a, b in spans)
    return flops, _kinds(s)[1] * decode_window_rows * row_bytes(s)


def moe_gmm_flops_bytes(s, tokens, chunk_launches, step_launches):
    """The routed experts' work here: ``tokens`` materialised, each
    through the held experts it chose (``held_experts_a_token``) of
    every expert layer; a chunk launch has to read every held expert's
    weights (256 tokens choose 128 times among 8), a decode or verify
    launch at least what one token chooses of them."""
    n_expert = s["num_hidden_layers"] - s["first_k_dense_replace"]
    expert = _gated(s["hidden_size"], s["moe_intermediate_size"])
    flops = 2 * tokens * held_experts_a_token(s) * expert * n_expert
    nbytes = n_expert * expert * BF16 * (
        chunk_launches * s["num_experts"]
        + step_launches * held_experts_a_token(s))
    return flops, nbytes
