"""Operations and bytes that the ``lfm2_moe`` decoder's work needs, from
the shapes alone: the counts of the reference beside this file
(``lfm2_moe.py``).

Everything here follows the equations and the tokens that were
materialised, never what the program happens to do: a token goes
through its layer's short convolution or attention, the dense
feed-forward or the router and the ``num_experts_per_tok`` experts it
chose; a query row of an attention layer meets every key at or before
it; the head is counted once for a span of tokens, since only a row that
is sampled needs its logits.  A later PR that changes a kernel must not
change a count.  A multiply-add is two operations.  ``sizes`` is a
configuration's file.
"""

BF16, F32 = 2, 4


def _kinds(s):
    """``(conv layers, attention layers)`` among those run."""
    kinds = s["layer_types"][:s["num_hidden_layers"]]
    conv = sum(k == "conv" for k in kinds)
    return conv, len(kinds) - conv


def _head_dim(s):
    return s["hidden_size"] // s["num_attention_heads"]


def _gated(h, width):
    return 3 * h * width


def params_by_part(s):
    """Parameters of each part, the norms' weights left out: one conv
    layer's mixer (``in_proj``, ``out_proj``, the taps), one attention
    layer's, the dense feed-forward, one routed expert, the router with
    its bias, and the embedding (the head is tied to it), and everything
    at ``num_hidden_layers``."""
    h, d = s["hidden_size"], _head_dim(s)
    e = s["num_experts"]
    conv = 3 * h * h + h * h + s["conv_L_cache"] * h
    attention = h * d * 2 * (s["num_attention_heads"]
                             + s["num_key_value_heads"])
    dense = _gated(h, s["intermediate_size"])
    expert = _gated(h, s["moe_intermediate_size"])
    router = h * e + e
    n_conv, n_attn = _kinds(s)
    n_dense = s["num_dense_layers"]
    n_expert = s["num_hidden_layers"] - n_dense
    embedding = s["vocab_size"] * h
    return {"conv": conv, "attention": attention, "dense_ff": dense,
            "expert": expert, "router": router, "embedding": embedding,
            "total": n_conv * conv + n_attn * attention + n_dense * dense
            + n_expert * (e * expert + router) + embedding}


def total_params(s):
    return params_by_part(s)["total"]


def active_matmul_params(s):
    """Parameters in the matrix products one token goes through, all
    layers, the head left out: each layer's mixer, the dense
    feed-forward or the router and the experts it chose."""
    p = params_by_part(s)
    n_conv, n_attn = _kinds(s)
    n_dense = s["num_dense_layers"]
    n_expert = s["num_hidden_layers"] - n_dense
    conv_products = p["conv"] - s["conv_L_cache"] * s["hidden_size"]
    return (n_conv * conv_products + n_attn * p["attention"]
            + n_dense * p["dense_ff"]
            + n_expert * (s["num_experts_per_tok"] * p["expert"]
                          + s["hidden_size"] * s["num_experts"]))


def _attention_per_key(s):
    """Operations of one query token against one key, one layer: every
    query head's score over ``head_dim`` values and its value row."""
    return 2 * s["num_attention_heads"] * 2 * _head_dim(s)


def _keys(first, last):
    """Keys the tokens at positions ``first .. last - 1`` attend, each
    itself and all before it: the sum of ``p + 1``."""
    return (first + 1 + last) * (last - first) // 2


def attention_flops(s, first, last):
    """The attention layers' attention operations for the tokens at
    ``first .. last - 1`` of one sequence."""
    if last <= first:
        return 0
    return _kinds(s)[1] * _attention_per_key(s) * _keys(first, last)


def _conv_elementwise(s):
    """A conv layer's operations on one token outside its products:
    ``B * x``, ``K`` taps (a multiply and an add each) and the gate."""
    return (2 * s["conv_L_cache"] + 1) * s["hidden_size"]


def forward_flops_at(s, first, last):
    """Forward operations for the tokens at positions ``first`` ..
    ``last - 1`` of one sequence: what prefill chunks and decode steps
    have to compute.  The head is counted once for the span."""
    n = last - first
    if n <= 0:
        return 0
    return (2 * active_matmul_params(s) * n
            + attention_flops(s, first, last)
            + _kinds(s)[0] * _conv_elementwise(s) * n
            + 2 * s["hidden_size"] * s["vocab_size"])


def row_bytes(s):
    """What the cache keeps of one token in one attention layer,
    bfloat16: every key-value head's ``K | V``."""
    return s["num_key_value_heads"] * 2 * _head_dim(s) * BF16


def state_bytes_a_slot(s):
    """What a sequence keeps through all the conv layers: its last
    ``conv_L_cache - 1`` rows of ``u``, float32."""
    return _kinds(s)[0] * (s["conv_L_cache"] - 1) * s["hidden_size"] * F32


# -- the kernels ----------------------------------------------------------------

def short_conv_flops_bytes(s, tokens, row_visits):
    """The conv layers' ``_short_conv_kernel`` work: ``tokens`` fed, each
    read as ``B | C | x`` (bfloat16), gated out (bfloat16) and its ``u``
    written to the state (float32) once; ``row_visits`` the launch rows
    that were live, summed over launches, each of which reads the
    ``conv_L_cache - 1`` rows of ``u`` before its first (float32)."""
    n_conv = _kinds(s)[0]
    w, keep = s["hidden_size"], s["conv_L_cache"] - 1
    flops = n_conv * _conv_elementwise(s) * tokens
    nbytes = n_conv * w * (tokens * (3 * BF16 + BF16 + F32)
                           + row_visits * keep * F32)
    return flops, nbytes


def attention_flops_bytes(s, spans, live_tokens):
    """The attention layers' attention for the tokens materialised:
    ``spans`` the ``(first, last)`` positions each sequence advanced
    over; ``live_tokens`` the cached tokens of the sequences each decode
    or verify launch served, summed over launches, whose rows such a
    launch has to read (a chunk's reads are not counted: the operations
    bound it)."""
    flops = sum(attention_flops(s, a, b) for a, b in spans)
    return flops, _kinds(s)[1] * live_tokens * row_bytes(s)


def experts_touched(s, rows):
    """The experts that ``rows`` tokens choose among, in expectation
    over an even router: ``E (1 - (1 - k / E)^rows)``."""
    e, k = s["num_experts"], s["num_experts_per_tok"]
    return e * (1.0 - (1.0 - k / e) ** rows)


def moe_gmm_flops_bytes(s, tokens, launch_rows):
    """The routed experts' work: ``tokens`` materialised, each through
    the ``num_experts_per_tok`` it chose in every expert layer; every
    launch has to read the weights of the experts its rows chose
    (``launch_rows``: the token rows of each launch), all 64 for a chunk
    of 256 and nearly all for a decode launch of 64 slots."""
    n_expert = s["num_hidden_layers"] - s["num_dense_layers"]
    expert = _gated(s["hidden_size"], s["moe_intermediate_size"])
    flops = 2 * tokens * s["num_experts_per_tok"] * expert * n_expert
    nbytes = n_expert * expert * BF16 * sum(
        experts_touched(s, r) for r in launch_rows)
    return flops, nbytes
