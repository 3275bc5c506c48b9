"""Operations and bytes that GPT-2's work needs, from the shapes alone:
the counts of the reference beside this file (``gpt2.py``).

These count what the algorithm has to do, whatever implements it: no
recomputation, no padding, no copies the program happens to make.  A
multiply-add is two operations.  ``sizes`` is a configuration's file.
A configuration's ``<name>.reference.py`` exports them with its
equations, and the readers (``harness/readers.py``) reach them through
the cell's reference: another family brings its own counts under the
same names and the metric files stay one line each.
"""


def matmul_params(sizes):
    """Parameters that sit in matrix products applied to every token:
    the four attention projections and the two MLP matrices of each
    layer, and the output head (tied to the token embedding).  The
    embedding lookups are gathers and cost no multiply-adds."""
    h = sizes["n_embd"]
    inner = sizes["n_inner"] or 4 * h
    return sizes["n_layer"] * (4 * h * h + 2 * h * inner) \
        + sizes["vocab_size"] * h


def total_params(sizes):
    h = sizes["n_embd"]
    inner = sizes["n_inner"] or 4 * h
    per_layer = 4 * h * h + 4 * h + 2 * h * inner + inner + h + 4 * h
    return (sizes["n_layer"] * per_layer + 2 * h
            + (sizes["vocab_size"] + sizes["n_positions"]) * h)


def attention_flops_causal(sizes, seq):
    """Forward operations of causal attention over one sequence of
    ``seq`` tokens, all layers: QK^T and PV over the lower triangle,
    2 * 2 * head_dim * heads = 4h operations per (query, key) pair."""
    pairs = seq * (seq + 1) // 2
    return sizes["n_layer"] * 4 * sizes["n_embd"] * pairs


def forward_flops_at(sizes, first, last):
    """Forward operations for the tokens at positions ``first`` ..
    ``last - 1`` of one sequence (each attends itself and all before
    it): what prefill chunks and decode steps have to compute."""
    n = last - first
    if n <= 0:
        return 0
    keys = (first + 1 + last) * n // 2          # sum of (p + 1)
    return 2 * matmul_params(sizes) * n \
        + sizes["n_layer"] * 4 * sizes["n_embd"] * keys


def train_flops_per_sequence(sizes, seq):
    """Forward plus backward (twice the forward) for one sequence."""
    return 3 * (2 * matmul_params(sizes) * seq
                + attention_flops_causal(sizes, seq))


def flash_train_flops_bytes(sizes, batch, seq):
    """Causal attention forward and backward for one step at (batch,
    seq): the forward's two products and the backward's four (dV, dP,
    dQ, dK).  The scores a fused backward recomputes are not counted,
    as in ``train_flops_per_sequence``.  Bytes: q, k, v read and o
    written forward; q, k, v, o, do read and dq, dk, dv written
    backward, all bfloat16."""
    flops = 3 * attention_flops_causal(sizes, seq) * batch
    elems = sizes["n_layer"] * batch * seq * sizes["n_embd"]
    return flops, (4 + 8) * elems * 2


def adam_bytes(sizes):
    """What one Adam step has to move: read the float32 parameter,
    gradient and two moments, write the parameter and two moments."""
    return total_params(sizes) * 4 * 7


def decode_attention_bytes(sizes, live_tokens):
    """Keys and values (bfloat16) of ``live_tokens`` cached positions,
    all layers, read once."""
    return live_tokens * sizes["n_layer"] * 2 * sizes["n_embd"] * 2
