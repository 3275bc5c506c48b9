"""The plain float32 reference of ``k-exaone-236b-a23b``: the equations
of the ``exaone_moe`` model type (``benchmarks/reference/exaone_moe.py``)
at the sizes of ``k-exaone-236b-a23b.json``, the share of a stated
deployment: 8 layers, the 8 routed experts held of 128 scored, 19,200
rows of the vocabulary.  Every token the timed server emitted for a
sample of its finished greedy requests is judged by one full forward
pass of these functions over the prompt and the served tokens: how far
the served token's logit lies below the reference's best.
``harness/compare.py`` holds the comparison and the configuration's
``limits`` the limit.  The counts of that work (operations, bytes,
parameters) that the per-layer readers divide by come from the same
place, ``reference/exaone_moe_counts.py``."""

from benchmarks.reference.exaone_moe import (  # noqa: F401
    logit_and_margin_at, logit_at, logits, longest_row, mass_above, param_table, stacked,
    token_gaps, vocab, weight_std)
from benchmarks.reference.exaone_moe_counts import (  # noqa: F401
    active_matmul_params, attention_flops, forward_flops_at,
    full_attention_flops_bytes, held_experts_a_token, moe_gmm_flops_bytes,
    params_by_part, row_bytes, total_params, window_attention_flops_bytes)
