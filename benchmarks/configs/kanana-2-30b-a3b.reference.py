"""The plain float32 reference of ``kanana-2-30b-a3b``: the published
equations of the ``deepseek_v3`` model type
(``benchmarks/reference/deepseek_v3.py``) at the sizes of
``kanana-2-30b-a3b.json``.  Every token the timed server emitted for a
sample of its finished greedy requests is judged by one full forward
pass of these functions over the prompt and the served tokens: how far
the served token's logit lies below the reference's best.
``harness/compare.py`` holds the comparison and the configuration's
``limits`` the limit.  The counts of that work (operations, bytes,
parameters) that the per-layer readers divide by come from the same
place, ``reference/deepseek_v3_counts.py``."""

from benchmarks.reference.deepseek_v3 import (  # noqa: F401
    logit_at, logits, longest_row, mass_above, param_table, stacked,
    token_gaps, vocab, weight_std)
from benchmarks.reference.deepseek_v3_counts import (  # noqa: F401
    active_matmul_params, cache_bytes_per_token, forward_flops_at,
    latent_attention_flops_bytes, moe_gmm_flops_bytes, params_by_part,
    total_params)
