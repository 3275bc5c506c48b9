"""The plain float32 reference of ``lfm2-24b-a2b``: the equations of the
``lfm2_moe`` model type (``benchmarks/reference/lfm2_moe.py``) at the
sizes of ``lfm2-24b-a2b.json``: 9 of the 40 layers, every expert, head
and vocabulary row.  Every token the timed server emitted for a sample of
its finished greedy requests is judged by one full forward pass of these
functions over the prompt and the served tokens: how far the served
token's logit lies below the reference's best.  ``harness/compare.py``
holds the comparison and the configuration's ``limits`` the limit.  The
counts of that work (operations, bytes, parameters) that the per-layer
readers divide by come from the same place,
``reference/lfm2_moe_counts.py``."""

from benchmarks.reference.lfm2_moe import (  # noqa: F401
    logit_and_margin_at, logit_at, logits, longest_row, mass_above,
    param_table, stacked, token_gaps, vocab, weight_std)
from benchmarks.reference.lfm2_moe_counts import (  # noqa: F401
    active_matmul_params, attention_flops, attention_flops_bytes,
    experts_touched, forward_flops_at, moe_gmm_flops_bytes, params_by_part,
    row_bytes, short_conv_flops_bytes, state_bytes_a_slot, total_params)
