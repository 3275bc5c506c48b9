"""The plain float32 reference of ``gpt2-medium``: GPT-2's published
equations (``benchmarks/reference/gpt2.py``) at the sizes of
``gpt2-medium.json``, trained with Adam.  What the timed step produced
in its first three steps (each loss, the first gradient, the change of
the parameters) is compared with what these functions give from the
same weights and batches; ``harness/compare.py`` holds the comparison
and the configuration's ``limits`` the limits.  The counts of that
work (operations, bytes, parameters) that the per-layer readers divide
by come from the same place, ``reference/gpt2_counts.py``."""

from benchmarks.reference.gpt2 import (  # noqa: F401
    logit_at, logits, longest_row, mass_above, next_token_loss, param_table,
    stacked, token_gaps, train_steps, unstacked_leaf_norms, vocab,
    weight_std)
from benchmarks.reference.gpt2_counts import (  # noqa: F401
    adam_bytes, attention_flops_causal, decode_attention_bytes,
    flash_train_flops_bytes, forward_flops_at, matmul_params, total_params,
    train_flops_per_sequence)
