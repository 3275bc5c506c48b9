"""The plain float32 reference of ``gpt2-medium``: GPT-2's published
equations (``benchmarks/reference/gpt2.py``) at the sizes of
``gpt2-medium.json``, trained with Adam.  What the timed step produced
in its first three steps (each loss, the first gradient, the change of
the parameters) is compared with what these functions give from the
same weights and batches; ``harness/compare.py`` holds the comparison
and the configuration's ``limits`` the limits."""

from benchmarks.reference.gpt2 import (  # noqa: F401
    logit_at, logits, mass_above, next_token_loss, param_table, stacked,
    token_gaps, train_steps, unstacked_leaf_norms)
