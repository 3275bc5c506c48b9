"""What a model family tells the serving engine.

``serving.DecodeEngine`` and ``serving.InferenceServer`` take a
configuration object and ask it, not its class name, three things:

(a) ``cfg.build_model(kv_quant=False)``: the flax module to run.  The
    engine's three program bodies (chunk prefill, decode, verify) make
    one call of it, ``model.apply(variables, input_ids, positions=,
    cache_views=view, return_kv=True, deterministic=True) -> (logits,
    view)``: ``view`` is the launch's ``serving.kv_cache.CacheView``,
    through which every layer writes the fed tokens' rows and attends
    (``view.attend``), handed on from layer to layer and returned
    after the last.  The engine never calls the model without a view,
    so a family owes serving no other forward pass
    (``models.gpt.GPTLMHeadModel`` is the pattern);
(b) ``cfg.cache_row()``: a :class:`CacheRow`, what one token keeps in
    one layer of the paged pool;
(c) ``cfg.vocab_size``, ``cfg.num_hidden_layers`` and
    ``cfg.max_position_embeddings``.

A family whose programs carry counters beside the pool (the tokens an
expert layer routed) also has ``cfg.serving_counters()``:
``{name: shape}`` of int32 arrays the engine allocates zeroed and the
model adds to through ``CacheView.count``.

``models.gpt.GPTConfig`` and ``models.deepseek.DeepseekV3Config``
implement it; the engine imports neither for it.
"""

from __future__ import annotations

import dataclasses
from typing import Tuple

LANES = 128


@dataclasses.dataclass(frozen=True)
class CacheRow:
    """One token's row in one layer of the pool: ``groups`` groups of
    ``group_width`` stored values side by side.

    - ``kind`` ``"kv"``: a group is one head's ``K_h | V_h`` pair
      (``group_width = 2 * head_dim``), read by that head alone
      (``heads_per_group`` 1); the value is the group's upper half.
    - ``kind`` ``"latent"``: one group, the compressed key-value row
      ``c | k_pe`` padded to whole 128-lane tiles, shared by all
      ``heads_per_group`` query heads; its first ``value[1]`` values
      are also the value.

    ``value`` is the ``(first, last)`` lane range of a group that is
    its value; ``used`` how many of a group's stored values carry data
    (the rest is lane padding the queries meet with zeros)."""

    kind: str
    groups: int
    group_width: int
    heads_per_group: int
    value: Tuple[int, int]
    used: int

    @property
    def width(self) -> int:
        """Stored values in a row: the pool leaf's minor dimension."""
        return self.groups * self.group_width

    @property
    def heads(self) -> int:
        """Query heads that read the row."""
        return self.groups * self.heads_per_group

    @property
    def shared(self) -> bool:
        """Whether several query heads read one group."""
        return self.kind == "latent"

    @classmethod
    def kv(cls, num_heads: int, head_dim: int) -> "CacheRow":
        """Multi-head attention: every head's ``K_h`` beside its
        ``V_h``."""
        return cls("kv", num_heads, 2 * head_dim, 1,
                   (head_dim, 2 * head_dim), 2 * head_dim)

    @classmethod
    def latent(cls, rank: int, rope_dim: int, heads: int) -> "CacheRow":
        """Latent attention: one ``c (rank) | k_pe (rope_dim)`` row for
        all ``heads``, stored in whole lane tiles."""
        used = rank + rope_dim
        return cls("latent", 1, -(-used // LANES) * LANES, heads,
                   (0, rank), used)
