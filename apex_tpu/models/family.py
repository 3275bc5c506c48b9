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
    ``cfg.max_position_embeddings``;
(d) which tokens EACH layer keeps, ``cfg.layer_windows()``: one entry a
    layer, ``None`` for a layer that attends every token before it
    (its rows live in the block table for as long as the sequence
    does) or ``w`` for one whose row at position ``p`` attends the
    positions ``p - w + 1 .. p`` alone (its rows live in a ring the
    slot owns and are let go as they slide out:
    ``serving.kv_cache``).  A family whose layers all keep everything
    need not have the method (:func:`layer_windows`).

A family whose programs carry counters beside the pool (the tokens an
expert layer routed) also has ``cfg.serving_counters()``:
``{name: shape}`` of int32 arrays the engine allocates zeroed and the
model adds to through ``CacheView.count``.

``models.gpt.GPTConfig``, ``models.deepseek.DeepseekV3Config`` and
``models.exaone_moe.ExaoneMoeConfig`` implement it; the engine imports
none of them for it.
"""

from __future__ import annotations

import dataclasses
from typing import Optional, Tuple

LANES = 128


def layer_windows(cfg) -> Tuple[Optional[int], ...]:
    """What each layer of ``cfg`` keeps (the contract's (d)): the
    family's answer, or "every token" for each where it gives none."""
    if hasattr(cfg, "layer_windows"):
        return tuple(cfg.layer_windows())
    return (None,) * cfg.num_hidden_layers


@dataclasses.dataclass(frozen=True)
class CacheRow:
    """One token's row in one layer of the pool: ``groups`` groups of
    ``group_width`` stored values side by side.

    - ``kind`` ``"kv"``: a group is one key-value head's ``K_h | V_h``
      pair (``group_width = 2 * head_dim``), read by
      ``heads_per_group`` query heads: 1 for multi-head attention,
      more where query heads share key-value heads (query head ``i``
      reads group ``i // heads_per_group``); the value is the group's
      upper half.
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
        return self.heads_per_group > 1

    @classmethod
    def kv(cls, num_heads: int, head_dim: int,
           num_kv_heads: Optional[int] = None) -> "CacheRow":
        """Every key-value head's ``K_h`` beside its ``V_h``:
        ``num_heads`` of them for multi-head attention, ``num_kv_heads``
        where ``num_heads // num_kv_heads`` query heads read each."""
        groups = num_kv_heads or num_heads
        if num_heads % groups:
            raise ValueError(f"{num_heads} query heads do not divide "
                             f"over {groups} key-value heads")
        return cls("kv", groups, 2 * head_dim, num_heads // groups,
                   (head_dim, 2 * head_dim), 2 * head_dim)

    @classmethod
    def latent(cls, rank: int, rope_dim: int, heads: int) -> "CacheRow":
        """Latent attention: one ``c (rank) | k_pe (rope_dim)`` row for
        all ``heads``, stored in whole lane tiles."""
        used = rank + rope_dim
        return cls("latent", 1, -(-used // LANES) * LANES, heads,
                   (0, rank), used)
