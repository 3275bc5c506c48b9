"""Operations and bytes that the ``deepseek_v3`` decoder's work needs,
from the shapes alone: the counts of the reference beside this file
(``deepseek_v3.py``).

Everything here follows the published, EXPANDED equations and the tokens
that were materialised, never what the program happens to do: the keys
and values of a head are 192 and 128 wide (the absorbed product the
serving kernel computes costs 3.4 times that for each key and is not
counted), a token goes through ``num_experts_per_tok`` routed experts
and the shared ones (no padding row, no expert read for nothing), and
the head is counted once for a span of tokens, since only a row that is
sampled needs its logits.  A later PR that changes a kernel must not
change a count.  A multiply-add is two operations.  ``sizes`` is a
configuration's file.
"""

BF16 = 2


def _attention_params(s):
    h, nh = s["hidden_size"], s["num_attention_heads"]
    return (h * nh * (s["qk_nope_head_dim"] + s["qk_rope_head_dim"])
            + h * (s["kv_lora_rank"] + s["qk_rope_head_dim"])
            + s["kv_lora_rank"] * nh * (s["qk_nope_head_dim"]
                                        + s["v_head_dim"])
            + nh * s["v_head_dim"] * h)


def _gated(h, width):
    return 3 * h * width


def params_by_part(s):
    """Parameters of each part, the norms' weights left out (65 thousand
    in all): one layer's attention, the dense feed-forward, one routed
    expert, the shared experts with the router and its bias, an expert
    layer whole, embedding and head, and the whole model at
    ``num_hidden_layers`` layers."""
    h, e, f = s["hidden_size"], s["n_routed_experts"], \
        s["moe_intermediate_size"]
    attention = _attention_params(s)
    dense = _gated(h, s["intermediate_size"])
    expert = _gated(h, f)
    shared_router = _gated(h, s["n_shared_experts"] * f) + h * e + e
    expert_layer = attention + e * expert + shared_router
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


def active_matmul_params(s):
    """Parameters in the matrix products one token goes through, all
    layers, the head left out: attention, the dense feed-forward or
    ``num_experts_per_tok`` routed experts beside the shared ones and
    the router."""
    p = params_by_part(s)
    n_dense = s["first_k_dense_replace"]
    n_expert = s["num_hidden_layers"] - n_dense
    routed = s["num_experts_per_tok"] * p["expert"]
    return (s["num_hidden_layers"] * p["attention"]
            + n_dense * p["dense_ff"]
            + n_expert * (routed + p["shared_and_router"]
                          - s["n_routed_experts"]))


def _attention_per_key(s):
    """Operations of one query token against one key, one layer: every
    head's score over ``qk_nope + qk_rope`` values and its value row."""
    return 2 * s["num_attention_heads"] * (
        s["qk_nope_head_dim"] + s["qk_rope_head_dim"] + s["v_head_dim"])


def _keys(first, last):
    """Keys the tokens at positions ``first .. last - 1`` attend, each
    itself and all before it: the sum of ``p + 1``."""
    return (first + 1 + last) * (last - first) // 2


def forward_flops_at(s, first, last):
    """Forward operations for the tokens at positions ``first`` ..
    ``last - 1`` of one sequence: what prefill chunks and decode steps
    have to compute.  The head is counted once for the span: a chunk
    samples its last row at most, and a count may fall short of the
    work (a decode step samples every row) but never pass it."""
    n = last - first
    if n <= 0:
        return 0
    return (2 * active_matmul_params(s) * n
            + s["num_hidden_layers"] * _attention_per_key(s)
            * _keys(first, last)
            + 2 * s["hidden_size"] * s["vocab_size"])


def cache_bytes_per_token(s, stored=False):
    """What the cache keeps of one token over all layers in bfloat16:
    ``kv_lora_rank + qk_rope_head_dim`` values a layer; ``stored``: as
    the pool lays it out, in whole 128-lane tiles."""
    width = s["kv_lora_rank"] + s["qk_rope_head_dim"]
    if stored:
        width = -(-width // 128) * 128
    return s["num_hidden_layers"] * width * BF16


# -- the two kernels ----------------------------------------------------------

def latent_attention_flops_bytes(s, spans, decode_live_tokens):
    """Attention's work for the tokens materialised: ``spans`` the
    ``(first, last)`` positions each sequence advanced over;
    ``decode_live_tokens`` the cached tokens of the sequences each
    decode launch served, summed over launches, whose rows such a launch
    has to read (a chunk's reads are not counted: the operations bound
    it)."""
    flops = sum(s["num_hidden_layers"] * _attention_per_key(s)
                * _keys(a, b) for a, b in spans if b > a)
    return flops, decode_live_tokens * cache_bytes_per_token(s)


def moe_gmm_flops_bytes(s, tokens, chunk_launches, step_launches):
    """The routed experts' work: ``tokens`` materialised, each through
    ``num_experts_per_tok`` experts of every expert layer; a chunk
    launch has to read every expert's weights (256 tokens choose 1,536
    times among 128), a decode or verify launch at least the
    ``num_experts_per_tok`` one token chooses."""
    n_expert = s["num_hidden_layers"] - s["first_k_dense_replace"]
    expert = _gated(s["hidden_size"], s["moe_intermediate_size"])
    flops = 2 * tokens * s["num_experts_per_tok"] * expert * n_expert
    nbytes = n_expert * expert * BF16 * (
        chunk_launches * s["n_routed_experts"]
        + step_launches * s["num_experts_per_tok"])
    return flops, nbytes
