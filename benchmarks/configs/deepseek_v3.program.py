"""How the program's own configuration object is made for
configurations of the ``deepseek_v3`` model type
(``kanana-2-30b-a3b.json``): the one function a configuration's
``"program"`` file holds.  It maps the configuration's own keys onto the
program's class and hands the program no option.  A key this family's
class cannot express (query compression, several groups, rope scaling,
another scoring function) stops the run here, before anything is
measured under a name it does not deserve."""

_ONLY = {"model_type": "deepseek_v3", "q_lora_rank": None, "n_group": 1,
         "topk_group": 1, "rope_scaling": None, "scoring_func": "sigmoid",
         "topk_method": "noaux_tc", "moe_layer_freq": 1,
         "attention_bias": False, "hidden_act": "silu",
         "tie_word_embeddings": False}
_KEYS = ("vocab_size", "hidden_size", "num_hidden_layers",
         "num_attention_heads", "intermediate_size", "moe_intermediate_size",
         "n_routed_experts", "n_shared_experts", "num_experts_per_tok",
         "first_k_dense_replace", "kv_lora_rank", "qk_nope_head_dim",
         "qk_rope_head_dim", "v_head_dim", "rope_theta", "rope_interleave",
         "rms_norm_eps", "routed_scaling_factor", "norm_topk_prob",
         "max_position_embeddings")


def model_config(models, sizes):
    """The program's ``DeepseekV3Config`` at a ``deepseek_v3``
    ``config.json``'s sizes."""
    other = {k: sizes.get(k) for k, v in _ONLY.items() if sizes.get(k) != v}
    if other:
        raise SystemExit(f"{sizes.get('name')}: the program's deepseek_v3 "
                         f"family cannot express {other}")
    if sizes["num_key_value_heads"] != sizes["num_attention_heads"] \
            or sizes["qk_head_dim"] != sizes["qk_nope_head_dim"] \
            + sizes["qk_rope_head_dim"]:
        raise SystemExit(f"{sizes.get('name')}: head sizes disagree")
    return models.DeepseekV3Config(
        initializer_range=sizes["assumed"]["initializer_range"],
        **{k: sizes[k] for k in _KEYS})
