"""How the program's own configuration object is made for
configurations of the ``exaone_moe`` model type
(``k-exaone-236b-a23b.json``): the one function a configuration's
``"program"`` file holds.  It maps the configuration's own keys onto the
program's class and hands the program no option.  The router's width is
the PUBLISHED count of experts and the experts held the configuration's
own ``num_experts`` (``reduced``), from ``experts_held_first`` on.  A key
this family's class cannot express (several groups, another scoring
function, a rope scaling, layers whose feed-forward is not dense first
and sparse after, a prediction module) stops the run here, before
anything is measured under a name it does not deserve."""

_ONLY = {"model_type": "exaone_moe", "n_group": 1, "topk_group": 1,
         "scoring_func": "sigmoid", "hidden_act": "silu",
         "tie_word_embeddings": False, "num_nextn_predict_layers": 0}
_KEYS = ("vocab_size", "hidden_size", "num_hidden_layers",
         "num_attention_heads", "num_key_value_heads", "head_dim",
         "intermediate_size", "moe_intermediate_size", "num_shared_experts",
         "num_experts_per_tok", "first_k_dense_replace", "sliding_window",
         "sliding_window_pattern", "rms_norm_eps", "routed_scaling_factor",
         "norm_topk_prob", "max_position_embeddings")


def model_config(models, sizes):
    """The program's ``ExaoneMoeConfig`` at an ``exaone_moe``
    ``config.json``'s sizes, cut as the file's ``reduced`` says."""
    name = sizes.get("name")
    if not hasattr(models, "ExaoneMoeConfig"):
        raise SystemExit(f"{name}: this checkout's apex_tpu.models has no "
                         "exaone_moe family")
    other = {k: sizes.get(k) for k, v in _ONLY.items() if sizes.get(k) != v}
    if sizes.get("rope_parameters", {}).get("rope_type") != "default":
        other["rope_parameters"] = sizes.get("rope_parameters")
    if other:
        raise SystemExit(f"{name}: the program's exaone_moe family cannot "
                         f"express {other}")
    n, dense = sizes["num_hidden_layers"], sizes["first_k_dense_replace"]
    kinds = tuple(sizes["layer_types"][:n])
    windows = sizes["sliding_windows"][:n]
    if list(sizes["mlp_layer_types"][:n]) != ["dense"] * dense \
            + ["sparse"] * (n - dense) \
            or any((w == sizes["sliding_window"])
                   != (k == "sliding_attention")
                   for k, w in zip(kinds, windows)):
        raise SystemExit(f"{name}: layer_types, sliding_windows and "
                         "mlp_layer_types disagree with sliding_window and "
                         "first_k_dense_replace")
    width = sizes.get("published", {}).get("num_experts",
                                           sizes["num_experts"])
    return models.ExaoneMoeConfig(
        num_experts=width,
        experts_held=(sizes.get("experts_held_first", 0),
                      sizes["num_experts"]),
        layer_types=kinds,
        rope_theta=float(sizes["rope_parameters"]["rope_theta"]),
        initializer_range=sizes["assumed"]["initializer_range"],
        **{k: sizes[k] for k in _KEYS})
