"""How the program's own configuration object is made for
configurations of the ``lfm2_moe`` model type (``lfm2-24b-a2b.json``):
the one function a configuration's ``"program"`` file holds.  It maps the
configuration's own keys onto the program's class and hands the program
no option.  A key this family's class cannot express (a convolution with
a bias, routing without the selection bias, a rope scaling) stops the
run here, before anything is measured under a name it does not
deserve."""

_ONLY = {"model_type": "lfm2_moe", "conv_bias": False,
         "use_expert_bias": True}
_KEYS = ("vocab_size", "hidden_size", "num_hidden_layers",
         "num_attention_heads", "num_key_value_heads", "intermediate_size",
         "moe_intermediate_size", "num_experts", "num_experts_per_tok",
         "num_dense_layers", "conv_L_cache", "norm_topk_prob", "norm_eps",
         "max_position_embeddings")


def model_config(models, sizes):
    """The program's ``Lfm2MoeConfig`` at an ``lfm2_moe``
    ``config.json``'s sizes, cut as the file's ``reduced`` says."""
    name = sizes.get("name")
    if not hasattr(models, "Lfm2MoeConfig"):
        raise SystemExit(f"{name}: this checkout's apex_tpu.models has no "
                         "lfm2_moe family")
    other = {k: sizes.get(k) for k, v in _ONLY.items() if sizes.get(k) != v}
    if sizes.get("rope_parameters", {}).get("rope_type") != "default":
        other["rope_parameters"] = sizes.get("rope_parameters")
    if other:
        raise SystemExit(f"{name}: the program's lfm2_moe family cannot "
                         f"express {other}")
    return models.Lfm2MoeConfig(
        layer_types=tuple(sizes["layer_types"][:sizes["num_hidden_layers"]]),
        routed_scaling_factor=float(sizes["routed_scaling_factor"]),
        rope_parameters=dict(sizes["rope_parameters"]),
        initializer_range=sizes["assumed"]["initializer_range"],
        **{k: sizes[k] for k in _KEYS})
