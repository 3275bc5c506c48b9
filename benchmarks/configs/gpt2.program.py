"""How the program's own configuration object is made for the GPT-2
configurations (``gpt2-medium.json``, ``gpt2-xl.json``): the one
function a configuration's ``"program"`` file holds.  It maps the
configuration's own keys onto the program's class and hands the program
no option: the runners make the same two calls for every configuration,
``chip_smoke.build_trainer(cfg, mesh, lr=...)`` and
``serving.InferenceServer(cfg, params, max_batch_size=...,
max_context=...)``."""


def model_config(models, sizes):
    """The program's ``GPTConfig`` at a GPT-2 ``config.json``'s sizes."""
    return models.GPTConfig(
        vocab_size=sizes["vocab_size"], hidden_size=sizes["n_embd"],
        num_hidden_layers=sizes["n_layer"],
        num_attention_heads=sizes["n_head"],
        intermediate_size=sizes["n_inner"] or 4 * sizes["n_embd"],
        max_position_embeddings=sizes["n_positions"],
        hidden_dropout_prob=sizes["resid_pdrop"],
        attention_probs_dropout_prob=sizes["attn_pdrop"],
        layer_norm_eps=sizes["layer_norm_epsilon"],
        initializer_range=sizes["initializer_range"])
