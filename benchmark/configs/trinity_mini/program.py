"""The ``trinity_mini`` configuration on the program's side: how the
benchmark builds the system under test for it.  Handing it the seeded
weights a leaf at a time, reading back what the output check compares and
parsing a packed row are what the other packed-row language models'
configurations do, leaf names and all (flat dicts, ``/`` for ``_``), and are
taken from there.  Everything the reference must not touch lives here; the
reference lives next door and imports none of this.
"""

from __future__ import annotations

from benchmark.configs.granite_4_0_h_micro.program import (  # noqa: F401
    first_gradient_norms, host_batch, load_weights, parameters, program_name,
    tfrecord_parse_fn)


def model_config(config: dict):
    """The zoo's ``Config`` of the configuration's file: the published
    widths, layer types, window, rotation and router, the layers run and
    which of them are dense, the experts held, the vocabulary's slice."""
    from tensorflowonspark_tpu.models import afmoe

    published = config["published"]
    if len(config["experts_held"]) != config["num_experts"]:
        raise ValueError("num_experts counts the experts held here")
    if len(config["layers_run"]) != config["num_hidden_layers"]:
        raise ValueError("num_hidden_layers counts the layers run")
    if config["num_dense_layers"] != sum(
            at < published["num_dense_layers"]
            for at in config["layers_run"]):
        raise ValueError("num_dense_layers counts the dense layers run")
    if (config["tie_word_embeddings"] or config["hidden_act"] != "silu"
            or config["rope_scaling"] is not None
            or config["gate_sum_eps"] != afmoe.GATE_SUM_EPS
            or (config["n_group"], config["topk_group"],
                config["num_expert_groups"],
                config["num_limited_groups"]) != (1, 1, 1, 1)):
        raise ValueError("an untied head, SiLU, plain RoPE, one group of "
                         "experts, 1e-20 under the chosen scores' sum")
    return afmoe.Config(
        vocab_size=config["vocab_size"], hidden_size=config["hidden_size"],
        head_dim=config["head_dim"],
        layer_types=tuple(config["layer_types"]),
        layers_run=tuple(config["layers_run"]),
        num_dense_layers=published["num_dense_layers"],
        sliding_window=config["sliding_window"],
        rope_theta=config["rope_theta"],
        intermediate_size=config["intermediate_size"],
        moe_intermediate_size=config["moe_intermediate_size"],
        num_experts=published["num_experts"],
        experts_held=tuple(config["experts_held"]),
        num_experts_per_tok=config["num_experts_per_tok"],
        num_shared_experts=config["num_shared_experts"],
        score_func=config["score_func"], route_norm=config["route_norm"],
        route_scale=config["route_scale"],
        load_balance_coeff=config["load_balance_coeff"],
        mup_enabled=config["mup_enabled"],
        num_attention_heads=config["num_attention_heads"],
        num_key_value_heads=config["num_key_value_heads"],
        rms_norm_eps=config["rms_norm_eps"], init_std=config["init_std"],
        post_norm_init=config["post_norm_init"], dtype=config["dtype"],
        seq_len=config["seq_len"])


def build(config: dict, ctx=None):
    """The Trainer a user's ``map_fun`` builds for this model."""
    from tensorflowonspark_tpu.models import afmoe
    from tensorflowonspark_tpu.trainer import Trainer

    opt = config["optimizer"]
    recipe = dict(afmoe.ADAMW, name="adamw",
                  learning_rate=opt["learning_rate"])
    if opt != recipe:
        raise ValueError(f"the program's AdamW is {recipe}, the "
                         f"configuration's file says {opt}")
    return Trainer(config["program_model"], config=model_config(config),
                   learning_rate=opt["learning_rate"],
                   error_sink=getattr(ctx, "report_error", None))
