"""The ``mellum2_12b_a2_5b`` configuration on the program's side: how the
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
    widths, layer types, window, rotations and router, the layers run, the
    experts held, the vocabulary's slice."""
    from tensorflowonspark_tpu.models import mellum_moe

    if len(config["experts_held"]) != config["num_experts"]:
        raise ValueError("num_experts counts the experts held here")
    if len(config["layers_run"]) != config["num_hidden_layers"]:
        raise ValueError("num_hidden_layers counts the layers run")
    if (config["tie_word_embeddings"] or config["attention_bias"]
            or config["hidden_act"] != "silu"
            or set(config["mlp_layer_types"]) != {"sparse"}
            or not config["use_sliding_window"]):
        raise ValueError("an untied head, no bias in attention's "
                         "projections, SiLU, experts in every layer, a "
                         "window in the layers layer_types says")
    return mellum_moe.Config(
        vocab_size=config["vocab_size"], hidden_size=config["hidden_size"],
        head_dim=config["head_dim"],
        layer_types=tuple(config["layer_types"]),
        layers_run=tuple(config["layers_run"]),
        sliding_window=config["sliding_window"],
        rope_parameters=config["rope_parameters"],
        moe_intermediate_size=config["moe_intermediate_size"],
        num_experts=config["published"]["num_experts"],
        experts_held=tuple(config["experts_held"]),
        num_experts_per_tok=config["num_experts_per_tok"],
        norm_topk_prob=config["norm_topk_prob"],
        num_attention_heads=config["num_attention_heads"],
        num_key_value_heads=config["num_key_value_heads"],
        rms_norm_eps=config["rms_norm_eps"], init_std=config["init_std"],
        embed_init_std=config["embed_init_std"], dtype=config["dtype"],
        seq_len=config["seq_len"])


def build(config: dict, ctx=None):
    """The Trainer a user's ``map_fun`` builds for this model."""
    from tensorflowonspark_tpu.models import mellum_moe
    from tensorflowonspark_tpu.trainer import Trainer

    opt = config["optimizer"]
    recipe = dict(mellum_moe.ADAMW, name="adamw",
                  learning_rate=opt["learning_rate"])
    if opt != recipe:
        raise ValueError(f"the program's AdamW is {recipe}, the "
                         f"configuration's file says {opt}")
    return Trainer(config["program_model"], config=model_config(config),
                   learning_rate=opt["learning_rate"],
                   error_sink=getattr(ctx, "report_error", None))
