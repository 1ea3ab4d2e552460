"""The ``kimi_linear_48b_a3b`` configuration on the program's side: how the
benchmark builds the system under test for it.  Handing it the seeded
weights a leaf at a time, reading back what the output check compares and
parsing a packed row are what the other packed-row language models'
configurations do, leaf names and all (flat dicts, ``/`` for ``_``; the
routing biases start at zero on both sides), and are taken from there.
Everything the reference must not touch lives here; the reference lives next
door and imports none of this.
"""

from __future__ import annotations

from benchmark.configs.granite_4_0_h_micro.program import (  # noqa: F401
    first_gradient_norms, host_batch, load_weights, parameters, program_name,
    tfrecord_parse_fn)


def model_config(config: dict):
    """The zoo's ``Config`` of the configuration's file: the published
    widths, lists of layers and router, the layers run, the experts held,
    the vocabulary's slice."""
    from tensorflowonspark_tpu.models import kimi_linear

    if len(config["experts_held"]) != config["num_experts"]:
        raise ValueError("num_experts counts the experts held here")
    if config["q_lora_rank"] is not None or not config["mla_use_nope"]:
        raise ValueError("the layout has no query latent and no rotation")
    if (config["num_nextn_predict_layers"] or config["tie_word_embeddings"]
            or config["moe_layer_freq"] != 1
            or (config["num_expert_group"], config["topk_group"]) != (1, 1)
            or config["moe_router_activation_func"] != "sigmoid"):
        raise ValueError("no prediction module, an untied head, experts in "
                         "every layer behind the dense ones, one group of "
                         "sigmoid scores")
    linear = config["linear_attn_config"]
    return kimi_linear.Config(
        vocab_size=config["vocab_size"], hidden_size=config["hidden_size"],
        num_hidden_layers=config["num_hidden_layers"],
        kda_layers=tuple(linear["kda_layers"]),
        full_attn_layers=tuple(linear["full_attn_layers"]),
        kda_num_heads=linear["num_heads"], kda_head_dim=linear["head_dim"],
        short_conv_kernel_size=linear["short_conv_kernel_size"],
        first_k_dense_replace=config["first_k_dense_replace"],
        intermediate_size=config["intermediate_size"],
        moe_intermediate_size=config["moe_intermediate_size"],
        num_experts=config["published"]["num_experts"],
        experts_held=tuple(config["experts_held"]),
        num_shared_experts=config["num_shared_experts"],
        num_experts_per_token=config["num_experts_per_token"],
        routed_scaling_factor=config["routed_scaling_factor"],
        moe_renormalize=config["moe_renormalize"],
        num_attention_heads=config["num_attention_heads"],
        kv_lora_rank=config["kv_lora_rank"],
        qk_nope_head_dim=config["qk_nope_head_dim"],
        qk_rope_head_dim=config["qk_rope_head_dim"],
        v_head_dim=config["v_head_dim"], rms_norm_eps=config["rms_norm_eps"],
        bias_update_speed=config["bias_update_speed"],
        init_std=config["init_std"], dtype=config["dtype"],
        seq_len=config["seq_len"], kda_chunk=config["kda_chunk"])


def build(config: dict, ctx=None):
    """The Trainer a user's ``map_fun`` builds for this model."""
    from tensorflowonspark_tpu.models import kimi_linear
    from tensorflowonspark_tpu.trainer import Trainer

    opt = config["optimizer"]
    recipe = dict(kimi_linear.ADAMW, name="adamw",
                  learning_rate=opt["learning_rate"])
    if opt != recipe:
        raise ValueError(f"the program's AdamW is {recipe}, the "
                         f"configuration's file says {opt}")
    return Trainer(config["program_model"], config=model_config(config),
                   learning_rate=opt["learning_rate"],
                   error_sink=getattr(ctx, "report_error", None))
