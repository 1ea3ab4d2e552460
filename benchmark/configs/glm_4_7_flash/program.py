"""The ``glm_4_7_flash`` configuration on the program's side: how the
benchmark builds the system under test for it.  Handing it the seeded
weights a leaf at a time, reading back what the output check compares and
parsing a packed row are what the other packed-row language model's
configuration does, leaf names and all (flat dicts, ``/`` for ``_``; the
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
    widths and router, the layers run, the experts held, the vocabulary's
    slice."""
    from tensorflowonspark_tpu.models import mla_moe

    if len(config["experts_held"]) != config["n_routed_experts"]:
        raise ValueError("n_routed_experts counts the experts held here")
    return mla_moe.Config(
        vocab_size=config["vocab_size"], hidden_size=config["hidden_size"],
        num_hidden_layers=config["num_hidden_layers"],
        first_k_dense_replace=config["first_k_dense_replace"],
        intermediate_size=config["intermediate_size"],
        moe_intermediate_size=config["moe_intermediate_size"],
        n_routed_experts=config["published"]["n_routed_experts"],
        experts_held=tuple(config["experts_held"]),
        n_shared_experts=config["n_shared_experts"],
        num_experts_per_tok=config["num_experts_per_tok"],
        routed_scaling_factor=config["routed_scaling_factor"],
        norm_topk_prob=config["norm_topk_prob"],
        num_attention_heads=config["num_attention_heads"],
        q_lora_rank=config["q_lora_rank"],
        kv_lora_rank=config["kv_lora_rank"],
        qk_nope_head_dim=config["qk_nope_head_dim"],
        qk_rope_head_dim=config["qk_rope_head_dim"],
        v_head_dim=config["v_head_dim"], rope_theta=config["rope_theta"],
        rms_norm_eps=config["rms_norm_eps"],
        num_nextn_predict_layers=config["num_nextn_predict_layers"],
        mtp_loss_weight=config["mtp_loss_weight"],
        bias_update_speed=config["bias_update_speed"],
        init_std=config["init_std"],
        dtype=config["dtype"], seq_len=config["seq_len"])


def build(config: dict, ctx=None):
    """The Trainer a user's ``map_fun`` builds for this model."""
    from tensorflowonspark_tpu.models import mla_moe
    from tensorflowonspark_tpu.trainer import Trainer

    opt = config["optimizer"]
    recipe = dict(mla_moe.ADAMW, name="adamw",
                  learning_rate=opt["learning_rate"])
    if opt != recipe:
        raise ValueError(f"the program's AdamW is {recipe}, the "
                         f"configuration's file says {opt}")
    return Trainer(config["program_model"], config=model_config(config),
                   learning_rate=opt["learning_rate"],
                   error_sink=getattr(ctx, "report_error", None))

