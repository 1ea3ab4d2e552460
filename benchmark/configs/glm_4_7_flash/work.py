"""Operations and bytes one training step of the ``glm_4_7_flash``
configuration needs, from shapes.

Lower bounds on both, so a roofline share built on them cannot pass 100%.
``step_work``: every matrix a token surely meets counted once forward and
twice backward (6 operations a parameter a token) — latent attention's five
projections, the dense feed-forward, the shared experts, the routers, the
prediction module's ``eh_proj``, the head twice (both losses) — and **the
routed experts and attention's scores counted at zero**: how many slots land
on the held experts and how long the documents are is the data's, and the
bound holds whatever they are.  Nothing recomputed, no normalisation or
activation.  Of the bytes only what no schedule can avoid: the batch read
once, and the optimizer's pass over the parameters (read parameter,
gradient, both moments; write parameter and both moments), all float32.
``experts_work``: the routed experts' grouped products alone for a given
number of slots, whatever implements them.
"""

from __future__ import annotations


def attention_parameters(config: dict) -> int:
    """Entries of latent attention's five projections in one layer."""
    d, heads = config["hidden_size"], config["num_attention_heads"]
    qk = config["qk_nope_head_dim"] + config["qk_rope_head_dim"]
    return (d * config["q_lora_rank"] + config["q_lora_rank"] * heads * qk
            + d * (config["kv_lora_rank"] + config["qk_rope_head_dim"])
            + config["kv_lora_rank"] * heads * (config["qk_nope_head_dim"]
                                                + config["v_head_dim"])
            + heads * config["v_head_dim"] * d)


def expert_parameters(config: dict) -> int:
    """Entries of one expert's three matrices."""
    return 3 * config["hidden_size"] * config["moe_intermediate_size"]


def expert_layers(config: dict) -> int:
    """Layers with a router, the prediction module's among them."""
    return (config["num_hidden_layers"] - config["first_k_dense_replace"]
            + config["num_nextn_predict_layers"])


def matmul_parameters(config: dict) -> int:
    """Entries of the matrices every token is multiplied by (the
    embedding's lookup is no product; a routed expert is not sure)."""
    d = config["hidden_size"]
    dense = config["first_k_dense_replace"]
    routers = d * config["published"]["n_routed_experts"]
    return ((dense + expert_layers(config)) * attention_parameters(config)
            + dense * 3 * d * config["intermediate_size"]
            + expert_layers(config) * (
                config["n_shared_experts"] * expert_parameters(config)
                + routers)
            + config["num_nextn_predict_layers"] * 2 * d * d
            + (1 + config["num_nextn_predict_layers"])
            * config["vocab_size"] * d)


def step_work(config: dict, batch: int) -> dict:
    tokens = batch * config["seq_len"]
    return {
        "flops": 6 * matmul_parameters(config) * tokens,
        "bytes": 2 * 4 * tokens + 7 * 4 * config["parameters"],
        "examples": batch,
    }


def experts_work(config: dict, slots: float) -> dict:
    """The routed experts' grouped products of every expert layer for
    ``slots`` slots (a token's choice of a held expert) a step, forward and
    backward, nothing recomputed: 6 operations an entry of an expert's
    three matrices a slot; of the bytes, the held experts' float32 weights
    read twice (forward and backward) and their gradient written once."""
    held = len(config["experts_held"]) * expert_parameters(config)
    return {"flops": 6 * expert_parameters(config) * slots,
            "bytes": 3 * 4 * held * expert_layers(config)}
