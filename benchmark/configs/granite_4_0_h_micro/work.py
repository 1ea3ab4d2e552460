"""Operations and bytes one training step of the ``granite_4_0_h_micro``
configuration needs, from shapes.

Lower bounds on both, so a roofline share built on them cannot pass 100%.
``step_work``: every matrix of the model counted once forward and twice
backward (6 operations a parameter a token), nothing recomputed, no
attention or state-space arithmetic, no normalisation or activation; of the
bytes only what no schedule can avoid — the batch read once, and the
optimizer's pass over the parameters (read parameter, gradient, both
moments; write parameter and both moments), all float32.  ``scan_work``: the
selective state-space recurrence alone, as its equations have it, whatever
implements it.
"""

from __future__ import annotations


def layer_types(config: dict) -> list:
    return list(config["layer_types"][:config["num_hidden_layers"]])


def matmul_parameters(config: dict) -> int:
    """Entries of the matrices a token is multiplied by: the projections of
    every mixer, the three matrices of every feed-forward, the tied head
    (the embedding's lookup is no product)."""
    d, f = config["hidden_size"], config["shared_intermediate_size"]
    d_inner = config["mamba_n_heads"] * config["mamba_d_head"]
    in_proj = d * (2 * d_inner + 2 * config["mamba_n_groups"]
                   * config["mamba_d_state"] + config["mamba_n_heads"])
    kv = config["num_key_value_heads"] * (d // config["num_attention_heads"])
    per_kind = {"mamba": in_proj + d_inner * d,
                "attention": 2 * d * d + 2 * d * kv}
    return (sum(per_kind[k] + 3 * d * f for k in layer_types(config))
            + config["vocab_size"] * d)


def step_work(config: dict, batch: int) -> dict:
    tokens = batch * config["seq_len"]
    return {
        "flops": 6 * matmul_parameters(config) * tokens,
        "bytes": 2 * 4 * tokens + 7 * 4 * config["parameters"],
        "examples": batch,
    }


def scan_work(config: dict, batch: int) -> dict:
    """The recurrence of every state-space layer over one step's tokens,
    forward and backward, nothing recomputed.

    Operations a token a head, state of P x N: forward ``S = a S + (dt x)
    B^T`` (3 P N) and ``y = S C`` (2 P N); backward at least twice that
    (the state's gradient runs the recurrence in reverse, and x, B, C and
    the decay each take a product of the state's size): 15 P N in all.
    Bytes a token: each operand moved once — forward reads x, B, C, dt and
    writes y; backward reads them and dy again and writes dx, dB, dC, ddt —
    activations at the configuration's ``dtype``, the step sizes float32.
    """
    heads, p = config["mamba_n_heads"], config["mamba_d_head"]
    n, groups = config["mamba_d_state"], config["mamba_n_groups"]
    layers = layer_types(config).count("mamba")
    tokens = batch * config["seq_len"]
    act = {"bfloat16": 2, "float32": 4}[config["dtype"]]
    per_token = (5 * heads * p + 6 * groups * n) * act + 3 * heads * 4
    return {"flops": 15 * heads * p * n * tokens * layers,
            "bytes": per_token * tokens * layers}
