"""Operations and bytes one training step of the ``kimi_linear_48b_a3b``
configuration needs, from shapes.

Lower bounds on both, so a roofline share built on them cannot pass 100%.
``step_work``: every matrix a token surely meets counted once forward and
twice backward (6 operations a parameter a token) — the KDA mixers' nine
projections, latent attention's four, the dense feed-forward, the routers,
the shared experts, the untied head — and **the routed experts, attention's
scores and the recurrence's own products counted at zero**: how many slots
land on the held experts and how long the documents are is the data's, the
chunk is the program's, and the bound holds whatever they are.  Nothing
recomputed, no normalisation, gate or activation.  Of the bytes only what no
schedule can avoid: the batch read once, and the optimizer's pass over the
parameters (read parameter, gradient, both moments; write parameter and both
moments), all float32.
``experts_work``: the routed experts' grouped products alone for a given
number of slots, whatever implements them.
``kda_work``: the gated delta rule's recurrence alone in its chunked form at
the configuration's chunk, whatever implements it.
"""

from __future__ import annotations

#: bytes of an activation (``dtype`` bfloat16)
ACTIVATION_BYTES = 2


def mixers(config: dict) -> list:
    """The mixer of every layer run, in forward order (``linear_attn_config``
    numbers the layers from 1)."""
    kda = config["linear_attn_config"]["kda_layers"]
    return ["kda" if i + 1 in kda else "full_attention"
            for i in range(config["num_hidden_layers"])]


def kda_parameters(config: dict) -> int:
    """Entries of a KDA mixer's nine projections (q, k, v, the decay's two,
    beta's, the gate's two, the output's)."""
    d, linear = config["hidden_size"], config["linear_attn_config"]
    heads, hd = linear["num_heads"], linear["head_dim"]
    p = heads * hd
    return 3 * d * p + 2 * (d * hd + hd * p) + d * heads + p * d


def attention_parameters(config: dict) -> int:
    """Entries of latent attention's four projections."""
    d, heads = config["hidden_size"], config["num_attention_heads"]
    nope, r = config["qk_nope_head_dim"], config["qk_rope_head_dim"]
    latent, vd = config["kv_lora_rank"], config["v_head_dim"]
    return (d * heads * (nope + r) + d * (latent + r)
            + latent * heads * (nope + vd) + heads * vd * d)


def expert_parameters(config: dict) -> int:
    """Entries of one expert's three matrices."""
    return 3 * config["hidden_size"] * config["moe_intermediate_size"]


def expert_layers(config: dict) -> int:
    """Layers with a router."""
    return config["num_hidden_layers"] - config["first_k_dense_replace"]


def matmul_parameters(config: dict) -> int:
    """Entries of the matrices every token is multiplied by (the
    embedding's lookup is no product; a routed expert is not sure, the
    shared one is)."""
    d, kinds = config["hidden_size"], mixers(config)
    return (kinds.count("kda") * kda_parameters(config)
            + kinds.count("full_attention") * attention_parameters(config)
            + config["first_k_dense_replace"] * 3 * d
            * config["intermediate_size"]
            + expert_layers(config) * (
                d * config["published"]["num_experts"]
                + config["num_shared_experts"] * expert_parameters(config))
            + config["vocab_size"] * d)


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


def kda_chunk_flops(chunk: int, dk: int, dv: int) -> int:
    """Operations of the chunked form's matrix products for one chunk of
    one head, one forward pass, a multiply and an add each: the two
    pairwise products (queries by keys and keys by keys, ``2 C^2 K`` each),
    the unit-triangular solve for ``V + K`` right-hand sides by forward
    substitution (``C^2 (V + K)``), the two products with the entering state
    inside the hand-over (``W S`` and ``K^T U``, ``2 C K V`` each), and the
    output's two (``Q S``, ``2 C K V``, and the pairwise terms by ``U``,
    ``2 C^2 V``)."""
    return (4 * chunk * chunk * dk + chunk * chunk * (dv + dk)
            + 6 * chunk * dk * dv + 2 * chunk * chunk * dv)


def kda_work(config: dict, batch: int) -> dict:
    """The recurrence of every KDA layer over one step's tokens in the
    chunked form at ``kda_chunk``: its matrix products
    (:func:`kda_chunk_flops` a chunk a head) in the four passes a step with
    per-layer recomputation makes of them — forward, recomputation, and the
    backward pass twice over —, and each operand moved once a pass: q, k, v
    in the activations' type, the decay (a number a channel), the step size
    and the output float32; in the backward pass their gradients beside
    them.  The same work whatever implements the scan."""
    linear = config["linear_attn_config"]
    heads, hd = linear["num_heads"], linear["head_dim"]
    chunk, tokens = config["kda_chunk"], batch * config["seq_len"]
    layers = mixers(config).count("kda")
    chunks = batch * -(-config["seq_len"] // chunk)
    a_token = heads * (3 * hd * ACTIVATION_BYTES + 4 * hd + 4 + 4 * hd)
    return {"flops": 4 * kda_chunk_flops(chunk, hd, hd) * chunks * heads
            * layers,
            "bytes": 4 * a_token * tokens * layers}
