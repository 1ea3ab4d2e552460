"""Operations and bytes one training step of the ``lfm2_8b_a1b``
configuration needs, from shapes.

Lower bounds on both, so a roofline share built on them cannot pass 100%.
``step_work``: every matrix a token surely meets counted once forward and
twice backward (6 operations a parameter a token) — the conv mixers' two
projections, attention's four, the dense feed-forward, the routers, the tied
head — and **the routed experts and attention's scores counted at zero**:
how many slots land on the held experts and how long the documents are is
the data's, and the bound holds whatever they are.  Nothing recomputed, no
normalisation, gate or activation.  Of the bytes only what no schedule can
avoid: the batch read once, and the optimizer's pass over the parameters
(read parameter, gradient, both moments; write parameter and both moments),
all float32.
``experts_work``: the routed experts' grouped products alone for a given
number of slots, whatever implements them.
``short_conv_work``: what lies between a conv mixer's two projections (the
two gates and the three taps), whatever implements it.
"""

from __future__ import annotations

#: bytes of an activation (``dtype`` bfloat16)
ACTIVATION_BYTES = 2


def mixers(config: dict) -> list:
    """The mixer of every layer run, in forward order."""
    return [config["layer_types"][i] for i in config["layers_run"]]


def conv_parameters(config: dict) -> int:
    """Entries of a conv mixer's two projections."""
    d = config["hidden_size"]
    return 3 * d * d + d * d


def attention_parameters(config: dict) -> int:
    """Entries of grouped-query attention's four projections."""
    d = config["hidden_size"]
    hd = d // config["num_attention_heads"]
    return 2 * d * d + 2 * d * config["num_key_value_heads"] * hd


def expert_parameters(config: dict) -> int:
    """Entries of one expert's three matrices."""
    return 3 * config["hidden_size"] * config["moe_intermediate_size"]


def expert_layers(config: dict) -> int:
    """Layers with a router."""
    return config["num_hidden_layers"] - config["num_dense_layers"]


def matmul_parameters(config: dict) -> int:
    """Entries of the matrices every token is multiplied by (the
    embedding's lookup is no product, the head tied to it is; a routed
    expert is not sure)."""
    d, kinds = config["hidden_size"], mixers(config)
    return (kinds.count("conv") * conv_parameters(config)
            + kinds.count("full_attention") * attention_parameters(config)
            + config["num_dense_layers"] * 3 * d * config["intermediate_size"]
            + expert_layers(config) * d * config["published"]["num_experts"]
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


def short_conv_work(config: dict, tokens: int) -> dict:
    """The gated short convolutions of every conv layer on ``tokens``
    tokens a step: ``c = conv_K(B * z)`` and ``C * c``.  Four arrays of a
    token's ``hidden_size`` numbers in the activations' type (``B``, ``C``,
    ``z`` and what goes on to the output projection) moved once in each of
    the three passes the program makes — forward, the layer's recomputation,
    and backward, where it is their gradients that move; the operations (a
    product for each gate, a product and a sum a tap) are counted for the
    same three passes, the backward one twice."""
    d, taps = config["hidden_size"], config["conv_L_cache"]
    layers = mixers(config).count("conv")
    return {"flops": 4 * (2 + 2 * taps - 1) * d * tokens * layers,
            "bytes": 3 * 4 * ACTIVATION_BYTES * d * tokens * layers}
