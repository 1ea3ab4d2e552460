"""Operations and bytes one training step of the ``trinity_mini``
configuration needs, from shapes.

Lower bounds on both, so a roofline share built on them cannot pass 100%.
``step_work``: every matrix a token surely meets counted once forward and
twice backward (6 operations a parameter a token) — attention's five
projections (the gate's among them) in every layer, the dense feed-forward,
the shared expert and the router in every expert layer, the untied head —
and **the routed experts and attention's scores counted at zero**: how many
slots land on the held experts and how long the documents are is the data's,
and the bound holds whatever they are.  Nothing recomputed, no
normalisation, rotation, gate or activation.  Of the bytes only what no
schedule can avoid: the batch read once, and the optimizer's pass over the
parameters (read parameter, gradient, both moments; write parameter and both
moments), all float32.
``experts_work``: the routed experts' grouped products alone for a given
number of slots, whatever implements them.
``window_attention_work`` / ``full_attention_work``: the scores, softmax and
values of the sliding layers and of the full ones alone, inside the mask's
band or triangle **whatever the documents are and whatever implements the
window**, in the passes the program has made of them since PR 49 (the
forward blocks once a step, their output and log-sum-exp kept; the backward
pass's five products): a later kernel is read against the same yardstick,
and a window that is only a mask reads low, not over 100.
``attention_gate_work`` / ``post_norm_work``: what the output gate and the
two post-norms of a layer add to a sibling's layer, whatever implements
them.
"""

from __future__ import annotations

#: bytes of an activation (``dtype`` bfloat16)
ACTIVATION_BYTES = 2


def mixers(config: dict) -> list:
    """The attention type of every layer run, in forward order."""
    return [config["layer_types"][i] for i in config["layers_run"]]


def attention_parameters(config: dict) -> int:
    """Entries of gated grouped-query attention's five projections."""
    d, hd = config["hidden_size"], config["head_dim"]
    return (3 * d * config["num_attention_heads"] * hd
            + 2 * d * config["num_key_value_heads"] * hd)


def expert_parameters(config: dict) -> int:
    """Entries of one expert's three matrices."""
    return 3 * config["hidden_size"] * config["moe_intermediate_size"]


def expert_layers(config: dict) -> int:
    """Layers with a router."""
    return config["num_hidden_layers"] - config["num_dense_layers"]


def matmul_parameters(config: dict) -> int:
    """Entries of the matrices every token is multiplied by (the
    embedding's lookup is no product, the untied head is; the shared expert
    is sure, a routed one is not)."""
    d = config["hidden_size"]
    return (config["num_hidden_layers"] * attention_parameters(config)
            + config["num_dense_layers"] * 3 * d * config["intermediate_size"]
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


def mask_pairs(t: int, window=None) -> int:
    """Query-key pairs a head's mask holds on a row of ``t`` tokens that is
    one document: the triangle ``j <= i``, ``t (t + 1) / 2``, or under a
    ``window`` the band ``i - j < window`` of it, ``t w - w (w - 1) / 2``."""
    w = t if window is None else min(window, t)
    return t * w - w * (w - 1) // 2


def _attention_work(config: dict, tokens: int, kind: str, window) -> dict:
    """The blocks of scores, softmax and values of every layer of ``kind``
    on ``tokens`` tokens a step in rows of ``seq_len``.  Operations: the two
    products of the scores inside the mask (``q k^T`` and ``p v``: 4 a pair
    a number of a head), once forward — a recomputed layer keeps the
    output and the log-sum-exp and makes them no second time — and two and
    a half times for the backward pass's five products: 3.5 times the
    forward.  Bytes: ``q`` and ``o`` (a query head's), ``k`` and ``v`` (a
    key head's) in the activations' type moved once a pass — forward, and
    backward, where their four gradients move beside them: three passes."""
    heads, kv = config["num_attention_heads"], config["num_key_value_heads"]
    hd, t = config["head_dim"], config["seq_len"]
    layers, rows = mixers(config).count(kind), tokens // t
    forward = 4 * mask_pairs(t, window) * hd * heads * rows
    a_token = 2 * (heads + kv) * hd * ACTIVATION_BYTES
    return {"flops": int(3.5 * forward) * layers,
            "bytes": 3 * a_token * tokens * layers}


def window_attention_work(config: dict, tokens: int) -> dict:
    """The sliding layers' attention inside the band ``i - j <
    sliding_window`` (:func:`_attention_work`)."""
    return _attention_work(config, tokens, "sliding_attention",
                           config["sliding_window"])


def full_attention_work(config: dict, tokens: int) -> dict:
    """The full layers' attention inside the triangle ``j <= i``
    (:func:`_attention_work`)."""
    return _attention_work(config, tokens, "full_attention", None)


def attention_gate_work(config: dict, tokens: int) -> dict:
    """The output gate of every layer on ``tokens`` tokens a step: the
    product ``h W_g`` (6 operations an entry a token, forward and backward)
    and, of the bytes, ``W_g`` in float32 read twice and its gradient
    written once, and a token's ``h`` (D), attention's output and the gated
    output (heads x hd each) in the activations' type moved once forward
    and their gradients once backward."""
    d = config["hidden_size"]
    wide = config["num_attention_heads"] * config["head_dim"]
    layers = config["num_hidden_layers"]
    return {"flops": 6 * d * wide * tokens * layers,
            "bytes": (3 * 4 * d * wide
                      + 2 * (d + 2 * wide) * ACTIVATION_BYTES * tokens)
            * layers}


def post_norm_work(config: dict, tokens: int) -> dict:
    """The two post-norms of every layer on ``tokens`` tokens a step: what
    a half adds, the residual stream read and written (three arrays of a
    token's D numbers in the activations' type) forward, and as many
    backward; 4 operations a number a pass (square, sum, scale, add)."""
    d, norms = config["hidden_size"], 2 * config["num_hidden_layers"]
    return {"flops": 2 * 4 * d * tokens * norms,
            "bytes": 2 * 3 * d * ACTIVATION_BYTES * tokens * norms}
