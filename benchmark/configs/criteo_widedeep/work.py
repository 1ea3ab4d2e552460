"""Operations and bytes one wide&deep training step needs, from shapes.

A lower bound, so a roofline share built on it cannot pass 100%: the MLP's
matrix products once forward and twice backward; of the bytes what the
algorithm cannot avoid — the batch read once, the embedding and wide rows
the batch touches read for the forward pass and read and written with their
AdaGrad accumulators for the update (the *touched* rows, at most
``batch * 26``, not the table), and AdamW's pass over the MLP parameters.
"""

from __future__ import annotations

NUM_DENSE = 13
NUM_CAT = 26


def mlp_macs_per_row(config: dict) -> int:
    dims = [NUM_CAT * config["embed_dim"] + NUM_DENSE, *config["hidden"], 1]
    return sum(a * b for a, b in zip(dims, dims[1:]))


def mlp_parameters(config: dict) -> int:
    dims = [NUM_CAT * config["embed_dim"] + NUM_DENSE, *config["hidden"], 1]
    return sum(a * b + b for a, b in zip(dims, dims[1:]))


def step_work(config: dict, batch: int) -> dict:
    row_floats = config["embed_dim"] + 1          # deep row + wide weight
    touched = batch * NUM_CAT
    table_bytes = 5 * 4 * row_floats * touched    # r fwd; r+r, w+w update
    batch_bytes = batch * (NUM_DENSE * 4 + NUM_CAT * 4 + 4)
    return {
        "flops": 3 * 2 * batch * mlp_macs_per_row(config),
        "bytes": table_bytes + batch_bytes + 7 * 4 * mlp_parameters(config),
        "examples": batch,
    }
