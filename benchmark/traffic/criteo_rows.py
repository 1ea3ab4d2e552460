"""Criteo-shaped rows from a seed (no JAX, none of the program).

13 dense floats, 26 categorical ids and a click label a row — the shape
``examples/criteo/criteo_pipeline.py::synth_criteo`` makes — with the ids
drawn from a Zipf law within each feature's buckets instead of uniformly:
Criteo's keys are power-law, and uniform keys flatter every table
strategy.  Rank 1 of feature ``f`` is bucket ``perm_f[0]`` of a seeded
permutation, so the hot ids are not the low ones.
"""

from __future__ import annotations

import numpy as np

NUM_DENSE = 13
NUM_CAT = 26


def zipf_cdf(buckets: int, s: float) -> np.ndarray:
    """Cumulative distribution of P(rank r) ~ (r + 1) ** -s over the ranks."""
    cdf = np.cumsum(np.arange(1, buckets + 1, dtype=np.float64) ** -float(s))
    return cdf / cdf[-1]


def zipf_ranks(rng, n: int, cdf: np.ndarray) -> np.ndarray:
    """``n`` ranks drawn by inverting the cumulative distribution."""
    return np.searchsorted(cdf, rng.random(n), side="left").astype(np.int64)


def arrays(params: dict, seed: int) -> dict:
    """The whole data set as columns: ``dense`` (n, 13) float32, ``cat``
    (n, 26) int32, ``label`` (n,) int32."""
    n, buckets = params["rows"], params["hash_buckets"]
    rng = np.random.default_rng([int(seed), 0xC217E0])
    dense = rng.random((n, NUM_DENSE), dtype=np.float32)
    cat = np.empty((n, NUM_CAT), np.int32)
    cdf = zipf_cdf(buckets, params["zipf_s"])
    for f in range(NUM_CAT):
        perm = rng.permutation(buckets).astype(np.int32)
        cat[:, f] = perm[zipf_ranks(rng, n, cdf)]
    # clicks driven by dense[0] and the parity of one categorical id, as in
    # the example's generator, so that the loss can fall
    logit = 3.0 * (dense[:, 0] - 0.5) + (cat[:, 0] % 2) - 0.5
    label = (1.0 / (1.0 + np.exp(-logit)) > rng.random(n)).astype(np.int32)
    return {"dense": dense, "cat": cat, "label": label}


def rows(params: dict, seed: int, ids) -> dict:
    """The batch the program should have built from rows ``ids``."""
    data = arrays(params, seed)
    ids = np.asarray(ids, np.int64)
    return {k: v[ids] for k, v in data.items()}


def generate(params: dict, seed: int, out_dir: str) -> dict:
    """Write the columns to ``out_dir/rows.npz`` for the driver program to
    build its DataFrame from.  A fourth column, the row's own number, rides
    along so the output check can make the rows a step saw again."""
    import os

    os.makedirs(out_dir, exist_ok=True)
    data = arrays(params, seed)
    path = os.path.join(out_dir, "rows.npz")
    np.savez(path, **data)
    nbytes = sum(int(v.nbytes) for v in data.values())
    return {"data_dir": out_dir, "npz": path, "records": params["rows"],
            "bytes": nbytes}
