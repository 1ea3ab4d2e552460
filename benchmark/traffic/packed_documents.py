"""Packed language-model rows as TFRecord shards from a seed (no JAX, none
of the program).

A row is ``seq_len`` tokens: documents of log-normal length (``doc_median``,
``doc_sigma``, clipped to ``doc_min`` .. ``doc_max``) laid end to end and cut
at the row's end — no padding, the cut document's tail is dropped.  Token ids
are Zipf(``zipf_s``) over a seeded permutation of the ``vocab`` ids the chip
holds, so the hot ids are not the low ones.  A record carries ``tokens`` and
``segment_ids`` (the document's number inside the row) as raw little-endian
int32 buffers and its own number under ``id``, so that the output check can
make the very rows a step saw again from the seed.

Every record is drawn from ``(seed, id)`` alone (the permutation from the
seed), so any row can be made again without making the rest.
"""

from __future__ import annotations

import math
import os

import numpy as np

from benchmark.traffic import imagenet_records as records


def vocabulary(params: dict, seed: int):
    """``(ids by rank, the ranks' cumulative probabilities)``."""
    ranks = np.arange(1, params["vocab"] + 1, dtype=np.float64)
    cdf = np.cumsum(ranks ** -float(params["zipf_s"]))
    by_rank = np.random.default_rng([int(seed)]).permutation(params["vocab"])
    return by_rank.astype(np.int32), cdf / cdf[-1]


def row_arrays(params: dict, seed: int, record_id: int, vocab):
    """The tokens and segment ids (int32, ``seq_len`` each) of one row."""
    seq = params["seq_len"]
    rng = np.random.default_rng([int(seed), int(record_id)])
    by_rank, cdf = vocab
    ranks = np.minimum(np.searchsorted(cdf, rng.random(seq)), len(cdf) - 1)
    segments = np.empty(seq, np.int32)
    at = doc = 0
    while at < seq:
        length = int(np.clip(round(rng.lognormal(
            math.log(params["doc_median"]), params["doc_sigma"])),
            params["doc_min"], params["doc_max"]))
        segments[at:at + length] = doc
        at, doc = at + length, doc + 1
    return by_rank[ranks], segments


def rows(params: dict, seed: int, ids) -> dict:
    """The batch the program should have built from records ``ids``."""
    vocab = vocabulary(params, seed)
    made = [row_arrays(params, seed, i, vocab) for i in ids]
    return {"tokens": np.stack([m[0] for m in made]),
            "segment_ids": np.stack([m[1] for m in made])}


def encode_example(tokens, segments, record_id: int) -> bytes:
    """A serialized ``tf.train.Example``: ``tokens`` and ``segment_ids``
    (BytesList, raw int32), ``id`` (Int64List)."""
    field, entry = records._field, records._feature_entry
    features = b"".join([
        entry("tokens", field(1, field(1, tokens.astype("<i4").tobytes()))),
        entry("segment_ids",
              field(1, field(1, segments.astype("<i4").tobytes()))),
        entry("id", field(3, field(1, records._varint(record_id)))),
    ])
    return field(1, features)


def generate(params: dict, seed: int, out_dir: str) -> dict:
    """Write ``params["records"]`` rows into ``params["shards"]`` files
    ``part-NNNNN`` under ``out_dir``; record ``i`` goes to shard
    ``i % shards``.  Returns what the feed plane needs to find them."""
    shards = params["shards"]
    vocab = vocabulary(params, seed)
    os.makedirs(out_dir, exist_ok=True)
    files = [open(os.path.join(out_dir, f"part-{s:05d}"), "wb")
             for s in range(shards)]
    nbytes = 0
    try:
        for i in range(params["records"]):
            framed = records.frame(encode_example(
                *row_arrays(params, seed, i, vocab), i))
            files[i % shards].write(framed)
            nbytes += len(framed)
    finally:
        for f in files:
            f.close()
    return {"data_dir": out_dir, "glob": os.path.join(out_dir, "part-*"),
            "records": params["records"], "bytes": nbytes}
