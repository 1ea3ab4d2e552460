"""ImageNet-shaped TFRecord shards from a seed (no JAX, none of the program).

The shape ``examples/imagenet/resnet_spark.py`` reads — raw ``uint8`` pixels
of ``side x side x 3`` under ``image`` and an ``int64`` ``label`` — plus the
record's own number under ``id``, so that the output check can make the very
rows a step saw again from the seed instead of taking them from the feed it
is checking.  The framing and the ``tf.train.Example`` encoding are written
out here: the records are the benchmark's input, not the program's output.

Every record is drawn from ``(seed, id)`` alone, so any row can be made
again without making the rest.
"""

from __future__ import annotations

import os
import struct

import google_crc32c
import numpy as np

_MASK_DELTA = 0xA282EAD8


def record_arrays(seed: int, record_id: int, side: int, classes: int):
    """The pixels (uint8, side x side x 3) and the label of one record."""
    rng = np.random.default_rng([int(seed), int(record_id)])
    pixels = rng.integers(0, 256, size=(side, side, 3), dtype=np.uint8)
    return pixels, int(rng.integers(0, classes))


def rows(params: dict, seed: int, ids) -> dict:
    """The batch the program should have built from records ``ids``: what
    the example's parse function makes of them (float32 pixels in [0, 1],
    int32 labels), computed from the seed."""
    side, classes = params["image_side"], params["classes"]
    made = [record_arrays(seed, i, side, classes) for i in ids]
    return {
        "image": np.stack([m[0] for m in made]).astype(np.float32) / 255.0,
        "label": np.asarray([m[1] for m in made], np.int32),
    }


def _masked_crc(data: bytes) -> int:
    crc = google_crc32c.value(data)
    return (((crc >> 15) | (crc << 17)) + _MASK_DELTA) & 0xFFFFFFFF


def _varint(n: int) -> bytes:
    out = bytearray()
    while True:
        bits = n & 0x7F
        n >>= 7
        if n:
            out.append(bits | 0x80)
        else:
            out.append(bits)
            return bytes(out)


def _field(number: int, payload: bytes) -> bytes:
    """One length-delimited protobuf field."""
    return _varint((number << 3) | 2) + _varint(len(payload)) + payload


def _feature_entry(key: str, feature: bytes) -> bytes:
    """One entry of ``Features.feature`` (a map<string, Feature>)."""
    return _field(1, _field(1, key.encode()) + _field(2, feature))


def encode_example(pixels: bytes, label: int, record_id: int) -> bytes:
    """A serialized ``tf.train.Example`` with ``image`` (BytesList),
    ``label`` and ``id`` (Int64List, packed)."""
    features = b"".join([
        _feature_entry("image", _field(1, _field(1, pixels))),
        _feature_entry("label", _field(3, _field(1, _varint(label)))),
        _feature_entry("id", _field(3, _field(1, _varint(record_id)))),
    ])
    return _field(1, features)


def frame(payload: bytes) -> bytes:
    """TFRecord framing: length, its masked crc32c, payload, its crc."""
    header = struct.pack("<Q", len(payload))
    return b"".join([header, struct.pack("<I", _masked_crc(header)), payload,
                     struct.pack("<I", _masked_crc(payload))])


def generate(params: dict, seed: int, out_dir: str) -> dict:
    """Write ``params["records"]`` records into ``params["shards"]`` files
    ``part-NNNNN`` under ``out_dir``; record ``i`` goes to shard
    ``i % shards``.  Returns what the feed plane needs to find them."""
    records, shards = params["records"], params["shards"]
    side, classes = params["image_side"], params["classes"]
    os.makedirs(out_dir, exist_ok=True)
    files = [open(os.path.join(out_dir, f"part-{s:05d}"), "wb")
             for s in range(shards)]
    nbytes = 0
    try:
        for i in range(records):
            pixels, label = record_arrays(seed, i, side, classes)
            framed = frame(encode_example(pixels.tobytes(), label, i))
            files[i % shards].write(framed)
            nbytes += len(framed)
    finally:
        for f in files:
            f.close()
    return {"data_dir": out_dir, "glob": os.path.join(out_dir, "part-*"),
            "records": records, "bytes": nbytes}
