"""The traffic generators: the same seed gives the same inputs, another seed
gives others, the Zipf parameter is honoured, and the records are ones the
program's own codec reads back."""

import glob
import json
import os

import numpy as np
import pytest

from benchmark.traffic import criteo_rows, imagenet_records

REPO = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
BIG_SEED = 2 ** 31 + 12345          # the driver's seeds pass 32 signed bits

RECORDS = {"records": 24, "shards": 4, "image_side": 16, "classes": 10}
ROWS = {"rows": 4000, "hash_buckets": 500, "zipf_s": 1.05}


def _shard_bytes(directory):
    return [open(p, "rb").read() for p in sorted(glob.glob(
        os.path.join(directory, "part-*")))]


def test_benchmark_records_are_the_same_for_the_same_seed(tmp_path):
    a = imagenet_records.generate(RECORDS, BIG_SEED, str(tmp_path / "a"))
    b = imagenet_records.generate(RECORDS, BIG_SEED, str(tmp_path / "b"))
    c = imagenet_records.generate(RECORDS, BIG_SEED + 1, str(tmp_path / "c"))
    assert _shard_bytes(a["data_dir"]) == _shard_bytes(b["data_dir"])
    assert _shard_bytes(a["data_dir"]) != _shard_bytes(c["data_dir"])
    assert a["records"] == 24 and len(_shard_bytes(a["data_dir"])) == 4


def test_benchmark_records_read_back_through_the_programs_codec(tmp_path):
    from tensorflowonspark_tpu import tfrecord

    made = imagenet_records.generate(RECORDS, BIG_SEED, str(tmp_path / "d"))
    seen = {}
    for path in sorted(glob.glob(made["glob"])):
        for payload in tfrecord.read_records(path, verify=True):
            ex = tfrecord.decode_example(payload)
            seen[int(ex["id"][1][0])] = (ex["image"][1][0],
                                         int(ex["label"][1][0]))
    assert sorted(seen) == list(range(24))
    again = imagenet_records.rows(RECORDS, BIG_SEED, [5, 17])
    for row, record_id in enumerate((5, 17)):
        pixels = np.frombuffer(seen[record_id][0], np.uint8)
        np.testing.assert_array_equal(
            again["image"][row].ravel(), pixels.astype(np.float32) / 255.0)
        assert again["label"][row] == seen[record_id][1]
    assert again["image"].dtype == np.float32
    assert 0 <= again["label"].min() and again["label"].max() < 10


def test_benchmark_rows_are_the_same_for_the_same_seed():
    a = criteo_rows.arrays(ROWS, BIG_SEED)
    b = criteo_rows.arrays(ROWS, BIG_SEED)
    c = criteo_rows.arrays(ROWS, BIG_SEED + 1)
    for key in ("dense", "cat", "label"):
        np.testing.assert_array_equal(a[key], b[key])
    assert (a["cat"] != c["cat"]).any()
    assert a["dense"].shape == (4000, 13) and a["cat"].shape == (4000, 26)
    assert a["cat"].min() >= 0 and a["cat"].max() < 500
    picked = criteo_rows.rows(ROWS, BIG_SEED, [3, 1999])
    np.testing.assert_array_equal(picked["cat"], a["cat"][[3, 1999]])


@pytest.mark.parametrize("s", [0.8, 1.05, 1.4])
def test_benchmark_zipf_parameter_is_honoured(s):
    """The rank-frequency slope of the drawn ids is the parameter's."""
    rng = np.random.default_rng(7)
    ranks = criteo_rows.zipf_ranks(rng, 400_000,
                                  criteo_rows.zipf_cdf(1000, s))
    counts = np.bincount(ranks, minlength=1000).astype(np.float64)
    top = np.arange(1, 51)
    slope = np.polyfit(np.log(top), np.log(counts[:50]), 1)[0]
    assert slope == pytest.approx(-s, abs=0.05)
    weights = np.arange(1, 1001) ** -s
    assert counts[0] / counts.sum() == pytest.approx(
        weights[0] / weights.sum(), rel=0.03)


def test_benchmark_zipf_hot_ids_are_not_the_low_ones():
    data = criteo_rows.arrays(ROWS, BIG_SEED)
    hot = [np.bincount(data["cat"][:, f], minlength=500).argmax()
           for f in range(26)]
    assert len(set(hot)) > 13       # a seeded permutation a feature


@pytest.mark.parametrize("path", sorted(glob.glob(os.path.join(
    REPO, "benchmark", "traffic", "*.json"))), ids=os.path.basename)
def test_benchmark_traffic_file_is_data_one_generator_reads(path):
    with open(path) as f:
        traffic = json.load(f)
    assert os.path.isfile(os.path.join(
        REPO, "benchmark", "traffic", traffic["generator"] + ".py"))
    assert os.path.isfile(os.path.join(
        REPO, "benchmark", "feeds", traffic["feed"] + ".py"))
    assert traffic["trace_steps"] >= 1 and traffic["max_epochs"] >= 1
