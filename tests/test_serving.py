"""Serving data plane units: bucket resolution, pad-and-mask, compile
accounting, columnar/Arrow ingest, masked emission (ISSUE 5 tentpole)."""

import numpy as np
import pytest

from tensorflowonspark_tpu import serving, sql_compat
from tensorflowonspark_tpu.sparkapi.sql import Row


# ---------------------------------------------------------------------------
# Buckets
# ---------------------------------------------------------------------------


def test_resolve_buckets_defaults_to_batch_size():
    assert serving.resolve_buckets(128) == (128,)
    assert serving.resolve_buckets(128, None) == (128,)
    assert serving.resolve_buckets(128, []) == (128,)


def test_resolve_buckets_sorts_dedups_and_drops_nonpositive():
    assert serving.resolve_buckets(512, [512, 32, 32, 0, -4]) == (32, 512)


def test_resolve_buckets_drops_oversize_buckets():
    # a batch never exceeds batch_size, so an oversize bucket would only
    # pad full batches past their own size — dropped; and the terminal
    # batch_size bucket is restored so tails above the surviving buckets
    # don't compile at their own shape
    assert serving.resolve_buckets(128, [512, 32]) == (32, 128)
    # all oversize: fall back to the batch_size bucket
    assert serving.resolve_buckets(128, [256, 512]) == (128,)


def test_resolve_buckets_always_covers_batch_size():
    # a set whose largest bucket is below batch_size would compile every
    # tail above it at its own shape — the terminal bucket is implied
    assert serving.resolve_buckets(128, [16, 32]) == (16, 32, 128)
    assert serving.resolve_buckets(128, [128]) == (128,)


def test_choose_bucket_smallest_fit_else_exact():
    buckets = (32, 128)
    assert serving.choose_bucket(1, buckets) == 32
    assert serving.choose_bucket(32, buckets) == 32
    assert serving.choose_bucket(33, buckets) == 128
    # nothing fits: the batch compiles at its own shape (legacy cost)
    assert serving.choose_bucket(200, buckets) == 200


def test_pow2_bucket():
    assert [serving.pow2_bucket(n) for n in (1, 2, 3, 5, 8, 9)] == \
        [1, 2, 4, 8, 8, 16]


def test_pad_columns_zero_pads_leading_axis_only():
    cols = {"x": np.ones((3, 4), np.float32), "y": np.arange(3)}
    padded = serving.pad_columns(cols, 5)
    assert padded["x"].shape == (5, 4)
    assert padded["y"].shape == (5,)
    np.testing.assert_array_equal(padded["x"][:3], cols["x"])
    np.testing.assert_array_equal(padded["x"][3:], 0.0)
    np.testing.assert_array_equal(padded["y"][3:], 0)


def test_batch_rows_shared_leading_dim():
    assert serving.batch_rows({"x": np.ones((5, 2), np.float32)}) == 5
    assert serving.batch_rows({"x": np.ones((5, 2)),
                               "y": np.arange(5)}) == 5
    # no batch axis anywhere (0-d inputs): nothing paddable
    assert serving.batch_rows({"x": np.float32(3.0)}) == 0


def test_batch_rows_refuses_mismatched_leading_dims():
    # a per-call side input (k,) riding along with (n, d) features: zero-
    # extending it would feed the model wrong VALUES, not padding — no
    # paddable batch axis is reported, so callers never pad such a dict
    assert serving.batch_rows({"x": np.ones((3, 5), np.float32),
                               "bias": np.arange(5,
                                                 dtype=np.float32)}) == 0


# ---------------------------------------------------------------------------
# Compile accounting
# ---------------------------------------------------------------------------


def test_note_compile_counts_distinct_shape_signatures():
    from tensorflowonspark_tpu import obs

    key = ("test_note_compile", id(test_note_compile_counts_distinct_shape_signatures))
    counter = obs.counter("serving_compiles_total")
    c0 = counter.value
    b1 = {"x": np.zeros((4, 2), np.float32)}
    assert serving.note_compile(key, b1) is True
    assert serving.note_compile(key, dict(b1)) is False  # same signature
    # different shape → new signature
    assert serving.note_compile(key, {"x": np.zeros((8, 2), np.float32)})
    # different dtype → new signature
    assert serving.note_compile(key, {"x": np.zeros((4, 2), np.int32)})
    assert counter.value - c0 == 3
    serving.forget(key)
    # after forget, the same shape counts again (fresh model)
    assert serving.note_compile(key, b1) is True
    serving.forget(key)


# ---------------------------------------------------------------------------
# Columnar ingest
# ---------------------------------------------------------------------------


def _rows(n, start=0):
    return [Row.from_fields(["x", "id"], [np.full(3, i, np.float32), i])
            for i in range(start, start + n)]


def test_note_rows_derives_padding_waste_ratio_gauge():
    """ISSUE 6 satellite: the waste gauge is derived from the existing
    rows/padded counters on every batch — padded / (real + padded), the
    fraction of forward compute spent on invented rows."""
    from tensorflowonspark_tpu import obs

    serving.note_rows(24, 32)
    rows = obs.counter("serving_rows_total").value
    padded = obs.counter("serving_padded_rows_total").value
    gauge = obs.gauge("serving_padding_waste_ratio").value
    assert gauge == pytest.approx(padded / (rows + padded))
    serving.note_rows(32, 32)  # a full batch moves the ratio down
    assert obs.gauge("serving_padding_waste_ratio").value < gauge


def test_padding_waste_warning_fires_once_over_threshold(monkeypatch):
    """Bad-bucket-ladder detection: waste above the threshold (over a
    meaningful row volume) emits ONE structured warning event."""
    from tensorflowonspark_tpu import obs

    monkeypatch.setattr(serving, "_PAD_WASTE_WARNED", False)
    # the process counters are cumulative across the suite, so use a
    # threshold any nonzero cumulative waste ratio clears
    monkeypatch.setattr(serving, "DEFAULT_PAD_WASTE_WARN", 0.000001)
    tracer = obs.get_tracer()
    before = sum(1 for e in tracer.snapshot()
                 if e["name"] == "serving.padding_waste")
    # enough volume to clear the min-rows guard, mostly padding
    serving.note_rows(1, serving._PAD_WARN_MIN_ROWS)
    serving.note_rows(1, serving._PAD_WARN_MIN_ROWS)
    events = [e for e in tracer.snapshot()
              if e["name"] == "serving.padding_waste"]
    assert len(events) == before + 1
    assert serving._PAD_WASTE_WARNED is True
    attrs = events[-1]["attrs"]
    assert attrs["ratio"] > 0
    assert {"threshold", "rows", "padded"} <= set(attrs)
    # warned-once: more waste does not re-fire
    serving.note_rows(1, serving._PAD_WARN_MIN_ROWS)
    assert sum(1 for e in tracer.snapshot()
               if e["name"] == "serving.padding_waste") == before + 1


def test_padding_waste_warning_respects_min_volume(monkeypatch):
    """A ragged first batch must not cry wolf: below the min-rows guard
    no warning fires even at 100% waste."""
    from tensorflowonspark_tpu import obs, serving as serving_mod

    monkeypatch.setattr(serving_mod, "_PAD_WASTE_WARNED", False)
    monkeypatch.setattr(serving_mod, "DEFAULT_PAD_WASTE_WARN", 0.000001)
    # raise the volume guard above anything the suite has accumulated —
    # the counters are process-cumulative by design
    monkeypatch.setattr(serving_mod, "_PAD_WARN_MIN_ROWS", 10**12)
    tracer = obs.get_tracer()
    before = sum(1 for e in tracer.snapshot()
                 if e["name"] == "serving.padding_waste")
    serving_mod.note_rows(1, 64)
    assert sum(1 for e in tracer.snapshot()
               if e["name"] == "serving.padding_waste") == before
    assert serving_mod._PAD_WASTE_WARNED is False


def test_ingest_chunks_rows_chunking_and_columns():
    chunks = list(serving.ingest_chunks(
        iter(_rows(10)), 4, {"x": "x"}, ["x", "id"]))
    assert [n for n, _ in chunks] == [4, 4, 2]
    got = np.concatenate([c["x"] for _, c in chunks])
    np.testing.assert_array_equal(got[:, 0], np.arange(10, dtype=np.float32))
    # only the mapped column is extracted
    assert all(set(c) == {"x"} for _, c in chunks)


def test_ingest_chunks_input_mapping_renames():
    chunks = list(serving.ingest_chunks(
        iter(_rows(3)), 8, {"id": "ident"}, ["x", "id"]))
    assert len(chunks) == 1
    n, cols = chunks[0]
    np.testing.assert_array_equal(cols["ident"], [0, 1, 2])


def test_ingest_chunks_missing_column_raises_keyerror():
    with pytest.raises(KeyError, match="nope"):
        list(serving.ingest_chunks(
            iter(_rows(3)), 8, {"nope": "nope"}, ["x", "id"]))


def test_ingest_chunks_plain_tuples_use_positional_columns():
    rows = [(float(i), i) for i in range(5)]
    chunks = list(serving.ingest_chunks(
        iter(rows), 8, {"v": "v"}, ["v", "id"]))
    n, cols = chunks[0]
    assert n == 5
    np.testing.assert_array_equal(cols["v"], [0.0, 1.0, 2.0, 3.0, 4.0])


def test_ingest_chunks_dict_rows():
    rows = [{"a": i, "b": -i} for i in range(4)]
    chunks = list(serving.ingest_chunks(iter(rows), 8, {"b": "b"}, ["a", "b"]))
    np.testing.assert_array_equal(chunks[0][1]["b"], [0, -1, -2, -3])


def test_ingest_chunks_arrow_record_batches():
    pa = pytest.importorskip("pyarrow")
    feats = np.arange(20, dtype=np.float32).reshape(10, 2)
    rb = pa.RecordBatch.from_arrays(
        [pa.array(list(feats)), pa.array(np.arange(10))], ["x", "id"])
    chunks = list(serving.ingest_chunks(iter([rb]), 4, {"x": "x"}, ["x", "id"]))
    assert [n for n, _ in chunks] == [4, 4, 2]
    got = np.concatenate([c["x"] for _, c in chunks])
    np.testing.assert_array_equal(got, feats)


def test_ingest_chunks_arrow_missing_column_raises():
    pa = pytest.importorskip("pyarrow")
    rb = pa.RecordBatch.from_arrays([pa.array([1, 2])], ["a"])
    with pytest.raises(KeyError, match="missing|lacks"):
        list(serving.ingest_chunks(iter([rb]), 4, {"b": "b"}, ["a", "b"]))


def test_ingest_chunks_mixed_rows_then_arrow_flushes_in_order():
    pa = pytest.importorskip("pyarrow")
    rows = [{"v": float(i)} for i in range(3)]
    rb = pa.RecordBatch.from_arrays(
        [pa.array([10.0, 11.0])], ["v"])
    chunks = list(serving.ingest_chunks(
        iter(rows + [rb]), 8, {"v": "v"}, ["v"]))
    got = np.concatenate([c["v"] for _, c in chunks])
    np.testing.assert_array_equal(got, [0.0, 1.0, 2.0, 10.0, 11.0])


# ---------------------------------------------------------------------------
# Arrow dense fast path
# ---------------------------------------------------------------------------


def test_arrow_dense_list_columns_densify_zero_copy():
    pa = pytest.importorskip("pyarrow")
    feats = np.arange(12, dtype=np.float32).reshape(4, 3)
    for arr in (pa.array(list(feats)),
                pa.FixedSizeListArray.from_arrays(pa.array(feats.ravel()), 3)):
        rb = pa.RecordBatch.from_arrays([arr], ["x"])
        out = sql_compat.arrow_batch_columns(rb)
        assert out["x"].shape == (4, 3)
        assert out["x"].dtype == np.float32
        np.testing.assert_array_equal(out["x"], feats)


def test_arrow_ragged_list_column_stays_object():
    pa = pytest.importorskip("pyarrow")
    rb = pa.RecordBatch.from_arrays(
        [pa.array([[1.0], [2.0, 3.0]])], ["x"])
    out = sql_compat.arrow_batch_columns(rb)
    assert out["x"].dtype == object
    assert list(out["x"][1]) == [2.0, 3.0]


def test_arrow_batch_columns_ignores_non_arrow_items():
    assert sql_compat.arrow_batch_columns({"x": 1}) is None
    assert sql_compat.arrow_batch_columns([1, 2]) is None


# ---------------------------------------------------------------------------
# Masked emission
# ---------------------------------------------------------------------------


def test_emit_rows_masks_padded_rows_and_matches_make_row():
    scores = np.arange(8, dtype=np.float32)
    out = serving.emit_rows({"score": scores}, 5, "sparkapi", fed_rows=8)
    assert len(out) == 5  # the 3 padded rows are never emitted
    expect = [sql_compat.make_row(["score"], [float(v)], "sparkapi")
              for v in scores[:5]]
    assert out == expect


def test_emit_rows_multi_column_zip():
    out = serving.emit_rows(
        {"a": np.array([1, 2, 3]), "b": np.array([[1.0, 2.0]] * 3)}, 2,
        "sparkapi", fed_rows=3)
    assert len(out) == 2
    assert out[0].a == 1 and out[0].b == [1.0, 2.0]


def test_emit_rows_rejects_outputs_without_batch_axis():
    with pytest.raises(ValueError, match="per-example"):
        serving.emit_rows({"loss": np.float32(0.5)}, 4, "sparkapi")
    with pytest.raises(ValueError, match="per-example"):
        serving.emit_rows({"short": np.zeros(2)}, 4, "sparkapi")
    # a batch-aggregated output LONGER than the fed batch (pooled
    # embedding of dim 8 on a 3-row exact-shape batch) must be rejected,
    # not sliced into plausible-looking garbage rows
    with pytest.raises(ValueError, match="per-example"):
        serving.emit_rows({"pooled": np.arange(8.0)}, 3, "sparkapi")
    # same on a padded batch: output length must equal the FED bucket
    with pytest.raises(ValueError, match="per-example"):
        serving.emit_rows({"pooled": np.arange(8.0)}, 3, "sparkapi",
                          fed_rows=16)


def test_row_maker_matches_make_row():
    make = sql_compat.row_maker(["a", "b"], "sparkapi")
    got = make([1, "x"])
    assert got == sql_compat.make_row(["a", "b"], [1, "x"], "sparkapi")
    assert got.a == 1 and got["b"] == "x"


# ---------------------------------------------------------------------------
# Stager / prefetch knobs
# ---------------------------------------------------------------------------


def test_stager_auto_skips_device_put_on_cpu(monkeypatch):
    import jax

    if jax.default_backend() != "cpu":
        pytest.skip("auto mode only skips on the CPU backend")
    monkeypatch.delenv("TFOS_SERVING_DEVICE_PUT", raising=False)
    batch = {"x": np.zeros(3)}
    out = serving.stager()(batch)
    assert out["x"] is batch["x"]  # identity: no per-batch dispatch on CPU
    # forced on: stages through jax (host platform still works)
    monkeypatch.setenv("TFOS_SERVING_DEVICE_PUT", "1")
    staged = serving.stager()(batch)
    np.testing.assert_array_equal(np.asarray(staged["x"]), batch["x"])
    # forced off
    monkeypatch.setenv("TFOS_SERVING_DEVICE_PUT", "0")
    assert serving.stager()(batch)["x"] is batch["x"]


def test_prefetch_depth_env(monkeypatch):
    monkeypatch.delenv("TFOS_SERVING_PREFETCH", raising=False)
    assert serving.prefetch_depth() == 2
    monkeypatch.setenv("TFOS_SERVING_PREFETCH", "0")
    assert serving.prefetch_depth() == 0
    monkeypatch.setenv("TFOS_SERVING_PREFETCH", "junk")
    assert serving.prefetch_depth() == 2


# ---------------------------------------------------------------------------
# Warmup shape helpers
# ---------------------------------------------------------------------------


def test_input_specs_from_example_and_signature():
    specs = serving.input_specs(
        example={"features": np.zeros(4, np.float32), "id": np.int32(0)})
    assert specs["features"] == ((4,), np.dtype(np.float32))
    assert specs["id"] == ((), np.dtype(np.int32))
    specs = serving.input_specs(signature={"inputs": [
        {"name": "features", "shape": [None, 6], "dtype": "float32"}]})
    assert specs["features"] == ((6,), np.dtype(np.float32))
    batch = serving.zero_batch(specs, 8)
    assert batch["features"].shape == (8, 6)
    assert batch["features"].dtype == np.float32


def test_input_specs_polymorphic_nonbatch_dim_is_value_error():
    """A symbolic NON-batch dim (variable seq len) must raise the
    actionable ValueError — not TypeError from int(None) — so callers'
    except-ValueError fallbacks (online add_tenant) degrade gracefully."""
    with pytest.raises(ValueError, match="polymorphic non-batch"):
        serving.input_specs(signature={"inputs": [
            {"name": "tokens", "shape": [None, None, 64],
             "dtype": "float32"}]})
    with pytest.raises(ValueError, match="exactly one"):
        serving.input_specs()
    with pytest.raises(ValueError, match="no inputs"):
        serving.input_specs(signature={"inputs": []})


def test_note_compile_hit_miss_counters_and_compile_seconds():
    """The hit/miss counter split + compile-seconds histogram (the
    persistent-compile-cache groundwork, ROADMAP item 4): every miss is a
    compile, every repeat is a hit, and warm_buckets times its forced
    warm forwards into serving_compile_seconds."""
    import numpy as np

    from tensorflowonspark_tpu import obs, serving

    misses = obs.counter("serving_compile_cache_misses_total")
    hits = obs.counter("serving_compile_cache_hits_total")
    compiles = obs.counter("serving_compiles_total")
    hist = obs.histogram("serving_compile_seconds")
    m0, h0, c0, n0 = misses.value, hits.value, compiles.value, hist.count
    key = ("hit_miss_test", id(object()))
    b = {"x": np.zeros((4, 2), np.float32)}
    assert serving.note_compile(key, b) is True
    assert serving.note_compile(key, dict(b)) is False
    assert serving.note_compile(key, dict(b)) is False
    assert misses.value - m0 == 1
    assert compiles.value - c0 == 1  # compiles == misses today
    assert hits.value - h0 == 2
    serving.observe_compile_seconds(0.25)
    assert hist.count - n0 == 1

    # warm_buckets reports one compile-seconds observation per bucket
    key2 = ("hit_miss_warm", id(object()))
    specs = {"x": ((2,), np.float32)}
    n1 = hist.count
    serving.warm_buckets(lambda p, batch: {"y": batch["x"] * 2}, None,
                         specs, (2, 8), key2)
    assert hist.count - n1 == 2
    assert misses.value - m0 == 3
