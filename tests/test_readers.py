"""InputMode.TENSORFLOW reader pipeline: sharding, interleave, shuffle,
prefetch overlap (VERDICT round-1 item 5b)."""

import os
import time

import numpy as np
import pytest

from tensorflowonspark_tpu import readers, tfrecord


def _write_part(path: str, values: list[int]) -> None:
    tfrecord.write_records(
        path,
        (tfrecord.encode_example({"v": (tfrecord.INT64_LIST, [v])})
         for v in values),
    )


@pytest.fixture()
def parts(tmp_path):
    """4 part files, 8 records each, values encode (file, index)."""
    paths = []
    for f in range(4):
        p = str(tmp_path / f"part-{f:05d}")
        _write_part(p, [f * 100 + i for i in range(8)])
        paths.append(p)
    return paths


def test_shard_files_strided_and_disjoint(parts, tmp_path):
    s0 = readers.shard_files(str(tmp_path / "part-*"), 0, 2)
    s1 = readers.shard_files(str(tmp_path / "part-*"), 1, 2)
    assert sorted(s0 + s1) == sorted(parts)
    assert not set(s0) & set(s1)


def test_batches_cover_all_records_once(parts):
    got = []
    for batch in readers.tfrecord_batches(parts, 5, prefetch=2, readers=2):
        got.extend(int(v[0]) for v in batch["v"])
    expected = sorted(f * 100 + i for f in range(4) for i in range(8))
    assert sorted(got) == expected


def test_multiple_epochs_and_drop_remainder(parts):
    batches = list(readers.tfrecord_batches(parts, 5, num_epochs=2,
                                            drop_remainder=True, prefetch=0))
    # 64 records over 2 epochs → 12 full batches of 5 per epoch
    assert len(batches) == 12
    assert all(len(b["v"]) == 5 for b in batches)


def test_shuffle_changes_order_but_not_content(parts):
    plain = [int(v[0]) for b in readers.tfrecord_batches(parts, 64, prefetch=0)
             for v in b["v"]]
    shuffled = [int(v[0]) for b in readers.tfrecord_batches(
        parts, 64, shuffle_buffer=32, shuffle_files=True, seed=7, prefetch=0)
        for v in b["v"]]
    assert sorted(shuffled) == sorted(plain)
    assert shuffled != plain


@pytest.mark.parametrize("n_readers", [1, 2])
def test_reader_error_surfaces(tmp_path, parts, n_readers):
    bad = str(tmp_path / "part-bad")
    with open(bad, "wb") as f:
        f.write(b"\x12\x34garbage-not-a-tfrecord")
    with pytest.raises(Exception):
        list(readers.tfrecord_batches(parts + [bad], 4, prefetch=2,
                                      readers=n_readers))


def test_slow_consumer_still_gets_end_sentinel(parts):
    """A consumer slower than the pump must still see the end of the
    dataset when the prefetch queue is full at pump completion
    (regression: put_nowait dropped the sentinel → consumer hung)."""
    got = []
    for batch in readers.tfrecord_batches(parts, 4, prefetch=1, readers=2):
        time.sleep(0.05)  # pump finishes + fills the queue long before us
        got.extend(int(v[0]) for v in batch["v"])
    assert len(got) == 32


def test_abandoned_iterator_stops_threads(parts):
    """Breaking out of the batch iterator must not leak pump/reader threads."""
    import threading

    before = {t.name for t in threading.enumerate()}
    it = readers.tfrecord_batches(parts, 4, prefetch=2, readers=2)
    next(it)
    it.close()  # GeneratorExit at the yield → finally → stop + join
    deadline = time.time() + 10
    while time.time() < deadline:
        leaked = {t.name for t in threading.enumerate()} - before
        leaked = {n for n in leaked if n.startswith("tfos-")}
        if not leaked:
            break
        time.sleep(0.05)
    assert not leaked, leaked


def test_prefetched_is_public_and_propagates_producer_errors():
    """``readers.prefetched`` is the ONE pump of the framework (training
    readers + the serving data plane double-buffer through it): a
    producer exception must re-raise on the consumer side, after the
    items produced before it — no wedge, no silent truncation."""
    def gen():
        yield 1
        yield 2
        raise RuntimeError("pump blew up")

    got = []
    with pytest.raises(RuntimeError, match="pump blew up"):
        for item in readers.prefetched(gen, 2):
            got.append(item)
    assert got == [1, 2]
    # prefetch <= 0 degrades to the plain generator, same contract
    with pytest.raises(RuntimeError, match="pump blew up"):
        list(readers.prefetched(gen, 0))


def test_prefetch_overlaps_feed_and_compute(parts, tmp_path):
    """With prefetch, wall time ≈ max(feed, compute), not their sum."""
    n_batches = 8
    work_s = 0.03
    big = str(tmp_path / "part-big")
    _write_part(big, list(range(n_batches * 4)))

    def slow_parse(payload):
        time.sleep(work_s / 4)  # 4 records per batch → work_s per batch
        return readers.default_parse(payload)

    def consume(prefetch):
        t0 = time.perf_counter()
        for batch in readers.tfrecord_batches([big], 4, parse_fn=slow_parse,
                                              prefetch=prefetch):
            time.sleep(work_s)  # simulated train step
        return time.perf_counter() - t0

    serial = consume(prefetch=0)
    overlapped = consume(prefetch=2)
    # serial ≈ n*(feed+compute); overlapped ≈ n*max(feed,compute) (+ramp).
    # Assert a conservative 25% improvement to stay robust on loaded CI.
    assert overlapped < serial * 0.75, (serial, overlapped)


# -- columns filled while parsing (ISSUE 25) ----------------------------------
#
# The contract: every column of every batch is what the rows-then-stack code
# made of it, ``np.asarray([parse(p)[name] for p in payloads])`` — values,
# dtype, shape, and the exception where that raised.

def _image_parse(payload: bytes) -> dict:
    i = int(payload)
    return {"image": np.full((4, 4, 3), i, np.float32) / 255.0,
            "label": np.int32(i % 10), "id": np.int64(i)}


def _growing_bytes_parse(payload: bytes) -> dict:
    i = int(payload)
    return {"name": b"ab" * (1 + i % 5), "np_name": np.bytes_(b"c" * (1 + i)),
            "id": np.int64(i)}


def _int_then_float_parse(payload: bytes) -> dict:
    i = int(payload)
    return {"x": np.float64(i + 0.5) if i % 8 == 5 else np.int64(i),
            "v": (np.full(2, i, np.float32) if i % 8 >= 3
                  else np.full(2, i, np.int16)),
            "id": np.int64(i)}


def _shape_change_parse(payload: bytes) -> dict:
    i = int(payload)
    return {"v": np.zeros(3 if i % 8 == 6 else 2, np.float32),
            "id": np.int64(i)}


def _shape_by_batch_parse(payload: bytes) -> dict:
    i = int(payload)    # a batch of 8 is all of one shape, the next of another
    return {"v": np.full(2 + i // 8, i, np.float32), "id": np.int64(i)}


def _mixed_kinds_parse(payload: bytes) -> dict:
    i = int(payload)
    return {"py_int": i, "py_float": i / 3, "flag": np.bool_(i % 2),
            "half": np.float16(i), "z": np.complex64(i + 1j),
            "big_endian": np.full(2, i, ">f4"),
            "zero_d": np.array(i, np.int32) if i % 2 else np.int32(i),
            "when": np.datetime64("2026-01-01") + i}


_EQUALITY_CASES = {
    # name: (parse_fn or None for default_parse, kwargs, raises)
    "numpy_rows_direct": (_image_parse, {}, None),
    "default_parse_lists": (None, {}, None),
    "bytes_growing_in_a_batch": (_growing_bytes_parse, {}, None),
    "ints_then_a_float_falls_back": (_int_then_float_parse, {}, None),
    "shape_change_mid_batch": (_shape_change_parse, {}, ValueError),
    "mixed_kinds": (_mixed_kinds_parse, {}, None),
    "shape_changes_between_batches": (_shape_by_batch_parse, {}, None),
    "remainder_batch": (_image_parse, {"batch_size": 5}, None),
    "remainder_dropped": (_image_parse,
                          {"batch_size": 5, "drop_remainder": True}, None),
    "reader_pool_shuffled": (_image_parse, {
        "readers": 2, "shuffle_buffer": 8, "shuffle_files": True,
        "seed": 3, "prefetch": 2}, None),
}


@pytest.fixture()
def numbered_parts(tmp_path):
    """3 part files of 8 records; a payload is its record's number."""
    paths = []
    for f in range(3):
        p = str(tmp_path / f"num-{f:05d}")
        tfrecord.write_records(
            p, (str(f * 8 + i).encode() for i in range(8)))
        paths.append(p)
    return paths


@pytest.mark.parametrize("case", sorted(_EQUALITY_CASES))
def test_batches_equal_asarray_over_parsed_rows(case, parts, numbered_parts):
    parse, kwargs, raises = _EQUALITY_CASES[case]
    kwargs = {"batch_size": 8, "prefetch": 0, **kwargs}
    files = numbered_parts if parse else parts
    parse_fn = parse or readers.default_parse
    seen = []

    def logged(payload):
        seen.append(payload)
        return parse_fn(payload)

    def expected(payloads):
        rows = [parse_fn(p) for p in payloads]
        return {name: np.asarray([r[name] for r in rows]) for name in rows[0]}

    if raises:
        with pytest.raises(raises):
            expected([str(i).encode() for i in range(8)])
        with pytest.raises(raises):
            list(readers.tfrecord_batches(files, parse_fn=logged, **kwargs))
        return
    batches = list(readers.tfrecord_batches(files, parse_fn=logged, **kwargs))
    n_records = 24 if parse else 32
    size = kwargs["batch_size"]
    if kwargs.get("drop_remainder"):
        assert [len(b["id"]) for b in batches] == [size] * (n_records // size)
    else:
        assert sum(len(next(iter(b.values()))) for b in batches) == n_records
        assert len(batches) == -(-n_records // size)
    at = 0
    for batch in batches:
        n = len(next(iter(batch.values())))
        want = expected(seen[at:at + n])
        at += n
        assert list(batch) == list(want)
        for name, col in batch.items():
            assert type(col) is np.ndarray, name
            assert col.dtype == want[name].dtype, (name, col.dtype)
            assert col.shape == want[name].shape, name
            assert col.tobytes() == want[name].tobytes(), name
            assert col.flags.owndata and col.flags.c_contiguous, name


def _column_counters():
    from tensorflowonspark_tpu import obs

    return (obs.counter("reader_columns_direct_total").value,
            obs.counter("reader_columns_stacked_total").value)


@pytest.mark.parametrize("rows", ["numpy", "default_parse"])
def test_column_counters_say_which_way_each_column_went(
        rows, parts, numbered_parts):
    direct0, stacked0 = _column_counters()
    if rows == "numpy":
        batches = list(readers.tfrecord_batches(
            numbered_parts, 8, parse_fn=_image_parse, prefetch=0))
        assert len(batches) == 3
        want = (3 * 3, 0)
    else:
        batches = list(readers.tfrecord_batches(parts, 8, prefetch=0))
        assert len(batches) == 4
        want = (0, 1 * 4)
    direct, stacked = _column_counters()
    assert (direct - direct0, stacked - stacked0) == want


def test_a_column_that_fell_back_counts_as_stacked(numbered_parts):
    direct0, stacked0 = _column_counters()
    list(readers.tfrecord_batches(numbered_parts, 8, prefetch=0,
                                  parse_fn=_int_then_float_parse))
    direct, stacked = _column_counters()
    # x and v fall back in each of the three batches, id never does
    assert (direct - direct0, stacked - stacked0) == (3, 6)


@pytest.mark.parametrize("prefetch", [0, 2])
def test_every_batch_has_arrays_of_its_own(prefetch, numbered_parts):
    """No ring, no reuse: ``device_put`` or the consumer may still hold a
    batch when the next ones are built."""
    it = readers.tfrecord_batches(numbered_parts, 4, parse_fn=_image_parse,
                                  prefetch=prefetch, num_epochs=2)
    held, copies = [], []
    for batch in it:
        held.append(batch)
        copies.append({k: v.copy() for k, v in batch.items()})
    assert len(held) == 12
    for k in range(len(held) - 1):
        for name in held[k]:
            for later in held[k + 1:k + 3]:
                assert not np.shares_memory(held[k][name], later[name])
    # and what was handed out is unchanged after the next ones were read
    for batch, copy in zip(held, copies):
        for name in batch:
            assert batch[name].tobytes() == copy[name].tobytes()
    ids = np.concatenate([b["id"] for b in held])
    assert sorted(ids.tolist()) == sorted(list(range(24)) * 2)


# -- the next batches' columns, made ahead on helper threads ------------------

def test_columns_made_ahead_are_the_ones_filled(numbered_parts, monkeypatch):
    """After the first batch has shown the columns, every array column of a
    batch is one a helper made for it, and no helper's array is used twice."""
    made_ids = []
    make = readers._ColumnsAhead._make

    def logged_make(spec):
        made = make(spec)
        made_ids.append({name: id(col) for name, col in made.items()})
        return made

    monkeypatch.setattr(readers._ColumnsAhead, "_make",
                        staticmethod(logged_make))
    batches = list(readers.tfrecord_batches(
        numbered_parts, 4, parse_fn=_image_parse, prefetch=0))
    assert len(batches) == 6
    all_made = {name: {m[name] for m in made_ids} for name in batches[0]}
    for batch in batches[1:]:
        for name, col in batch.items():
            assert id(col) in all_made[name], name
    for name in batches[0]:
        assert len({id(b[name]) for b in batches}) == len(batches)


def test_columns_ahead_adds_a_helper_only_while_they_are_behind(monkeypatch):
    import threading

    release = threading.Event()
    make = readers._ColumnsAhead._make

    def gated_make(spec):
        release.wait(timeout=10)
        return make(spec)

    monkeypatch.setattr(readers._ColumnsAhead, "_make",
                        staticmethod(gated_make))
    ahead = readers._ColumnsAhead()
    try:
        assert ahead.take() == {} and ahead.depth == 1
        cols = {"x": np.empty((4, 2), np.float32), "names": [b"a"]}
        ahead.expect_more_like(cols)
        threading.Timer(0.25, release.set).start()
        made = ahead.take()                 # not ready: the helpers are behind
        assert ahead.depth == 2
        assert set(made) == {"x"} and made["x"].shape == (4, 2)
        assert made["x"].dtype == np.float32 and made["x"] is not cols["x"]
        for want in (2, 2, 2):              # ready when asked for: no more
            ahead.expect_more_like(cols)
            for pending in list(ahead._pending):
                pending.result(timeout=10)
            ahead.take()
            assert ahead.depth == want
        while ahead._pending:
            ahead.take()
        release.clear()
        for want in (3, 4, 4):              # behind again, up to the most
            ahead.expect_more_like(cols)
            threading.Timer(0.25, release.set).start()
            ahead.take()
            assert ahead.depth == want
            while ahead._pending:
                ahead.take()
            release.clear()
    finally:
        release.set()
        ahead.close()


def test_abandoned_iterator_stops_the_column_helpers(numbered_parts):
    import threading

    # threads an earlier test file of this worker left behind (a substrate's
    # reservation server, a result router) are not the readers' to stop
    before = set(threading.enumerate())
    it = readers.tfrecord_batches(numbered_parts, 4, parse_fn=_image_parse,
                                  prefetch=2)
    next(it)
    next(it)
    assert any(t.name.startswith("tfos-columns")
               for t in threading.enumerate())
    it.close()
    deadline = time.time() + 10
    while time.time() < deadline:
        left = [t.name for t in threading.enumerate()
                if t not in before and t.name.startswith("tfos-")]
        if not left:
            break
        time.sleep(0.05)
    assert not left, left
