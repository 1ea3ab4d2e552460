"""Tests for the local Spark substrate (process-per-executor execution)."""

import os
import pickle
import time

import cloudpickle
import pytest

from tensorflowonspark_tpu import obs
from tensorflowonspark_tpu.sparkapi import (
    LocalSparkContext,
    LocalSparkSession,
    Row,
    StructField,
    StructType,
)
from tensorflowonspark_tpu.sparkapi.rdd import BATCH_ROWS
from tensorflowonspark_tpu.sparkapi.sql import infer_schema


@pytest.fixture(scope="module")
def sc():
    ctx = LocalSparkContext("local-cluster[3,1,1024]", "sparkapi-test")
    yield ctx
    ctx.stop()


# -- module-level functions (cloudpickle ships lambdas too, but these also
#    exercise the plain-pickle path) --


def _double(x):
    return x * 2


def _pid_of_partition(it):
    list(it)
    return [os.getpid()]


def test_parallelize_collect_ordering(sc):
    data = list(range(100))
    rdd = sc.parallelize(data, 7)
    assert rdd.getNumPartitions() == 7
    assert rdd.collect() == data


def test_map_filter_flatmap_chain(sc):
    rdd = sc.parallelize(range(10), 3)
    out = (
        rdd.map(_double)
        .filter(lambda x: x % 4 == 0)
        .flatMap(lambda x: [x, -x])
        .collect()
    )
    assert out == [y for x in range(10) if (2 * x) % 4 == 0 for y in (2 * x, -2 * x)]


def test_count_take_first(sc):
    rdd = sc.parallelize(range(11), 4)
    assert rdd.count() == 11
    assert rdd.take(3) == [0, 1, 2]
    assert rdd.first() == 0


def test_tasks_run_in_separate_processes(sc):
    rdd = sc.parallelize(range(3), 3)
    pids = rdd.mapPartitions(_pid_of_partition).collect()
    assert len(set(pids)) == 3, f"expected 3 distinct executor pids, got {pids}"
    assert os.getpid() not in pids


def test_mapPartitionsWithIndex(sc):
    rdd = sc.parallelize(range(6), 3)
    out = rdd.mapPartitionsWithIndex(lambda i, it: [(i, sorted(it))]).collect()
    assert out == [(0, [0, 1]), (1, [2, 3]), (2, [4, 5])]


def test_concurrent_barrier_across_executors(sc):
    """The property TFCluster depends on: an n-partition job on n executors
    runs all n tasks simultaneously, so a cross-task barrier completes."""
    from tensorflowonspark_tpu import reservation

    server = reservation.Server(count=3)
    addr = server.start()
    token = server.auth_token

    def barrier_task(it):
        part = list(it)
        c = reservation.Client(addr, token)
        c.register({"executor_id": part[0]})
        c.await_reservations(timeout=15)

    t0 = time.monotonic()
    sc.parallelize(range(3), 3).foreachPartition(barrier_task)
    assert time.monotonic() - t0 < 15
    assert len(server.await_reservations(timeout=1)) == 3
    server.stop()


def test_task_failure_propagates_with_traceback(sc):
    def boom(it):
        list(it)
        raise ValueError("synthetic failure in executor")

    with pytest.raises(RuntimeError, match="synthetic failure in executor"):
        sc.parallelize(range(3), 3).foreachPartition(boom)
    # context still usable after a failed job (no retry, but no poisoning)
    assert sc.parallelize(range(4), 2).count() == 4


def test_broadcast_and_closure_capture(sc):
    b = sc.broadcast({"scale": 10})
    out = sc.parallelize([1, 2, 3], 3).map(lambda x: x * b.value["scale"]).collect()
    assert out == [10, 20, 30]


def test_union_repartition_zipWithIndex(sc):
    a = sc.parallelize([1, 2], 1)
    b = sc.parallelize([3, 4], 1).map(_double)
    assert a.union(b).collect() == [1, 2, 6, 8]
    assert sorted(sc.parallelize(range(5), 5).repartition(2).collect()) == list(range(5))
    assert sc.parallelize(["a", "b"], 1).zipWithIndex().collect() == [("a", 0), ("b", 1)]


def test_executor_cwd_isolated(sc):
    cwds = sc.parallelize(range(3), 3).mapPartitions(
        lambda it: [os.getcwd() if list(it) else None]
    ).collect()
    assert len(set(cwds)) == 3
    assert all("executor_" in c for c in cwds)


def test_master_string_parsing():
    assert LocalSparkContext("local", "t").num_executors == 1
    ctx = LocalSparkContext("local[2]", "t")
    assert ctx.num_executors == 2
    ctx.stop()
    with pytest.raises(ValueError):
        LocalSparkContext("yarn", "t")


# -- the partition's hand-over: row-batch pickles made once, unpickled under
#    the task's iterator --


def _counter(name):
    return obs.counter(name).value


def _identity(it):
    return it


def _noop(it):
    for _ in it:
        pass


def _local_point_class():
    # a class no module exports: plain pickle refuses its instances,
    # cloudpickle ships the class by value
    class Point:
        def __init__(self, i):
            self.i = i

    return Point


def _make_rows(kind, n):
    if kind == "Row":
        return [Row(id=i, cat=[i, i + 1], dense=[i / 2]) for i in range(n)]
    if kind == "tuple":
        return [(i, [i, i + 1], i / 2) for i in range(n)]
    point = _local_point_class()
    return [point(i) for i in range(n)]


def _plain(kind, rows):
    return [r.i for r in rows] if kind == "cloudpickle_only" else rows


@pytest.mark.parametrize("kind", ["Row", "tuple", "cloudpickle_only"])
@pytest.mark.parametrize("n", [0, 1, 1023, 1024, 1025, 65536])
def test_partition_rows_reach_the_task_in_order(sc, kind, n):
    rows = _make_rows(kind, n)
    if kind == "cloudpickle_only" and n:
        with pytest.raises((pickle.PicklingError, AttributeError)):
            pickle.dumps(rows[:1])
    rdd = sc.parallelize(rows, 1)
    assert rdd.getNumPartitions() == 1
    sent = _counter("spark_partition_batches_sent_total")
    got = rdd.mapPartitions(_identity).collect()
    assert (_counter("spark_partition_batches_sent_total") - sent
            == -(-n // BATCH_ROWS))
    assert _plain(kind, got) == _plain(kind, rows)
    # and equal what one pickle of the whole partition gives
    assert _plain(kind, got) == _plain(
        kind, cloudpickle.loads(cloudpickle.dumps(rows)))


class _CountsLoads:
    """A row that counts, in the process that unpickles it, how many of
    its kind have been unpickled there."""

    loaded = 0

    def __init__(self, i):
        self.i = i

    def __getstate__(self):
        return {"i": self.i}

    def __setstate__(self, state):
        type(self).loaded += 1
        self.i = state["i"]


def _loads_seen_at_first_row(it):
    before = _CountsLoads.loaded
    first = next(it)
    at_first = _CountsLoads.loaded - before
    rest = sum(1 for _ in it)
    return [(first.i, at_first, 1 + rest, _CountsLoads.loaded - before)]


def test_first_row_comes_after_one_batch_is_unpickled(sc):
    n = 3 * BATCH_ROWS + 7
    rdd = sc.parallelize([_CountsLoads(i) for i in range(n)], 1)
    [(first, at_first, rows, at_end)] = rdd.mapPartitions(
        _loads_seen_at_first_row).collect()
    assert first == 0 and rows == n and at_end == n
    assert 0 < at_first <= BATCH_ROWS


def _refuse_to_load():
    raise ValueError("synthetic failure while a row unpickles")


class _FailsToLoad:
    def __reduce__(self):
        return (_refuse_to_load, ())


def test_unpickling_error_in_a_later_batch_fails_the_job(sc):
    rows = list(range(4 * BATCH_ROWS))
    rows[2 * BATCH_ROWS + 5] = _FailsToLoad()
    t0 = time.monotonic()
    with pytest.raises(RuntimeError) as err:
        sc.parallelize(rows, 1).foreachPartition(_noop)
    assert time.monotonic() - t0 < 60
    # the executor's own traceback: through the task's iterator
    assert "synthetic failure while a row unpickles" in str(err.value)
    assert "executor.py" in str(err.value) and "_rows" in str(err.value)
    assert sc.parallelize(range(4), 2).count() == 4


@pytest.mark.parametrize("derive", ["same_rdd", "map_list"])
def test_second_job_sends_without_pickling(sc, derive):
    rdd = sc.parallelize([(i, i) for i in range(5000)], 2)
    reused = _counter("spark_partition_blobs_reused_total")
    sent = _counter("spark_partition_batches_sent_total")
    rdd.foreachPartition(_noop)
    assert _counter("spark_partition_blobs_reused_total") == reused
    assert _counter("spark_partition_batches_sent_total") - sent == 6
    again = rdd if derive == "same_rdd" else rdd.map(list)
    again.foreachPartition(_noop)
    assert _counter("spark_partition_blobs_reused_total") - reused == 2
    assert _counter("spark_partition_batches_sent_total") - sent == 12
    want = [[i, i] for i in range(5000)]
    assert rdd.map(list).collect() == want


@pytest.mark.parametrize("how", ["cache", "union", "repartition"])
def test_new_rows_never_meet_stale_blobs(sc, how):
    base = sc.parallelize(range(3000), 2)
    base.foreachPartition(_noop)          # base's blobs are made
    doubled = [2 * x for x in range(3000)]
    if how == "cache":
        new = base.map(_double).cache()
        assert new.count() == 3000        # resolves the chain
        want = doubled
    elif how == "union":
        new = base.map(_double).union(base)
        want = doubled + list(range(3000))
    else:
        new = base.map(_double).repartition(3)
        want = doubled
    reused = _counter("spark_partition_blobs_reused_total")
    assert new.mapPartitions(_identity).collect() == want
    assert _counter("spark_partition_blobs_reused_total") == reused
    assert base.mapPartitions(_identity).collect() == list(range(3000))


def test_mutating_a_collect_result_changes_no_later_job(sc):
    rdd = sc.parallelize([[i] for i in range(2000)], 2)
    for got in (rdd.collect(), rdd.take(5),
                rdd.mapPartitions(_identity).collect()):
        for row in got:
            row.append("mutated")
        got.clear()
    want = [[i] for i in range(2000)]
    assert rdd.mapPartitions(_identity).collect() == want
    assert rdd.collect() == want


# -- DataFrame layer --


@pytest.fixture(scope="module")
def spark(sc):
    return LocalSparkSession(sc)


def test_create_dataframe_infer_schema(spark):
    df = spark.createDataFrame(
        [(1, 2.5, "a"), (2, 3.5, "b")], schema=["id", "val", "name"]
    )
    assert df.dtypes == [("id", "bigint"), ("val", "double"), ("name", "string")]
    rows = df.collect()
    assert rows[0].id == 1 and rows[1].name == "b"
    assert df.count() == 2


def test_dataframe_select(spark):
    df = spark.createDataFrame([(1, "x"), (2, "y")], schema=["k", "v"])
    sel = df.select("v")
    assert sel.columns == ["v"]
    assert [r.v for r in sel.collect()] == ["x", "y"]


def test_dataframe_from_rows_and_rdd(spark):
    rows = [Row(a=1, b=[1.0, 2.0]), Row(a=2, b=[3.0, 4.0])]
    df = spark.createDataFrame(rows)
    assert df.dtypes == [("a", "bigint"), ("b", "array<double>")]
    rdd_df = spark.createDataFrame(spark.sparkContext.parallelize(rows))
    assert rdd_df.count() == 2


def test_infer_schema_binary_and_bool():
    st = infer_schema({"flag": True, "blob": b"xyz"})
    assert st == StructType(
        [StructField("flag", "boolean"), StructField("blob", "binary")]
    )


def test_row_access_patterns():
    r = Row(x=1, y="s")
    assert r.x == 1 and r["y"] == "s" and r[0] == 1
    assert r.asDict() == {"x": 1, "y": "s"}
    assert list(r) == [1, "s"]
