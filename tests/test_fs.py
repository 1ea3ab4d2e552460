"""Remote-filesystem abstraction: record I/O through registered schemes
(VERDICT r2 task 3 / SURVEY §3.5 — the Hadoop-FileSystem-API seam)."""

import io
import os

import pytest

from tensorflowonspark_tpu import fs, readers, tfrecord


class MemFS(fs.FileSystem):
    """In-memory filesystem for a mock scheme (``mock://…``)."""

    def __init__(self):
        self.files: dict[str, bytes] = {}
        self.dirs: set[str] = set()

    def open(self, path, mode="rb"):
        if "w" in mode:
            buf = io.BytesIO()
            outer = self

            class W(io.BytesIO):
                def close(self_inner):
                    outer.files[path] = self_inner.getvalue()
                    super().close()

            return W()
        if path not in self.files:
            raise FileNotFoundError(path)
        return io.BytesIO(self.files[path])

    def listdir(self, path):
        prefix = path.rstrip("/") + "/"
        return sorted({p[len(prefix):].split("/")[0]
                       for p in self.files if p.startswith(prefix)})

    def exists(self, path):
        return path in self.files or path in self.dirs

    def makedirs(self, path):
        self.dirs.add(path)

    def glob(self, pattern):
        import fnmatch

        return sorted(p for p in self.files if fnmatch.fnmatch(p, pattern))


@pytest.fixture()
def memfs():
    m = MemFS()
    fs.register("mock", m)
    yield m
    fs.unregister("mock")


def test_local_glob_and_file_scheme(tmp_path):
    p = tmp_path / "part-00000"
    p.write_bytes(b"x")
    assert fs.glob(str(tmp_path / "part-*")) == [str(p)]
    got = fs.glob(f"file://{tmp_path}/part-*")
    assert got == [f"file://{p}"]
    with fs.open(f"file://{p}") as f:
        assert f.read() == b"x"
    assert fs.exists(f"file://{p}")


def test_join_preserves_scheme():
    assert fs.join("hdfs://nn:8020/data", "part-0") == "hdfs://nn:8020/data/part-0"
    assert fs.join("/tmp/x", "y") == os.path.join("/tmp/x", "y")


def test_unknown_scheme_clear_error():
    with pytest.raises(OSError, match="register"):
        fs.get_fs("zzzz://bucket/x").open("zzzz://bucket/x")


def test_tfrecord_roundtrip_through_mock_scheme(memfs):
    path = "mock://bucket/data/part-r-00000"
    payloads = [b"alpha", b"beta", b"gamma"]
    n = tfrecord.write_records(path, iter(payloads))
    assert n == 3
    assert list(tfrecord.read_records(path)) == payloads


def test_readers_pipeline_through_mock_scheme(memfs):
    for part in range(2):
        tfrecord.write_records(
            f"mock://bucket/data/part-{part:05d}",
            (tfrecord.encode_example({"v": (tfrecord.INT64_LIST, [part * 10 + i])})
             for i in range(4)),
        )
    shard = readers.shard_files("mock://bucket/data/part-*", 0, 1)
    assert len(shard) == 2
    got = []
    for batch in readers.tfrecord_batches(shard, 3, prefetch=2):
        got.extend(int(v[0]) for v in batch["v"])
    assert sorted(got) == [0, 1, 2, 3, 10, 11, 12, 13]


def test_dfutil_roundtrip_file_scheme(tmp_path):
    """Scheme-qualified dirs flow through the real save/load job path."""
    from tensorflowonspark_tpu import dfutil
    from tensorflowonspark_tpu.sparkapi import get_spark_context
    from tensorflowonspark_tpu.sparkapi.sql import LocalSparkSession

    sc = get_spark_context("local[2]", "fs-roundtrip")
    try:
        spark = LocalSparkSession(sc)
        df = spark.createDataFrame(
            [(i, float(i) / 2, f"s{i}") for i in range(6)],
            ["a", "b", "c"],
        ).repartition(2)
        out = f"file://{tmp_path}/tfr"
        dfutil.saveAsTFRecords(df, out)
        assert (tmp_path / "tfr" / "part-r-00000").exists()
        back = dfutil.loadTFRecords(sc, out)
        rows = sorted(back.collect(), key=lambda r: r["a"])
        assert len(rows) == 6
        assert rows[3]["c"] == "s3"
    finally:
        sc.stop()


def test_local_path_helper():
    assert fs.local_path("/tmp/x") == "/tmp/x"
    assert fs.local_path("file:///tmp/x") == "/tmp/x"
    assert fs.local_path("gs://bucket/x") is None


# ---------------------------------------------------------------------------
# Persistent compile cache on the fs seam (compile_cache.py round-trip)
# ---------------------------------------------------------------------------


def _fsspec_memory_ns(tag):
    pytest.importorskip("fsspec")
    ns = f"memory://tfos-cc-{tag}/ns"
    fs.makedirs(ns)
    return ns


def test_compile_cache_entries_roundtrip_through_fsspec_memory(tmp_path):
    """push_entries → pull_entries through a real FsspecFS scheme: one
    process's spool entries land remotely with digest sidecars and a
    second process's fresh spool receives byte-identical copies — the
    'one replica compiles, the fleet loads' transport."""
    from tensorflowonspark_tpu import compile_cache

    remote = _fsspec_memory_ns("roundtrip")
    spool_a = tmp_path / "spool_a"
    spool_a.mkdir()
    (spool_a / "jit_f-0abc-cache").write_bytes(b"executable-a" * 100)
    (spool_a / "jit_g-1def-cache").write_bytes(b"executable-b" * 100)
    (spool_a / "not-an-entry.txt").write_bytes(b"ignored")

    pushed = set()
    assert compile_cache.push_entries(str(spool_a), remote, pushed) == 2
    assert pushed == {"jit_f-0abc-cache", "jit_g-1def-cache"}
    assert fs.exists(fs.join(remote, "jit_f-0abc-cache.sha256"))
    # re-push is a no-op (the pushed set remembers)
    assert compile_cache.push_entries(str(spool_a), remote, pushed) == 0

    spool_b = tmp_path / "spool_b"
    spool_b.mkdir()
    got = compile_cache.pull_entries(remote, str(spool_b))
    assert got == {"pulled": 2, "corrupt": 0, "skipped": 0}
    assert (spool_b / "jit_f-0abc-cache").read_bytes() == \
        (spool_a / "jit_f-0abc-cache").read_bytes()
    # a second pull is a no-op (entries already spooled)
    assert compile_cache.pull_entries(remote, str(spool_b))["pulled"] == 0
    # and the puller marks remote entries as pushed so a shared spool
    # never echoes them back
    spool_c = tmp_path / "spool_c"
    spool_c.mkdir()
    pushed_b: set = set()
    compile_cache.pull_entries(remote, str(spool_c), pushed=pushed_b)
    assert "jit_f-0abc-cache" in pushed_b


def test_compile_cache_corrupt_and_halfwritten_entries_rejected(tmp_path):
    """The rejection path: a digest-mismatched remote entry is REFUSED
    (counted + warned, never spooled for XLA to load) and an entry with
    no sidecar yet (a mid-write on shared fs) is skipped, not an error."""
    from tensorflowonspark_tpu import compile_cache, obs

    remote = _fsspec_memory_ns("corrupt")
    spool_a = tmp_path / "spool_a"
    spool_a.mkdir()
    (spool_a / "jit_ok-cache").write_bytes(b"good" * 50)
    (spool_a / "jit_bad-cache").write_bytes(b"fine-at-push" * 50)
    compile_cache.push_entries(str(spool_a), remote, set())

    # corrupt jit_bad AFTER its sidecar was written (bit rot / truncated
    # rewrite): payload no longer matches the digest
    with fs.open(fs.join(remote, "jit_bad-cache"), "wb") as f:
        f.write(b"damaged")
    # and a half-written entry: payload present, sidecar not yet
    with fs.open(fs.join(remote, "jit_half-cache"), "wb") as f:
        f.write(b"still-being-written")

    corrupt_counter = obs.counter("serving_compile_cache_disk_corrupt_total")
    c0 = corrupt_counter.value
    spool_b = tmp_path / "spool_b"
    spool_b.mkdir()
    pushed_b: set = set()
    got = compile_cache.pull_entries(remote, str(spool_b), pushed=pushed_b)
    assert got == {"pulled": 1, "corrupt": 1, "skipped": 1}
    assert (spool_b / "jit_ok-cache").exists()
    assert not (spool_b / "jit_bad-cache").exists()
    assert not (spool_b / "jit_half-cache").exists()
    assert corrupt_counter.value - c0 == 1

    # repair: a rejected entry is NOT marked pushed, so the process that
    # later produces a good local copy (recompile) overwrites the remote
    assert "jit_bad-cache" not in pushed_b
    assert "jit_ok-cache" in pushed_b  # verified copies never re-push
    (spool_b / "jit_bad-cache").write_bytes(b"recompiled-good" * 20)
    assert compile_cache.push_entries(str(spool_b), remote,
                                      pushed_b) == 1
    spool_d = tmp_path / "spool_d"
    spool_d.mkdir()
    got2 = compile_cache.pull_entries(remote, str(spool_d))
    assert got2["corrupt"] == 0
    assert (spool_d / "jit_bad-cache").read_bytes() == \
        b"recompiled-good" * 20


def test_compile_cache_remote_namespace_configures_spool(tmp_path,
                                                         monkeypatch):
    """ensure() against a remote scheme: jax is pointed at a LOCAL spool
    (the LRU cache cannot speak fsspec) at a fixed per-namespace path, the
    remote namespace is created through fs.py, and pre-existing remote
    entries are pulled in."""
    pytest.importorskip("fsspec")
    from tensorflowonspark_tpu import compile_cache

    root = "memory://tfos-cc-ensure"
    # pre-seed the topology namespace with one valid remote entry
    monkeypatch.setenv("TFOS_COMPILE_CACHE_DIR", root)
    monkeypatch.delenv("TFOS_COMPILE_CACHE", raising=False)
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    monkeypatch.setattr(compile_cache, "SPOOL_DIR", str(tmp_path / "spools"))
    compile_cache.disable()
    try:
        ns = fs.join(root, compile_cache.topology_key())
        fs.makedirs(ns)
        seed_spool = tmp_path / "seed"
        seed_spool.mkdir()
        (seed_spool / "jit_seed-cache").write_bytes(b"seeded" * 10)
        compile_cache.push_entries(str(seed_spool), ns, set())

        got_ns = compile_cache.ensure()
        assert got_ns == ns
        st = compile_cache.stats()
        assert st["remote"] is True
        import jax

        spool = jax.config.jax_compilation_cache_dir
        assert spool and os.path.isdir(spool)
        assert os.path.dirname(spool) == str(tmp_path / "spools")
        assert fs.local_path(spool) == spool  # jax got a LOCAL dir
        assert (os.path.join(spool, "jit_seed-cache")) and \
            os.path.exists(os.path.join(spool, "jit_seed-cache"))

        # a new local entry syncs back through the fs seam
        with open(os.path.join(spool, "jit_new-cache"), "wb") as f:
            f.write(b"fresh" * 10)
        assert compile_cache.sync() == 1
        assert fs.exists(fs.join(ns, "jit_new-cache"))
        assert fs.exists(fs.join(ns, "jit_new-cache.sha256"))
    finally:
        compile_cache.disable()
