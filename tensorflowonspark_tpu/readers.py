"""InputMode.TENSORFLOW input pipeline: sharded, parallel, prefetched
TFRecord and Parquet (Arrow columnar) reading.

Reference anchor: in the reference this layer *is* ``tf.data`` —
``TFRecordDataset(files).shard(num_workers, task_index).shuffle(...).
interleave(..., num_parallel_reads=args.readers).batch(...).prefetch(...)``
as hand-written in each example's ``map_fun`` (``SURVEY.md §2.1`` TFCluster
``InputMode.TENSORFLOW``; the ``readers`` knob is ``pipeline.py::HasReaders``).
The TPU rebuild has no TensorFlow, so the same pipeline is built from
threads + queues over :mod:`tensorflowonspark_tpu.tfrecord`:

- **file sharding** by ``task_index`` stride (every node reads a disjoint
  subset of part files — the file-level auto-shard the reference relied on);
- **parallel readers**: ``readers`` threads interleave records from several
  files at once (I/O-bound decode overlaps);
- **shuffle**: a bounded reservoir of records, files reshuffled per epoch;
- **columns filled while parsing**: a NumPy array or scalar that
  ``parse_fn`` returns is copied once, straight into its row of the
  batch's column array; only values whose dtype the first row cannot
  promise (Python lists, numbers, ``bytes``) are collected and stacked
  after the batch's last record.  Every batch gets arrays of its own, and
  the arrays of the batches to come are made, and their pages first
  written, on helper threads beside the parse (as many as it takes to
  keep ahead of it, four at most);
- **prefetch**: batches are built (and optionally ``device_put`` into
  HBM) in a pipeline thread ``prefetch`` batches ahead of the consumer, so
  step time approaches ``max(compute, feed)`` instead of their sum
  (``SURVEY.md §3.2`` perf-critical path / hard part (b)).

:func:`parquet_batches` is the Arrow-columnar sibling (``SURVEY.md §2.2``):
row groups decode straight to column buffers — no per-row hot loop at all —
through the same prefetch/``device_put`` machinery.

Everything is pull-based and bounded; no unbounded buffering.
"""

from __future__ import annotations

import collections
import concurrent.futures
import contextlib
import itertools
import logging
import mmap
import queue as _queue_mod
import threading
import time as _time_mod
from typing import Any, Callable, Iterator, Sequence

import numpy as np

from tensorflowonspark_tpu import fs, obs, tfrecord

logger = logging.getLogger(__name__)

_END = object()  # sentinel: a producer finished
_NO_SPAN = contextlib.nullcontext()


def shard_files(
    files: Sequence[str] | str, task_index: int, num_shards: int
) -> list[str]:
    """Deterministic ``task_index``-strided file shard for one node.

    ``files`` may be a list or a glob pattern (scheme paths like
    ``hdfs://…/part-*`` resolve through :mod:`tensorflowonspark_tpu.fs`).
    Sorting before striding makes every node's view consistent without
    coordination (same trick the reference's examples used with ``tf.data``
    auto-shard by file).
    """
    if isinstance(files, str):
        files = fs.glob(files)
    ordered = sorted(files)
    if num_shards <= 1:
        return ordered
    return ordered[task_index::num_shards]


def default_parse(payload: bytes) -> dict[str, Any]:
    """Decode a ``tf.train.Example`` into ``{name: list-of-values}``."""
    return {k: v for k, (_, v) in tfrecord.decode_example(payload).items()}


def _is_numpy_value(value: Any) -> bool:
    """A plain NumPy array or scalar of a native numeric or bool dtype: the
    values for which ``np.asarray`` over equal rows keeps dtype and shape,
    so that one row can size the whole column."""
    return ((type(value) is np.ndarray or isinstance(value, np.generic))
            and value.dtype.kind in "biufc" and value.dtype.isnative)


def _fits(col: np.ndarray, value: Any) -> bool:
    """``value`` can be a row of the array column ``col`` as it is."""
    return (_is_numpy_value(value) and value.dtype == col.dtype
            and value.shape == col.shape[1:])


def _fill_columns(rows: Iterator[dict[str, Any]], batch_size: int,
                  take_made: Callable[[], dict[str, np.ndarray]]
                  ) -> tuple[int, dict[str, np.ndarray | list]]:
    """Consume ``rows`` (at most ``batch_size``) into columns, and return
    their number with the columns.

    A column whose first value is a NumPy value is an array of
    ``batch_size`` rows that each row is copied into as it arrives (the row
    is still in cache, and is dropped before the next is parsed): the one
    that ``take_made()``, asked once there is a row, holds under its name
    if that fits the value, else a new one.  Any other column is the list
    of its values, for ``np.asarray`` to type across all of them.  An array
    column that meets a value of another dtype or shape turns back into the
    list of its rows so far and goes on as one, so every column ends as
    ``np.asarray([r[name] for r in rows])`` would.
    """
    n = 0
    cols: dict[str, np.ndarray | list] = {}
    for row in rows:
        if n == 0:
            made = take_made()
            for name, value in row.items():
                if not _is_numpy_value(value):
                    cols[name] = []
                    continue
                col = made.get(name)
                if col is None or not _fits(col, value):
                    col = np.empty((batch_size, *value.shape), value.dtype)
                cols[name] = col
        for name, col in cols.items():
            value = row[name]
            if type(col) is list:
                col.append(value)
            elif _fits(col, value):
                col[n] = value
            else:
                cols[name] = [*col[:n], value]
        n += 1
    return n, cols


class _ColumnsAhead:
    """The array columns of the batches to come, each made new and written
    to once a page on helper threads.

    The first write to memory fresh from the system is the dearest part of
    filling a batch — a page fault every 4 KB, three quarters of the time a
    77 MB batch took on the benchmark's host (``PERF.md``, PR 25) — and it
    needs no record, so it is done ahead, beside the parse.  Every batch
    still gets arrays of its own: nothing here is used twice.
    """

    MAX_DEPTH = 4  # batches made ahead at most, one a helper thread

    def __init__(self) -> None:
        self._pool: concurrent.futures.ThreadPoolExecutor | None = None
        self._pending: collections.deque = collections.deque()
        self.depth = 1

    @staticmethod
    def _make(spec: dict[str, tuple]) -> dict[str, np.ndarray]:
        made = {}
        for name, (shape, dtype) in spec.items():
            col = made[name] = np.empty(shape, dtype)
            # one write a page maps it; NumPy drops the GIL for the loop
            col.reshape(-1)[::max(1, mmap.PAGESIZE // col.itemsize)] = 0
        return made

    def take(self) -> dict[str, np.ndarray]:
        """The columns made for the batch that starts now; none before a
        batch has shown what columns there are.  Waiting for a helper that
        has not finished costs no more than making them here, and says that
        the helpers are behind the parse: one more from now on."""
        if not self._pending:
            return {}
        made = self._pending.popleft()
        if not made.done() and self.depth < self.MAX_DEPTH:
            self.depth += 1
        return made.result()

    def expect_more_like(self, cols: dict[str, np.ndarray | list]) -> None:
        """Have the next batches' columns made like this batch's arrays.
        Should the rows change, what was made for the old ones does not fit
        and is dropped column by column in :func:`_fill_columns`."""
        spec = {name: (col.shape, col.dtype) for name, col in cols.items()
                if type(col) is not list}
        if not spec:
            return
        if self._pool is None:
            self._pool = concurrent.futures.ThreadPoolExecutor(
                self.MAX_DEPTH, thread_name_prefix="tfos-columns")
        while len(self._pending) < self.depth:
            self._pending.append(self._pool.submit(self._make, spec))

    def close(self) -> None:
        if self._pool is not None:
            self._pool.shutdown(wait=False, cancel_futures=True)


def _finish_columns(n: int, cols: dict[str, np.ndarray | list]
                    ) -> dict[str, np.ndarray]:
    """What is left to make ``n`` filled rows a batch: ``np.asarray`` over
    a list column, and a copy of a short last batch's rows (one copy an
    epoch, so that the batch does not pin the tail of a whole block)."""
    batch: dict[str, np.ndarray] = {}
    direct = 0
    for name, col in cols.items():
        if type(col) is list:
            batch[name] = np.asarray(col)
        else:
            batch[name] = col if n == len(col) else col[:n].copy()
            direct += 1
    obs.counter("reader_columns_direct_total").inc(direct)
    obs.counter("reader_columns_stacked_total").inc(len(cols) - direct)
    return batch


class _ReaderPool:
    """``readers`` threads pulling files off a queue, records into a queue."""

    def __init__(self, files: list[str], readers: int, capacity: int):
        self._files: _queue_mod.Queue = _queue_mod.Queue()
        for f in files:
            self._files.put(f)
        self.records: _queue_mod.Queue = _queue_mod.Queue(maxsize=capacity)
        self._n = max(1, readers)
        self._stop = threading.Event()
        # reader exceptions land here; _record_stream re-raises after all
        # producers finish so a corrupt file fails the dataset instead of
        # silently truncating it
        self.errors: list[BaseException] = []
        self._threads = [
            threading.Thread(target=self._read_loop, daemon=True,
                             name=f"tfos-reader-{i}")
            for i in range(self._n)
        ]
        for t in self._threads:
            t.start()

    def _put(self, item) -> bool:
        """Blocking put that gives up once the pool is stopped (so producers
        never wedge on a full queue after the consumer has gone away)."""
        while not self._stop.is_set():
            try:
                self.records.put(item, timeout=0.1)
                return True
            except _queue_mod.Full:
                continue
        return False

    def _read_loop(self) -> None:
        try:
            while not self._stop.is_set():
                try:
                    path = self._files.get_nowait()
                except _queue_mod.Empty:
                    break
                for payload in tfrecord.read_records(path):
                    if not self._put(payload):
                        return
        except BaseException as e:
            logger.exception("reader thread failed")
            self.errors.append(e)
        finally:
            # after stop() nobody counts sentinels, so dropping it is fine
            self._put(_END)

    @property
    def n_producers(self) -> int:
        return self._n

    def stop(self) -> None:
        self._stop.set()
        for t in self._threads:
            t.join(timeout=5.0)


def _record_stream(files: list[str], readers: int,
                   shuffle_buffer: int, rng) -> Iterator[bytes]:
    """Interleaved (and optionally shuffled) record payloads from files."""
    if readers <= 1 and shuffle_buffer <= 0:
        for path in files:
            yield from tfrecord.read_records(path)
        return

    pool = _ReaderPool(files, readers, capacity=max(64, 2 * shuffle_buffer))
    try:
        live = pool.n_producers
        buf: list[bytes] = []
        while live > 0:
            item = pool.records.get()
            if item is _END:
                live -= 1
                continue
            if shuffle_buffer > 0:
                buf.append(item)
                if len(buf) >= shuffle_buffer:
                    i = rng.integers(0, len(buf))
                    buf[i], buf[-1] = buf[-1], buf[i]
                    yield buf.pop()
            else:
                yield item
        if pool.errors:  # a reader died: fail, don't silently truncate
            raise pool.errors[0]
        if shuffle_buffer > 0:
            rng.shuffle(buf)
            yield from buf
    finally:
        pool.stop()


def tfrecord_batches(
    files: Sequence[str] | str,
    batch_size: int,
    *,
    parse_fn: Callable[[bytes], dict[str, Any]] | None = None,
    num_epochs: int = 1,
    readers: int = 1,
    shuffle_buffer: int = 0,
    shuffle_files: bool = False,
    seed: int = 0,
    drop_remainder: bool = False,
    prefetch: int = 2,
    device_put: bool | Callable[[dict[str, Any]], dict[str, Any]] = False,
) -> Iterator[dict[str, Any]]:
    """Yield columnar batches from TFRecord files.

    ``files`` should already be this node's shard (see :func:`shard_files`).
    ``readers`` maps the reference's ``HasReaders`` param; ``prefetch`` is
    the number of ready batches staged ahead (0 = fully synchronous);
    ``device_put=True`` stages each batch onto the default JAX device from
    the pipeline thread — the double-buffered host→HBM path.  ``device_put``
    may also be a callable applied to each columnar batch (e.g.
    ``Trainer.shard`` to stage with mesh shardings).

    Every column equals ``np.asarray([parse_fn(p)[name] for p in batch])``.
    A ``parse_fn`` that returns NumPy arrays or scalars (numeric or bool)
    has each value copied once, into the batch's own array, as its record
    is parsed; other values (``default_parse``'s lists, Python numbers,
    ``bytes``) are stacked after the last record.  Nothing selects between
    the two but the values themselves.  The arrays are new in every batch;
    those of the next batches are made ahead by up to four helper threads
    (:class:`_ColumnsAhead`), because the first write to fresh memory costs
    more than the copy.  Spans: ``reader.batch`` (attrs ``records``,
    ``bytes``, ``ahead`` = batches being made ahead) > ``reader.parse``
    (any wait for the arrays made ahead + read + ``parse_fn`` + the rows'
    copies), ``reader.stack`` (what is then left: ``np.asarray`` of list
    columns, the trim of a short last batch), ``feed.stage``.  Counters,
    one increment a column a batch: ``reader_columns_direct_total``,
    ``reader_columns_stacked_total``.
    """
    if isinstance(files, str):
        files = fs.glob(files)
    files = list(files)
    if not files:
        return
    parse = parse_fn or default_parse
    rng = np.random.default_rng(seed)

    def read_batch(stream: Iterator[bytes]) -> dict[str, Any] | None:
        """The next batch of the epoch, staged, or None at its end.  One
        ``reader.batch`` span a batch, whose children split it: read +
        parse of its records into the columns, what is left to stack,
        the staging."""
        with obs.span("reader.batch") as sp:
            with obs.span("reader.parse") as parse_sp:
                n, cols = _fill_columns(
                    map(parse, itertools.islice(stream, batch_size)),
                    batch_size, ahead.take)
                if not n:
                    parse_sp.cancel()
            ahead.expect_more_like(cols)
            if not n or (n < batch_size and drop_remainder):
                if not n:
                    sp.cancel()
                return None
            obs.counter("reader_records_total").inc(n)
            with obs.span("reader.stack"):
                batch = _finish_columns(n, cols)
            sp.set(records=n, ahead=ahead.depth,
                   bytes=sum(int(c.nbytes) for c in batch.values()))
            with obs.span("feed.stage"):
                return _stage(batch)

    def batch_gen() -> Iterator[dict[str, Any]]:
        for epoch in range(num_epochs):
            epoch_files = list(files)
            if shuffle_files:
                np.random.default_rng(seed + epoch).shuffle(epoch_files)
            # the epoch is recorded with its two ends read here, NOT as a
            # `with obs.span(...)` around the loop: a generator suspends
            # inside the with-block at every yield, which would leave
            # "readers.epoch" on the CONSUMER thread's span stack and
            # mis-parent unrelated spans recorded between batches (and an
            # abandoned iterator might never pop it at all).  The batch's
            # own spans close before its yield.
            t0_wall, t0 = _time_mod.time(), _time_mod.perf_counter()
            stream = _record_stream(epoch_files, readers, shuffle_buffer,
                                    rng)
            try:
                while (batch := read_batch(stream)) is not None:
                    yield batch
            finally:
                stream.close()  # → pool.stop(), also when abandoned
            obs.complete("readers.epoch", t0_wall,
                         _time_mod.perf_counter() - t0,
                         epoch=epoch, files=len(epoch_files))

    _stage = _stager(device_put)
    ahead = _ColumnsAhead()
    try:
        yield from prefetched(batch_gen, prefetch, spans=True)
    finally:
        ahead.close()  # after the pump has stopped: its helpers go too


def _stager(device_put) -> Callable[[dict[str, Any]], dict[str, Any]]:
    """Batch-staging function from the ``device_put`` option: ``False`` =
    host arrays, ``True`` = default-device ``jax.device_put``, callable =
    custom staging (e.g. ``Trainer.shard`` — device_put with the mesh
    shardings).  Runs in the pipeline thread, overlapping H2D with
    compute."""
    if callable(device_put):
        return device_put
    if device_put:
        def _put(batch: dict[str, Any]) -> dict[str, Any]:
            import jax

            return {k: jax.device_put(v) for k, v in batch.items()}

        return _put
    return lambda batch: batch


def prefetched(batch_gen_fn: Callable[[], Iterator[Any]],
               prefetch: int, spans: bool = False) -> Iterator[Any]:
    """Run ``batch_gen_fn()`` in a pipeline thread, ``prefetch`` items ahead.

    ``spans`` (the training readers set it) records the two sides of the
    hand-off: ``feed.pump_blocked`` while the producer waits on a full
    queue, ``feed.wait`` while the consumer waits on an empty one.

    ``prefetch <= 0`` degrades to the plain generator.  Producer exceptions
    re-raise on the consumer side; abandoning the iterator (break /
    GeneratorExit) stops the pump and the underlying generator's cleanup
    (``finally`` blocks, reader pools) runs promptly.

    Public because it is the ONE pump of the framework: the TFRecord/Parquet
    training readers below and the serving data plane
    (``pipeline._RunModel`` — batch N+1 assembled and ``device_put`` while
    batch N computes) all double-buffer through it.
    """
    if prefetch <= 0:
        yield from batch_gen_fn()
        return

    out: _queue_mod.Queue = _queue_mod.Queue(maxsize=prefetch)
    err: list[BaseException] = []
    abandoned = threading.Event()  # consumer gave up (break / GeneratorExit)

    def pump() -> None:
        gen = batch_gen_fn()
        try:
            for b in gen:
                with (obs.span("feed.pump_blocked", depth=out.qsize())
                      if spans else _NO_SPAN):
                    while not abandoned.is_set():
                        try:
                            out.put(b, timeout=0.1)
                            break
                        except _queue_mod.Full:
                            continue
                if abandoned.is_set():
                    return
        except BaseException as e:  # surfaced on the consumer side
            err.append(e)
        finally:
            gen.close()  # runs the source's finally → pool.stop()
            # The sentinel MUST reach a live consumer even when the queue is
            # momentarily full of staged batches; dropping it is only safe
            # once the consumer has abandoned the iterator.
            while True:
                try:
                    out.put(_END, timeout=0.1)
                    break
                except _queue_mod.Full:
                    if abandoned.is_set():
                        break

    t = threading.Thread(target=pump, daemon=True, name="tfos-prefetch")
    t.start()
    try:
        while True:
            with (obs.span("feed.wait", depth=out.qsize())
                  if spans else _NO_SPAN):
                item = out.get()
            if item is _END:
                break
            yield item
    finally:
        abandoned.set()
        while True:  # drain so a blocked timed put wakes promptly
            try:
                out.get_nowait()
            except _queue_mod.Empty:
                break
        t.join(timeout=10.0)
    if err:
        raise err[0]


def parquet_batches(
    files: Sequence[str] | str,
    batch_size: int,
    *,
    columns: Sequence[str] | None = None,
    num_epochs: int = 1,
    shuffle_files: bool = False,
    seed: int = 0,
    drop_remainder: bool = False,
    prefetch: int = 2,
    device_put: bool | Callable[[dict[str, Any]], dict[str, Any]] = False,
) -> Iterator[dict[str, Any]]:
    """Yield columnar batches straight from Parquet row groups.

    The Arrow→HBM path (``SURVEY.md §2.2``: "columnar (Arrow/Parquet)→HBM
    path, the idiomatic 2026 choice"): row groups decode to Arrow column
    buffers and convert to NumPy without any per-row Python work — there is
    no row-at-a-time hot loop anywhere on this path, unlike the reference's
    pickled-row queues (``SURVEY.md §3.2``).  Shares the prefetch pipeline
    thread and ``device_put`` staging with :func:`tfrecord_batches`, so
    batch N+1 moves host→HBM while batch N trains.

    ``files`` should already be this node's shard (:func:`shard_files`
    works on ``.parquet`` part files too).  Row-level shuffling is not
    provided here — shuffle at the file/row-group level
    (``shuffle_files=True``) or upstream at write time.
    """
    import pyarrow.parquet as pq

    if isinstance(files, str):
        files = fs.glob(files)
    files = list(files)
    if not files:
        return
    _stage = _stager(device_put)

    def _open_parquet(path: str):
        """Returns (ParquetFile, handle-to-close-or-None): ParquetFile.close
        does not close a caller-supplied source, so remote handles must be
        closed explicitly."""
        local = fs.local_path(path)
        if local is not None:
            return pq.ParquetFile(local), None
        handle = fs.open(path, "rb")
        return pq.ParquetFile(handle), handle

    def batch_gen() -> Iterator[dict[str, Any]]:
        for epoch in range(num_epochs):
            epoch_files = list(files)
            if shuffle_files:
                np.random.default_rng(seed + epoch).shuffle(epoch_files)
            pending: dict[str, list[np.ndarray]] = {}
            count = 0
            names: list[str] | None = None
            for path in epoch_files:
                pf, handle = _open_parquet(path)
                try:
                    for rb in pf.iter_batches(columns=list(columns)
                                              if columns else None):
                        if rb.num_rows == 0:
                            continue
                        if names is None:
                            names = list(rb.schema.names)
                        elif list(rb.schema.names) != names:
                            # schema drift across part files would silently
                            # misalign the columnar accumulators
                            raise ValueError(
                                f"{path}: columns {rb.schema.names} != "
                                f"{names} of the first file"
                            )
                        for name, col in zip(rb.schema.names, rb.columns):
                            pending.setdefault(name, []).append(
                                _column_to_numpy(path, name, col)
                            )
                        count += rb.num_rows
                        while count >= batch_size:
                            batch, pending, count = _slice_batch(
                                pending, count, batch_size
                            )
                            obs.counter("reader_records_total").inc(
                                batch_size)
                            yield _stage(batch)
                finally:
                    pf.close()
                    if handle is not None:
                        handle.close()
            if count and not drop_remainder:
                obs.counter("reader_records_total").inc(count)
                batch, pending, count = _slice_batch(pending, count, count)
                yield _stage(batch)

    yield from prefetched(batch_gen, prefetch)


def _column_to_numpy(path: str, name: str, col) -> np.ndarray:
    """One Arrow column → a dense numeric numpy array.

    ``np.asarray`` on a list-typed or null-bearing Arrow column silently
    yields ``dtype=object``, which only fails much later at
    ``device_put``/jnp conversion — so convert deliberately: scalar columns
    via ``to_numpy``; fixed-length list columns (the ``array<T>`` vectors
    ``dfutil.saveAsParquet`` writes, e.g. criteo ``cat``) stack to
    ``(N, k)``; nulls and ragged lists fail loudly with the file and
    column named.
    """
    import pyarrow as pa

    if col.null_count:
        raise ValueError(
            f"{path}: column {name!r} has {col.null_count} null values — "
            "fill or drop them before the TPU feed (object arrays cannot "
            "be device_put)"
        )
    t = col.type
    if pa.types.is_fixed_size_list(t):
        flat = col.flatten()
        if flat.null_count:
            raise ValueError(
                f"{path}: column {name!r} has null list elements")
        k = t.list_size
        return flat.to_numpy(zero_copy_only=False).reshape(len(col), k)
    if pa.types.is_list(t) or pa.types.is_large_list(t):
        offsets = col.offsets.to_numpy(zero_copy_only=False)
        lengths = np.diff(offsets)
        if len(lengths) and not (lengths == lengths[0]).all():
            raise ValueError(
                f"{path}: column {name!r} is a ragged list column "
                f"(lengths {lengths.min()}..{lengths.max()}); TPU batches "
                "need rectangular arrays — pad it at write time"
            )
        values = col.values
        if values.null_count:
            raise ValueError(
                f"{path}: column {name!r} has null list elements")
        k = int(lengths[0]) if len(lengths) else 0
        flat = values.to_numpy(zero_copy_only=False)
        # offsets may not start at 0 for a sliced array
        flat = flat[offsets[0]:offsets[0] + len(col) * k]
        return flat.reshape(len(col), k)
    if not (pa.types.is_floating(t) or pa.types.is_integer(t)
            or pa.types.is_boolean(t)):
        # string/binary/temporal scalars come back dtype=object from
        # to_numpy — the exact deferred device_put failure this helper
        # exists to prevent
        raise ValueError(
            f"{path}: column {name!r} has non-numeric type {t} — encode it "
            "to a numeric dtype before the TPU feed (object arrays cannot "
            "be device_put)"
        )
    return col.to_numpy(zero_copy_only=False)


def _slice_batch(pending: dict[str, list[np.ndarray]], count: int,
                 batch_size: int):
    """Take the first ``batch_size`` rows out of columnar accumulators."""
    batch: dict[str, np.ndarray] = {}
    rest: dict[str, list[np.ndarray]] = {}
    for name, chunks in pending.items():
        col = chunks[0] if len(chunks) == 1 else np.concatenate(chunks)
        batch[name] = col[:batch_size]
        if len(col) > batch_size:
            rest[name] = [col[batch_size:]]
    return batch, rest, count - batch_size
