"""Serving data plane: bucketed batch shapes, columnar ingest, masked emit.

The training side stopped paying per-row Python costs in the zero-copy data
plane rebuild (:mod:`tensorflowonspark_tpu.shm`); this module brings the
*serving* hot path (``pipeline.TFModel.transform`` → ``_RunModel``, and the
JNI shim's :mod:`tensorflowonspark_tpu.infer_embed`) to parity.  Three
mechanisms, each with the measured failure mode it removes:

- **Shape bucketing with pad-and-mask** (:func:`resolve_buckets` /
  :func:`choose_bucket` / :func:`pad_columns`): every batch is zero-padded
  up to a small fixed set of bucket sizes (default: just ``batch_size``), so
  a jitted forward compiles once per *bucket* instead of once per distinct
  partition-tail size — on a Spark job every partition has a ragged tail,
  and each distinct tail size is a fresh XLA compilation (TF-Replicator,
  arXiv:1902.00465 §3, makes the same fixed-shape argument for TPU
  execution).  Padded rows are masked out of the emitted output
  (:func:`emit_rows` slices every column back to the true row count).  The
  claim is measurable: :func:`note_compile` counts distinct input-shape
  signatures handed to each loaded forward — exactly the jit/XLA
  compilation keys — into the ``serving_compiles_total`` counter.
- **Columnar partition ingest** (:func:`ingest_chunks`): each chunk of
  rows becomes column arrays via one C-level ``operator.itemgetter`` map
  per needed column (touching only the columns the model uses — the
  row→column direction the feed transport's feeder-side columnarization
  shares) instead of a per-column, per-row ``row[col]`` indexing loop;
  pyarrow ``RecordBatch``/``Table`` partition elements (real pyspark
  ``df.mapInArrow``) take a no-per-row-work fast path through
  ``sql_compat.arrow_batch_columns``.
- **Masked per-column emission** (:func:`emit_rows`): one ``np.asarray`` +
  one ``tolist()`` per output column per batch, then a single zip into
  Rows — replacing the per-row, per-cell ``_pyval(a[i])`` materialization.

The double-buffering itself lives in the caller: ``_RunModel`` runs the
ingest + pad + ``device_put`` stage (:func:`stager`) inside a
``readers.prefetched`` pump thread so batch N+1 is assembled and staged onto
the device while batch N computes.

Registry counters (exported with every metrics snapshot): ``serving_compiles_total``,
``serving_rows_total``, ``serving_padded_rows_total``, and the compile
hit/miss family ``serving_compile_cache_{hits,misses}_total`` — whose disk
dimension (``serving_compile_cache_disk_{hits,writes}_total``,
``serving_compile_disk_seconds``) lives in
:mod:`tensorflowonspark_tpu.compile_cache`.  Shape POLICY (buckets,
signatures, warmup enumeration) lives in
:mod:`tensorflowonspark_tpu.shapes`; this module re-exports the
historical names.
"""

from __future__ import annotations

import logging
import os
import threading
from typing import Any, Iterator, Mapping, Sequence

import numpy as np

from tensorflowonspark_tpu import shapes

logger = logging.getLogger(__name__)

#: distinct input-shape signatures observed per loaded forward — the jit
#: compilation keys.  Keyed by the model-cache key (or any hashable handle);
#: :func:`forget` drops entries when the owning model is evicted/closed.
_SEEN_SHAPES: dict[Any, set] = {}


# ---------------------------------------------------------------------------
# Buckets — POLICY LIVES IN shapes.py (the one shape-policy module); these
# are this module's historical names, kept so the wide existing call
# surface (tests, notebooks, the JNI shim's env contract) stays stable.
# ---------------------------------------------------------------------------

resolve_buckets = shapes.resolve_buckets
choose_bucket = shapes.choose_bucket
pow2_bucket = shapes.pow2_bucket
batch_rows = shapes.batch_rows
input_specs = shapes.input_specs
zero_batch = shapes.zero_batch


def bucketing_enabled() -> bool:
    """``TFOS_SERVING_BUCKETS=0`` disables pad-and-mask in
    ``TFModel.transform`` (every batch then compiles at its own shape —
    the legacy compile cost, but the columnar ingest / prefetch pipeline /
    fast emission stay on).

    The knob exists for forwards whose per-example outputs depend on the
    WHOLE batch — inference-time batch-stats normalization, in-batch
    softmax or contrastive scoring: padded zero rows would change the real
    rows' values while passing every shape check, so padding must be off
    for them."""
    return os.environ.get("TFOS_SERVING_BUCKETS", "1").strip().lower() \
        not in ("0", "false")


def pad_columns(cols: Mapping[str, Any], target: int) -> dict:
    """Zero-pad every column's leading axis to ``target`` rows.

    Delegates to ``saved_model.pad_batch`` — the ONE padding convention,
    shared with the fixed-batch serialized-forward caller, so masked-row
    semantics agree on every serving path."""
    from tensorflowonspark_tpu import saved_model

    return saved_model.pad_batch(cols, target)


# ---------------------------------------------------------------------------
# Warmup shapes
# ---------------------------------------------------------------------------


def warm_buckets(fn, params, specs: Mapping[str, tuple[tuple, Any]],
                 buckets: Sequence[int], cache_key: Any) -> None:
    """Pre-compile ``fn`` for every bucket shape — the ONE warm loop,
    shared by ``TFModel.warmup`` and the online tier's warm-on-load.

    Each warm compile is counted through :func:`note_compile` under
    ``cache_key`` (the model-cache key the data plane will use), so the
    invariant *``serving_compiles_total`` == distinct jit keys* holds —
    warmup only moves the compiles off the first request's critical path.
    The shapes warmed are exactly ``shapes.enumerate_signatures(specs,
    buckets)`` — the one shape policy, so the data plane can add zero new
    jit keys afterwards.  Every warm forward is FORCED (leaves
    materialized): jax dispatch is async, and an unforced warm would
    leave the compile racing the first real batch.

    Warmup is also the persistent compile cache's designated seeding
    path: :func:`compile_cache.ensure` runs first (so the warm compiles
    read/write the configured cache dir) and a synchronous
    :func:`compile_cache.sync` pushes the fresh entries to a shared-fs
    namespace before the method returns — one replica warms, the fleet
    loads."""
    from tensorflowonspark_tpu import compile_cache, obs

    import time as _time

    compile_cache.ensure()
    with obs.span("serving.warmup", buckets=list(buckets)):
        for b in buckets:
            batch = zero_batch(specs, b)
            fresh = note_compile(cache_key, batch)
            t0 = _time.perf_counter()
            out = fn(params, batch)
            for leaf in _tree_leaves(out):
                np.asarray(leaf)
            if fresh:
                # forced forward: this wall is the real compile cost the
                # warmup moved off the first request's critical path
                observe_compile_seconds(_time.perf_counter() - t0)
    compile_cache.sync()


def _tree_leaves(tree):
    if isinstance(tree, Mapping):
        for v in tree.values():
            yield from _tree_leaves(v)
    elif isinstance(tree, (list, tuple)):
        for v in tree:
            yield from _tree_leaves(v)
    else:
        yield tree


# ---------------------------------------------------------------------------
# Compile accounting
# ---------------------------------------------------------------------------


#: compile wall-time histogram bounds: XLA compiles run 10ms (trivial
#: MLP) to minutes (big models) — the registry default tops out too low
_COMPILE_BUCKETS = (0.01, 0.05, 0.1, 0.5, 1.0, 5.0, 10.0, 30.0, 60.0,
                    120.0, float("inf"))
#: cached (compiles_total, misses, hits, compile_seconds) — note_compile
#: runs per serving batch and must not pay registry lookups there (same
#: rule as the flight recorder's instrument cache)
_COMPILE_INSTRUMENTS = None


def _compile_instruments():
    global _COMPILE_INSTRUMENTS
    if _COMPILE_INSTRUMENTS is None:
        from tensorflowonspark_tpu import obs

        _COMPILE_INSTRUMENTS = (
            obs.counter(
                "serving_compiles_total",
                "distinct input-shape signatures handed to a serving "
                "forward (jit compilation keys)"),
            obs.counter(
                "serving_compile_cache_misses_total",
                "shape signatures that paid a TRUE XLA compile (new to "
                "their forward AND not served from the persistent "
                "compile cache — disk hits ride "
                "serving_compile_cache_disk_hits_total instead)"),
            obs.counter(
                "serving_compile_cache_hits_total",
                "batches whose shape signature was already compiled for "
                "the owning forward (jit executable cache hits)"),
            obs.histogram(
                "serving_compile_seconds",
                "wall time of first-call forwards with a new shape "
                "signature (compile-inclusive: trace + XLA compile + the "
                "first execution)", buckets=_COMPILE_BUCKETS))
    return _COMPILE_INSTRUMENTS


#: per-thread pending first-call settlement: the disk-hit count snapshot
#: taken when note_compile reported a fresh signature, resolved by
#: observe_compile_seconds (or the next note_compile on the thread)
_PENDING = threading.local()


def note_compile(key: Any, batch: Mapping[str, Any]) -> bool:
    """Record the batch's shape signature; True when it is new for ``key``.

    The signature (``shapes.signature`` — the one policy module's
    canonical (structure, shape, dtype) fingerprint) is exactly what
    ``jax.jit`` keys its executable cache on, so for a jitted forward
    "new signature" == "fresh XLA compile *or* persistent-cache load".
    Every new signature increments ``serving_compiles_total``, making the
    bucketing claim ("compiles == buckets, not distinct tail sizes")
    measurable in tests, in ``bench.py --serving``, and on a live
    ``/metrics`` endpoint.

    The hit/miss split has a **disk dimension**: a first-call forward
    served from the persistent compile cache is neither an in-process hit
    (the signature WAS new to this process) nor a true miss (no XLA
    compile ran) — it counts in ``serving_compile_cache_disk_hits_total``
    and NOT in ``serving_compile_cache_misses_total``.  Since the disk
    outcome is only known after the forward runs, a fresh signature
    leaves a thread-local pending settlement that
    :func:`observe_compile_seconds` (called by every data plane after the
    first-call forward) resolves against ``compile_cache``'s thread-exact
    disk-hit count; an abandoned pending (the forward raised, or a legacy
    caller never timed it) settles conservatively as a true miss at the
    thread's next ``note_compile``."""
    _settle_pending(None)
    sig = shapes.signature(batch)
    compiles, misses, hits, _ = _compile_instruments()
    seen = _SEEN_SHAPES.setdefault(key, set())
    if sig in seen:
        hits.inc()
        return False
    seen.add(sig)
    compiles.inc()
    from tensorflowonspark_tpu import compile_cache

    if compile_cache.active():
        # the disk outcome is only knowable after the forward: leave a
        # pending settlement for observe_compile_seconds
        _PENDING.snapshot = compile_cache.thread_disk_hits()
    else:
        # no persistent cache in this process: a fresh signature IS a
        # true miss, settled immediately (counter deltas stay exact for
        # callers that never time their forwards)
        misses.inc()
    return True


def _settle_pending(observed: float | None) -> None:
    """Resolve a thread's pending first-call as disk hit or true miss.

    The comparison is thread-exact: jax's cache-hit monitoring event
    fires synchronously on the compiling thread, so a disk-hit delta
    since the snapshot means THIS thread's compile loaded from disk.
    Only a true miss observes ``serving_compile_seconds`` — the disk
    half is ``serving_compile_disk_seconds``, fed by the cache layer's
    retrieval-time events."""
    snap = getattr(_PENDING, "snapshot", None)
    compiles, misses, hits, hist = _compile_instruments()
    if snap is None:
        if observed is not None:
            # a timed wall with no pending note: legacy caller — keep the
            # histogram observation (old observe_compile_seconds contract)
            hist.observe(float(observed))
        return
    _PENDING.snapshot = None
    from tensorflowonspark_tpu import compile_cache

    if compile_cache.thread_disk_hits() > snap:
        return  # disk hit: counted by the cache layer's event listener
    misses.inc()
    if observed is not None:
        hist.observe(float(observed))


def observe_compile_seconds(seconds: float) -> None:
    """Record one first-call forward's wall (a shape signature
    :func:`note_compile` reported as new) and settle its pending
    hit/miss/disk classification."""
    _settle_pending(float(seconds))


def cache_health() -> dict[str, Any]:
    """The compile-cache block ``/healthz`` surfaces: persistent-cache
    state + the in-process counters + a ``warm_ratio`` so a router can
    see a cold replica (low ratio = shape requests are still paying
    compiles; 1.0 = every request hit a warm executable).  ``warm_ratio``
    counts disk hits as warm — that is the fleet cache doing its job."""
    from tensorflowonspark_tpu import compile_cache

    compiles, misses, hits, _ = _compile_instruments()
    doc = compile_cache.stats()
    warm = int(hits.value) + doc["disk_hits"]
    total = warm + int(misses.value)
    doc.update({
        "compiles_total": int(compiles.value),
        "in_process_hits": int(hits.value),
        "true_misses": int(misses.value),
        "warm_ratio": round(warm / total, 4) if total else None,
    })
    return doc


#: padded-row fraction above which the bucket ladder is called bad;
#: judged only after ``_PAD_WARN_MIN_ROWS`` forwarded rows so a ragged
#: first batch can't cry wolf
DEFAULT_PAD_WASTE_WARN = 0.5
_PAD_WARN_MIN_ROWS = 256
_PAD_WASTE_WARNED = False
#: cached (rows_counter, padded_counter, waste_gauge) — note_rows runs on
#: the serving pump per batch and must not pay registry lookups there
#: (same rule as the flight recorder's instrument cache)
_ROW_INSTRUMENTS = None


def _row_instruments():
    global _ROW_INSTRUMENTS
    if _ROW_INSTRUMENTS is None:
        from tensorflowonspark_tpu import obs

        _ROW_INSTRUMENTS = (
            obs.counter("serving_rows_total",
                        "rows scored through the serving data plane"),
            obs.counter("serving_padded_rows_total",
                        "rows invented by bucket padding (masked out of "
                        "the output)"),
            obs.gauge("serving_padding_waste_ratio",
                      "fraction of forwarded rows invented by bucket "
                      "padding (padded / (real + padded))"))
    return _ROW_INSTRUMENTS


def note_rows(n_real: int, bucket: int) -> None:
    """Count scored rows and the padding overhead of their bucket.

    ``serving_padded_rows_total / serving_rows_total`` is the padding-waste
    ratio of the configured bucket geometry — the number to look at before
    adding smaller buckets (each one costs a compile).  The derived
    ``serving_padding_waste_ratio`` gauge (padded / forwarded rows — the
    fraction of forward compute spent on invented rows) is refreshed on
    every batch, and the first time it exceeds the warn threshold over a
    meaningful volume a structured ``serving.padding_waste`` event + log
    WARNING names the bad bucket ladder."""
    global _PAD_WASTE_WARNED

    rows, padded, waste = _row_instruments()
    rows.inc(n_real)
    if bucket > n_real:
        padded.inc(bucket - n_real)
    forwarded = rows.value + padded.value
    ratio = padded.value / forwarded if forwarded else 0.0
    waste.set(ratio)
    if _PAD_WASTE_WARNED or forwarded < _PAD_WARN_MIN_ROWS:
        return
    if ratio > DEFAULT_PAD_WASTE_WARN:
        from tensorflowonspark_tpu import obs

        _PAD_WASTE_WARNED = True
        logger.warning(
            "serving padding waste %.0f%% exceeds %.0f%% (%d padded vs "
            "%d real rows): the bucket ladder is a bad fit for this "
            "batch-size distribution — add a smaller bucket (each costs "
            "one compile) or lower batch_size",
            ratio * 100, DEFAULT_PAD_WASTE_WARN * 100, int(padded.value),
            int(rows.value))
        obs.event("serving.padding_waste", ratio=round(ratio, 4),
                  threshold=DEFAULT_PAD_WASTE_WARN, rows=int(rows.value),
                  padded=int(padded.value))


def forget(key: Any = None) -> None:
    """Drop shape tracking for one model key (or all, with no argument) —
    called when the owning model-cache entry is evicted or a handle
    closes, so the tracking dict cannot outgrow the model cache."""
    if key is None:
        _SEEN_SHAPES.clear()
    else:
        _SEEN_SHAPES.pop(key, None)


# ---------------------------------------------------------------------------
# Columnar ingest
# ---------------------------------------------------------------------------


def ingest_chunks(iterator, chunk_rows: int, in_map: Mapping[str, str],
                  columns: Sequence[str]
                  ) -> Iterator[tuple[int, dict[str, np.ndarray]]]:
    """Partition iterator → ``(n_rows, {feature: column array})`` chunks.

    Row-shaped elements (either backend's ``Row``, plain tuples, dicts) are
    buffered to ``chunk_rows`` and columnarized in one transpose pass;
    pyarrow ``RecordBatch``/``Table`` elements (``df.mapInArrow``-style
    partitions) are sliced straight from their column buffers with no
    per-row work at all.  ``in_map`` maps DataFrame column → model input
    name; ``columns`` supplies positional names for rows that don't carry
    their own fields (plain tuples).
    """
    from tensorflowonspark_tpu import sql_compat

    pending: list[Any] = []

    def flush():
        n, cols = _columnarize_rows(pending, in_map, columns)
        pending.clear()
        return n, cols

    for item in iterator:
        arrow = sql_compat.arrow_batch_columns(item, columns=list(in_map))
        if arrow is not None:
            if pending:
                yield flush()
            missing = [c for c in in_map if c not in arrow]
            if missing:
                raise KeyError(
                    f"arrow partition batch lacks input column(s) {missing}; "
                    f"has {sorted(arrow)}")
            total = int(next(iter(arrow.values())).shape[0]) if arrow else 0
            for start in range(0, total, chunk_rows):
                stop = min(start + chunk_rows, total)
                yield stop - start, {feat: arrow[col][start:stop]
                                     for col, feat in in_map.items()}
            continue
        pending.append(item)
        if len(pending) >= chunk_rows:
            yield flush()
    if pending:
        yield flush()


def _columnarize_rows(rows: list, in_map: Mapping[str, str],
                      columns: Sequence[str]
                      ) -> tuple[int, dict[str, np.ndarray]]:
    """One chunk of rows → columns, one C-level extraction pass per column.

    ``operator.itemgetter(pos)`` over the whole chunk (C speed on
    tuple-like pyspark Rows, one ``__getitem__`` per row on the substrate
    Row) touches only the columns the model actually needs — a partition
    often carries more — instead of transposing every field of every row.
    Positional extraction assumes the schema-uniform rows a DataFrame
    partition guarantees; a chunk that violates that (hand-built RDD rows
    of mixed arity) falls back to the legacy by-name per-row indexing.
    """
    import operator

    first = rows[0]
    if isinstance(first, dict):
        return len(rows), {feat: np.asarray([r[col] for r in rows])
                           for col, feat in in_map.items()}
    fields = getattr(first, "__fields__", None)
    if fields is not None:  # pyspark attribute / sparkapi method
        names = list(fields() if callable(fields) else fields)
    else:
        names = list(columns)
    out = {}
    for col, feat in in_map.items():
        try:
            pos = names.index(col)
        except ValueError:
            raise KeyError(
                f"input column {col!r} not found in partition rows "
                f"(row fields: {names})") from None
        try:
            out[feat] = np.asarray(list(map(operator.itemgetter(pos), rows)))
        except IndexError:
            # a short row (mixed arity): legacy by-name behavior — numpy /
            # the model complains about whatever the names produce
            out[feat] = np.asarray([r[col] for r in rows])
    return len(rows), out


# ---------------------------------------------------------------------------
# Device staging + pipeline knobs
# ---------------------------------------------------------------------------


def stager():
    """Batch-staging function for the prefetch pump thread.

    ``jax.device_put`` from the pump overlaps H2D transfer with the
    consumer's compute on batch N-1 (the readers double-buffering, reused).
    Fail-soft: a backend that can't stage (or a host-only predict_fn world
    with no jax) hands back host arrays — numpy consumers accept jax arrays
    and vice versa, so staging is a throughput knob, never a correctness
    one.  ``TFOS_SERVING_DEVICE_PUT``: unset/``auto`` stages only when the
    default backend is a real accelerator (on CPU there is no H2D to
    overlap — the put is pure per-batch dispatch overhead), ``1`` always,
    ``0`` never."""
    mode = os.environ.get("TFOS_SERVING_DEVICE_PUT", "auto").strip().lower()
    if mode in ("0", "false"):
        return lambda batch: batch
    if mode not in ("1", "true"):  # auto
        try:
            import jax

            if jax.default_backend() == "cpu":
                return lambda batch: batch
        except Exception:
            return lambda batch: batch

    def put(batch: dict) -> dict:
        try:
            import jax

            return {k: jax.device_put(v) for k, v in batch.items()}
        except Exception:
            return batch

    return put


def prefetch_depth() -> int:
    """Batches staged ahead by the serving pump (``TFOS_SERVING_PREFETCH``,
    default 2; 0 degrades to fully synchronous assembly)."""
    try:
        return int(os.environ.get("TFOS_SERVING_PREFETCH", "2"))
    except ValueError:
        return 2


# ---------------------------------------------------------------------------
# Masked emission
# ---------------------------------------------------------------------------


def emit_rows(named: Mapping[str, Any], n_real: int, backend: str,
              fed_rows: int | None = None) -> list:
    """Named output arrays → ``n_real`` Rows, one ``tolist()`` per column.

    Slicing to ``n_real`` is the mask half of pad-and-mask: rows the bucket
    padding invented are never emitted.  Every output's leading dimension
    must EQUAL the row count of the batch that was fed (``fed_rows`` — the
    bucket size for a padded batch; defaults to ``n_real``): that is what
    makes it a per-example output.  An output of any other length — a
    pooled embedding, a scalar metric, anything aggregated over the batch —
    is rejected loudly instead of being sliced into plausible-looking
    garbage rows (the contract the legacy ``a[i]`` loop silently assumed).
    Returns a list (not a generator): the whole batch materializes in one
    comprehension, so the caller's ``yield from`` is the only per-row
    frame resume."""
    from tensorflowonspark_tpu import sql_compat

    expect = n_real if fed_rows is None else fed_rows
    cols = list(named.keys())
    pylists = []
    for c in cols:
        a = np.asarray(named[c])
        if a.ndim == 0 or a.shape[0] != expect:
            raise ValueError(
                f"serving output {c!r} has shape {np.shape(a)} but the batch "
                f"fed {expect} rows — outputs must be per-example (leading "
                "batch dimension matching the fed batch) to be emitted as "
                "DataFrame rows")
        pylists.append(a[:n_real].tolist())
    make = sql_compat.row_maker(cols, backend)
    if len(pylists) == 1:
        return [make([v]) for v in pylists[0]]
    return [make(values) for values in zip(*pylists)]
