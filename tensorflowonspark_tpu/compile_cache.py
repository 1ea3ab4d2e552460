"""Persistent XLA compile cache: on by default, placed from outside.

Every process pays its own XLA compiles — the dominant cold-start cost of
a trainer relaunch or a serving replica.  This module turns JAX's
persistent compilation cache on for every compile-adjacent path (trainer
construction, serving model load, warmup, the JNI shim's ``load``) and
counts what it saves:

- **Where the cache lives** is JAX's own setting.  If
  ``JAX_COMPILATION_CACHE_DIR`` is set, JAX already uses that directory and
  :func:`ensure` sets none in code — it only installs the counters.  If it
  is unset, the cache is on at a fixed path inside the checkout
  (:data:`DEFAULT_DIR`, ``<repo>/.jax_cache``, git-ignored).  The path is
  part of nothing here that moves: no temp dir, pid or timestamp, because a
  directory that moves never hits.  No topology sub-directory either — JAX's
  cache key already covers the computation, compile options, backend,
  device kind and jax version, so a changed model or chip cannot collide.
- **Fleet sharing** (optional): ``TFOS_COMPILE_CACHE_DIR=<uri>`` names a
  shared root resolved through :mod:`tensorflowonspark_tpu.fs` (``gs://``,
  ``hdfs://``, a shared mount, ``memory://`` in tests).  JAX's cache
  cannot speak fsspec, so it is pointed at a local **spool** — the
  ``JAX_COMPILATION_CACHE_DIR`` directory when that is set, else a fixed
  per-namespace directory under :data:`SPOOL_DIR` beside the default cache
  — and entries are pulled from the remote namespace at configure time and
  pushed as new compiles land, so one replica compiles and the fleet
  loads.  Remote entries live under a
  *topology namespace* (``jax<ver>-<platform>-<device kind>-d<devices>-
  p<processes>``) so a heterogeneous fleet sharing one bucket never even
  lists another topology's entries, and each carries a ``.sha256`` sidecar
  written *after* the payload; the pull path verifies it and **rejects
  corrupt or half-written entries** (counted in
  ``serving_compile_cache_disk_corrupt_total``) instead of handing XLA a
  truncated executable.
- **Observability**: disk hits / writes / corrupt-rejections counters and
  a ``serving_compile_disk_seconds`` retrieval-time histogram, split out
  of the in-process compile metrics (``serving_compile_cache_{hits,
  misses}_total`` keep meaning "jit executable cache" — a disk hit is
  neither an in-process hit nor a true miss).  Attribution is
  thread-exact: JAX's monitoring events fire synchronously on the
  compiling thread, so ``serving.note_compile``'s settle logic can tell
  *this* forward's disk hit from a concurrent one.
- **A start's seconds from inside**: this module is the one place that
  listens to JAX's monitoring, so it also turns JAX's own time-span
  events (``dispatch.log_elapsed_time`` round every trace, lowering and
  backend compile, on the wall clock the ring is on) into ring spans
  on the compiling thread, children of whatever span is open there
  (``trainer.dispatch``, ``ckpt.restore``, a feed's thread):
  ``jit.trace`` (attr ``fun``; only a trace of at least
  :data:`TRACE_SPAN_FLOOR_S`, a nested one inside the one that holds it),
  ``jit.lower`` (attr ``fun``) and ``jit.compile`` (attrs ``fun`` and
  ``cache``: ``hit`` with ``retrieval_s`` and ``saved_s``, JAX's two
  durations; ``miss`` with ``entry_bytes``, the put's ``len(val)``, and
  ``written``, 1 where the entry's file is there after the put and was
  not before; ``off`` where :func:`active` is false or the compile asked
  no cache).  The persistent cache's read and write run inside the
  compile's stretch, so the sibling listeners leave thread-local notes
  that the span takes and clears.  Two counters:
  ``jit_traces_total`` counts every trace, recorded or not, and
  ``compile_cache_disk_misses_total`` every ``jit.compile`` that says
  ``miss``.  The listeners are installed by :func:`ensure` whether or not
  the cache is on; a steady step fires no JAX event, and under
  ``TFOS_TRACE=0`` only the counters move.

``TFOS_COMPILE_CACHE=0`` opts a process out (the test suite does).  Every
compile is worth writing (serving forwards are small and the whole point
is the fleet's long tail of them) unless jax's own
``JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS`` says otherwise.
"""

from __future__ import annotations

import hashlib
import logging
import os
import re
import threading
from typing import Any

logger = logging.getLogger(__name__)

_REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
#: where the cache lives when ``JAX_COMPILATION_CACHE_DIR`` does not say
DEFAULT_DIR = os.path.join(_REPO, ".jax_cache")
#: parent of the per-namespace local spools of a fleet root
SPOOL_DIR = os.path.join(_REPO, ".jax_cache_spool")

#: JAX monitoring event names (jax/_src/compiler.py, compilation_cache.py).
#: Note the naming skew: jax's "cache_misses" event fires when an entry is
#: WRITTEN — for us that is the disk-write counter, not a miss.
_EV_HIT = "/jax/compilation_cache/cache_hits"
_EV_WRITE = "/jax/compilation_cache/cache_misses"
_EV_REQUEST = "/jax/compilation_cache/compile_requests_use_cache"
_DUR_RETRIEVAL = "/jax/compilation_cache/cache_retrieval_time_sec"
_DUR_SAVED = "/jax/compilation_cache/compile_time_saved_sec"
#: JAX's time-span events (jax/_src/dispatch.py) -> the ring span of each
_SPAN_TRACE = "/jax/core/compile/jaxpr_trace_duration"
_SPAN_LOWER = "/jax/core/compile/jaxpr_to_mlir_module_duration"
_SPAN_COMPILE = "/jax/core/compile/backend_compile_duration"

#: a trace shorter than this is counted and leaves no span: every ``jnp``
#: primitive's own ``jit`` is traced and reported, thousands a start
TRACE_SPAN_FLOOR_S = 0.005

#: retrieval-time histogram bounds: a disk hit is mmap+deserialize —
#: sub-ms local, tens of ms on shared fs, seconds only when something is
#: wrong
_DISK_BUCKETS = (0.001, 0.005, 0.01, 0.05, 0.1, 0.5, 1.0, 5.0,
                 float("inf"))

_LOCK = threading.Lock()
_SYNC_LOCK = threading.Lock()
_TLS = threading.local()
_INSTRUMENTS = None
_LISTENING = False

_STATE: dict[str, Any] = {
    "attempted": False,     # one configure attempt per process
    "namespace": None,      # remote namespace, else the local dir; or None
    "active_dir": None,     # the local dir jax reads/writes
    "set_dir": False,       # ensure() set jax's dir itself (env was unset)
    "remote_ns": None,      # set only with a fleet root
    "pushed": set(),        # local entry names verified to exist remotely
    "sync_scheduled": False,  # a delayed background push is pending
    "error": None,          # why configuration failed, if it did
}


# ---------------------------------------------------------------------------
# Configuration
# ---------------------------------------------------------------------------


def enabled() -> bool:
    """False only under the ``TFOS_COMPILE_CACHE=0`` opt-out."""
    return os.environ.get("TFOS_COMPILE_CACHE", "1").strip().lower() not in (
        "0", "false")


def fleet_root() -> str | None:
    """The shared root (``TFOS_COMPILE_CACHE_DIR``, any ``fs.py`` URI) the
    local cache is synced with, or None."""
    root = os.environ.get("TFOS_COMPILE_CACHE_DIR", "").strip()
    if not root or root.lower() in ("0", "off", "none"):
        return None
    return root


def active() -> bool:
    """True once :func:`ensure` has successfully configured the cache in
    this process — the gate for the hit/miss/disk settlement in
    ``serving.note_compile`` (with no cache, a fresh signature is simply
    a true miss and settles immediately)."""
    return _STATE["namespace"] is not None


def topology_key() -> str:
    """The namespace of a fleet root an entry set is valid for.

    JAX's cache key already content-addresses the computation, backend
    and jax version; the namespace exists so a cross-device or
    cross-version entry is never even LISTED for (or pulled by) this
    process — shared roots serve heterogeneous fleets (a v5e pod and a
    CPU CI box can share one bucket).  Requires an initialized backend
    (callers are about to compile anyway)."""
    import jax

    devices = jax.devices()
    kind = devices[0].device_kind if devices else "unknown"
    raw = (f"jax{jax.__version__}-{jax.default_backend()}-{kind}"
           f"-d{len(devices)}-p{jax.process_count()}")
    return re.sub(r"[^A-Za-z0-9_.+-]+", "-", raw)


def ensure() -> str | None:
    """Configure the persistent compile cache for this process (idempotent).

    Returns the namespace in use (the fleet namespace when a root is set,
    else the local directory), or None when opted out or unconfigurable.
    Never raises: a cache problem must not take down a training step or a
    tenant load — the process just compiles like it always did, and the
    reason lands in :func:`stats` (and so on ``/healthz``).  Opted out or
    not, the process gets the monitoring listeners: the ``jit.*`` spans of
    its compiles say ``cache: off``."""
    with _LOCK:
        if _STATE["attempted"]:
            return _STATE["namespace"]
        if not enabled():
            try:
                _install_listeners()
            except Exception as e:  # pragma: no cover - a jax without them
                logger.warning("compile monitoring not installed: %s", e)
            return None
        _STATE["attempted"] = True
        try:
            _configure()
        except Exception as e:  # pragma: no cover - env-specific failures
            _STATE["error"] = f"{type(e).__name__}: {e}"[:300]
            _STATE["namespace"] = None
            logger.warning("persistent compile cache disabled: %s", e)
        return _STATE["namespace"]


def _configure() -> None:
    from tensorflowonspark_tpu import fs, util

    util.ensure_jax_platform()
    import jax

    root = fleet_root()
    remote_ns = fs.join(root, topology_key()) if root is not None else None
    local = os.environ.get("JAX_COMPILATION_CACHE_DIR", "").strip()
    if local:
        # jax read the variable at import and already uses that directory:
        # no directory is set in code
        os.makedirs(local, exist_ok=True)
    else:
        local = DEFAULT_DIR if remote_ns is None else os.path.join(
            SPOOL_DIR, hashlib.sha256(remote_ns.encode()).hexdigest()[:16])
        os.makedirs(local, exist_ok=True)
        jax.config.update("jax_compilation_cache_dir", local)
        _STATE["set_dir"] = True
    namespace = remote_ns or local
    if remote_ns is not None:
        fs.makedirs(remote_ns)
        _STATE["remote_ns"] = remote_ns
        pulled = pull_entries(remote_ns, local, pushed=_STATE["pushed"])
        logger.info("compile cache %s: pulled %d entries to %s "
                    "(%d corrupt rejected)", namespace, pulled["pulled"],
                    local, pulled["corrupt"])
    _install_listeners()
    # serving forwards compile in well under jax's 1s default; the fleet
    # amortizes even tiny compiles, so cache everything unless the
    # operator said otherwise via jax's own env knobs
    if "JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS" not in os.environ:
        jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    if "JAX_PERSISTENT_CACHE_MIN_ENTRY_SIZE_BYTES" not in os.environ:
        jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    _STATE["namespace"] = namespace
    _STATE["active_dir"] = local
    logger.info("persistent compile cache at %s (local dir %s)",
                namespace, local)


def disable() -> None:
    """Tear the configuration down (tests, A/B benches): the next
    :func:`ensure` re-reads env.  Undoes only what :func:`ensure` set —
    a directory jax took from ``JAX_COMPILATION_CACHE_DIR`` is the
    caller's to change — and has jax drop the cache object it opened, so
    a directory changed in between is re-read."""
    from jax.experimental.compilation_cache import compilation_cache as cc

    with _LOCK:
        if _STATE["set_dir"]:
            import jax

            jax.config.update("jax_compilation_cache_dir", None)
        _STATE.update(attempted=False, namespace=None, active_dir=None,
                      set_dir=False, remote_ns=None, error=None,
                      sync_scheduled=False)
        _STATE["pushed"] = set()
        cc.reset_cache()


# ---------------------------------------------------------------------------
# Remote sync (the fs.py seam)
# ---------------------------------------------------------------------------


def _digest(payload: bytes) -> str:
    return hashlib.sha256(payload).hexdigest()


def pull_entries(remote_ns: str, spool: str,
                 pushed: set | None = None) -> dict:
    """Copy remote cache entries into the local spool, digest-verified.

    Only ``*-cache`` entry files WITH a matching ``.sha256`` sidecar are
    accepted: the sidecar is written after the payload (see
    :func:`push_entries`), so a half-written entry on NFS/object storage
    simply has no sidecar yet and is skipped — and a corrupt payload
    (truncated write, bit rot) fails the digest and is **rejected
    loudly** (warning + ``serving_compile_cache_disk_corrupt_total``)
    instead of being handed to XLA.  Returns ``{"pulled", "corrupt",
    "skipped"}``."""
    from tensorflowonspark_tpu import fs

    pulled = corrupt = skipped = 0
    try:
        names = fs.listdir(remote_ns)
    except Exception as e:
        logger.warning("compile cache: cannot list %s: %s", remote_ns, e)
        return {"pulled": 0, "corrupt": 0, "skipped": 0}
    have = set(os.listdir(spool)) if os.path.isdir(spool) else set()
    for name in sorted(names):
        if not name.endswith("-cache"):
            continue
        src = fs.join(remote_ns, name)
        if name in have:
            # already spooled: mark pushed only when the remote SIDECAR
            # digest matches our local bytes — a half-written (no
            # sidecar) or sidecar-divergent remote entry stays
            # un-"pushed" so the next sync() overwrites it with the good
            # local copy (repair).  Payload-only bit rot under an intact
            # sidecar is the fresh puller's full verification to catch;
            # the first process to RECOMPILE that entry repairs it, since
            # a rejected pull never marks the name pushed.
            if pushed is not None:
                try:
                    with fs.open(src + ".sha256", "rb") as f:
                        want = f.read().decode("ascii", "replace").strip()
                    with open(os.path.join(spool, name), "rb") as f:
                        if _digest(f.read()) == want:
                            pushed.add(name)
                except Exception:
                    pass
            continue
        try:
            # sidecar FIRST: the writer's order is payload-then-sidecar,
            # so a readable sidecar proves the payload write finished —
            # reading in the opposite order would race a mid-write into
            # a false "corrupt" alarm instead of a benign skip
            with fs.open(src + ".sha256", "rb") as f:
                want = f.read().decode("ascii", "replace").strip()
            with fs.open(src, "rb") as f:
                payload = f.read()
        except Exception:
            # no sidecar (mid-write by another replica) or transient read
            # failure: not an error, just not loadable yet — and not
            # marked pushed, so a local copy of it would re-push
            skipped += 1
            continue
        if _digest(payload) != want:
            corrupt += 1
            _instruments()[2].inc()
            logger.warning(
                "compile cache: REJECTED corrupt entry %s (digest "
                "mismatch) — recompiling locally instead of loading a "
                "damaged executable (a locally-compiled replacement will "
                "overwrite it on the next sync)", src)
            continue
        tmp = os.path.join(spool, f".{name}.tmp")
        with open(tmp, "wb") as f:
            f.write(payload)
        os.replace(tmp, os.path.join(spool, name))
        if pushed is not None:
            pushed.add(name)  # verified remote copy: never echo it back
        pulled += 1
    return {"pulled": pulled, "corrupt": corrupt, "skipped": skipped}


def push_entries(spool: str, remote_ns: str, pushed: set) -> int:
    """Copy new spool entries to the remote namespace through fs.py.

    Payload first, digest sidecar second — a reader accepts an entry only
    once its sidecar matches, so the non-atomic remote write can never be
    *loaded* half-done (the NFS caveat is documented in DEPLOY.md: the
    window costs a skipped pull, never a bad load)."""
    from tensorflowonspark_tpu import fs

    n = 0
    if not os.path.isdir(spool):
        return 0
    for name in sorted(os.listdir(spool)):
        if not name.endswith("-cache") or name in pushed:
            continue
        with open(os.path.join(spool, name), "rb") as f:
            payload = f.read()
        dst = fs.join(remote_ns, name)
        try:
            with fs.open(dst, "wb") as f:
                f.write(payload)
            with fs.open(dst + ".sha256", "wb") as f:
                f.write(_digest(payload).encode("ascii"))
        except Exception as e:
            logger.warning("compile cache: cannot push %s: %s", dst, e)
            continue
        pushed.add(name)
        n += 1
    return n


def sync() -> int:
    """Push spool entries that are not yet remote; no-op without a fleet
    root.

    Called synchronously after warmup (the warm loop just produced the
    exact entry set the fleet wants) and asynchronously after data-plane
    first-compiles (:func:`sync_async`)."""
    with _SYNC_LOCK:
        if not _STATE["remote_ns"]:
            return 0
        n = push_entries(_STATE["active_dir"], _STATE["remote_ns"],
                         _STATE["pushed"])
        if n:
            logger.info("compile cache: pushed %d new entries to %s", n,
                        _STATE["remote_ns"])
            try:
                from tensorflowonspark_tpu.obs import journal as _journal

                _journal.emit("compile_cache.spool", entries=n,
                              remote_ns=str(_STATE["remote_ns"])[:200])
            except Exception:  # pragma: no cover - best effort
                pass
        return n


def sync_async(delay_s: float = 2.0) -> None:
    """Schedule a :func:`sync` off the compute thread, slightly delayed.

    The trigger is jax's write event, which fires just BEFORE the entry
    file lands in the spool — the delay lets the write (and the rest of
    a warm burst) finish so the last compile of a burst is never left
    unpushed.  At most one sync is scheduled at a time; the scheduled
    flag clears before the push runs, so a write landing mid-push
    schedules a fresh pass that picks it up."""
    if not _STATE["remote_ns"]:
        return
    with _LOCK:
        if _STATE.get("sync_scheduled"):
            return
        _STATE["sync_scheduled"] = True

    def _run():
        import time

        time.sleep(delay_s)
        with _LOCK:
            _STATE["sync_scheduled"] = False
        try:
            sync()
        except Exception:  # pragma: no cover - never fail a compile path
            logger.warning("compile cache: background sync failed",
                           exc_info=True)

    threading.Thread(target=_run, name="tfos-compile-cache-sync",
                     daemon=True).start()


# ---------------------------------------------------------------------------
# Counters + event attribution
# ---------------------------------------------------------------------------


def _instruments():
    global _INSTRUMENTS
    if _INSTRUMENTS is None:
        from tensorflowonspark_tpu import obs

        _INSTRUMENTS = (
            obs.counter(
                "serving_compile_cache_disk_hits_total",
                "compiles served from the persistent compile cache (an "
                "XLA executable loaded from disk instead of compiled — "
                "neither an in-process jit hit nor a true miss)"),
            obs.counter(
                "compile_cache_disk_writes_total",
                "XLA executables this process wrote to the persistent "
                "compile cache as files (each one is a compile some other "
                "process can now skip); a put that wrote nothing — the "
                "entry was there, or is larger than the cache — is not "
                "one"),
            obs.counter(
                "serving_compile_cache_disk_corrupt_total",
                "persistent-cache entries REJECTED on pull (digest "
                "mismatch: truncated or damaged remote entry)"),
            obs.histogram(
                "serving_compile_disk_seconds",
                "wall time to retrieve one executable from the "
                "persistent compile cache (the disk half split out of "
                "serving_compile_seconds)", buckets=_DISK_BUCKETS))
    return _INSTRUMENTS


def _install_listeners() -> None:
    global _LISTENING
    if _LISTENING:
        return
    from tensorflowonspark_tpu import util

    util.ensure_jax_platform()
    from jax import monitoring

    monitoring.register_event_listener(_on_event)
    monitoring.register_event_duration_secs_listener(_on_duration)
    monitoring.register_event_time_span_listener(_on_time_span)
    _LISTENING = True


def _notes() -> dict:
    """What the cache's events said on this thread since its last
    ``jit.compile`` span: they fire inside the compile's stretch, before
    its time-span event does."""
    notes = getattr(_TLS, "notes", None)
    if notes is None:
        notes = _TLS.notes = {}
    return notes


def _on_event(event: str, **kw) -> None:
    # runs inside jax's compile path: must never raise
    try:
        if event == _EV_REQUEST:
            _notes()["requested"] = True
        elif event == _EV_HIT:
            _instruments()[0].inc()
            _TLS.hits = getattr(_TLS, "hits", 0) + 1
            _notes()["hit"] = True
        elif event == _EV_WRITE and _STATE["active_dir"]:
            _count_files_written()
            sync_async()
    except Exception:  # pragma: no cover
        pass


def _count_files_written() -> None:
    """Feed the writes counter from the put itself.  jax fires its write
    event BEFORE it hands the entry to its cache, and that put may write
    nothing (the entry is there already; it is larger than the cache; a
    cache written without a size limit refuses one written with it, PR
    21) — counting the event counted attempts.  So on the first write
    event the cache object's ``put`` is wrapped (the event fires just
    before jax looks ``put`` up, so the wrapper already sees that very
    write) to see whether the entry's own file is there after it and was
    not before — not what the directory gained, which a put that evicts
    older entries to make room makes zero or less.  The put's size and
    outcome are left for the ``jit.compile`` span of the stretch it runs
    in."""
    from jax._src import compilation_cache as cc

    cache = cc._cache
    if cache is None or getattr(cache, "_tfos_counted", False):
        return
    put = cache.put

    def counted_put(key, val):
        path = os.path.join(_STATE["active_dir"], f"{key}-cache")
        before = os.path.exists(path)
        try:
            put(key, val)
        finally:
            written = int(os.path.exists(path) and not before)
            _notes().update(entry_bytes=len(val), written=written)
            if written:
                _instruments()[1].inc()

    cache.put = counted_put
    cache._tfos_counted = True


def _on_duration(event: str, duration: float, **kw) -> None:
    try:
        if event == _DUR_RETRIEVAL:
            _instruments()[3].observe(float(duration))
            _notes()["retrieval_s"] = float(duration)
        elif event == _DUR_SAVED:
            _notes()["saved_s"] = float(duration)
    except Exception:  # pragma: no cover
        pass


def _on_time_span(event: str, start: float, end: float, fun_name: str = "",
                  **kw) -> None:
    """One trace, lowering or backend compile of JAX's, as a ring span on
    the compiling thread (``start`` / ``end`` are JAX's reads of the wall
    clock the ring is on).  Runs inside jax's compile path: must never raise."""
    try:
        from tensorflowonspark_tpu import obs

        if event == _SPAN_TRACE:
            obs.counter("jit_traces_total",
                        "functions JAX traced to a jaxpr in this process "
                        "(every jit, the jnp primitives' own included); "
                        "none in a steady step").inc()
            if end - start >= TRACE_SPAN_FLOOR_S:
                obs.complete("jit.trace", start, end - start, fun=fun_name)
        elif event == _SPAN_LOWER:
            obs.complete("jit.lower", start, end - start, fun=fun_name)
        elif event == _SPAN_COMPILE:
            notes, _TLS.notes = _notes(), None
            if not (active() and notes.get("requested")):
                # jax "requests" its cache with no directory set too
                attrs = {"cache": "off"}
            elif notes.get("hit"):
                attrs = {"cache": "hit",
                         "retrieval_s": notes.get("retrieval_s"),
                         "saved_s": notes.get("saved_s")}
            else:
                attrs = {"cache": "miss",
                         "entry_bytes": notes.get("entry_bytes", 0),
                         "written": notes.get("written", 0)}
                obs.counter("compile_cache_disk_misses_total",
                            "backend compiles that asked the persistent "
                            "compile cache and were not served from it "
                            "(XLA compiled; the put may or may not have "
                            "written a file)").inc()
            obs.complete("jit.compile", start, end - start, fun=fun_name,
                         **attrs)
    except Exception:
        pass


def thread_disk_hits() -> int:
    """Disk hits observed ON THIS THREAD since process start.

    jax's monitoring events fire synchronously on the compiling thread,
    so a caller that snapshots this before a forward and compares after
    knows whether *its own* compile was served from disk — the exact
    attribution ``serving.note_compile``'s hit/miss/disk split needs,
    immune to concurrent compiles on other threads."""
    return getattr(_TLS, "hits", 0)


def stats() -> dict[str, Any]:
    """JSON-able cache state for ``/healthz`` and the bench child.

    Reads counters via ``Registry.peek`` — the instruments are minted by
    the cache's own event listeners, and a /healthz scrape on a
    cache-off process must not publish phantom 0 disk series on
    /metrics (the ``Registry.peek`` discipline)."""
    from tensorflowonspark_tpu import obs

    reg = obs.get_registry()

    def val(name: str) -> int:
        inst = reg.peek(name)
        return int(inst.value) if inst is not None else 0

    return {
        "enabled": enabled(),
        "dir": _STATE["active_dir"],
        "namespace": _STATE["namespace"],
        "remote": bool(_STATE["remote_ns"]),
        "error": _STATE["error"],
        "disk_hits": val("serving_compile_cache_disk_hits_total"),
        "disk_writes": val("compile_cache_disk_writes_total"),
        "disk_corrupt": val("serving_compile_cache_disk_corrupt_total"),
    }
