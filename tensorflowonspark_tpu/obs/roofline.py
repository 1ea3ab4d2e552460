"""In-run hardware roofline probes: delivered HBM + interconnect bandwidth.

Rounds 4-5 exposed a measurement-integrity hole: the MFU roofline in
``BENCH_NOTES.md`` rests on a *datasheet* bandwidth claim that no run ever
verified, so nothing in-tree would notice if a healthy chip appeared and the
framework still ran at MFU 0.30 (VERDICT r5).  This module closes the hole
the MLPerf way (PAPERS.md): the system measures its own rooflines, every
run, and publishes them beside the throughput number they contextualise —

- **memory bandwidth** (:func:`measure_memory_bandwidth`): a big elementwise
  op (read N + write N bytes) and a reduction (read N bytes, write a
  scalar), each timed to a host ``device_get`` of a value that
  *data-depends* on the op — a readiness ack can lie (BENCH_NOTES.md
  timing methodology), a fetched byte cannot;
- **interconnect all-reduce bandwidth** (:func:`measure_ici_bandwidth`): a
  ``psum`` over all local devices, reported as the per-device ring
  all-reduce bandwidth ``2*S*(n-1)/n / dt`` — ``None`` with a reason on a
  single device (there is no interconnect to measure);
- **cross-slice DCN bandwidth** (:func:`measure_dcn_bandwidth`): the same
  collective over one device per slice, so the ring crosses only the
  data-centre network; ``None`` + reason on a single-slice topology;
- :func:`probe` runs all three, never raises, and mirrors the results into
  the process obs registry (``roofline_mem_bw_gbps`` /
  ``roofline_ici_bw_gbps`` / ``roofline_dcn_bw_gbps`` gauges) so they ride
  the MetricsReporter publications like every other instrument.

``bench.py`` calls :func:`probe` after its timing loop and stamps
``mem_bw_gbps`` / ``ici_bw_gbps`` into every BENCH JSON (explicit ``null``
+ reason when unmeasurable), so a healthy-bandwidth chip automatically
re-litigates the 0.30-vs-0.53 MFU question: measured-bw ≈ datasheet with
MFU stuck at 0.30 indicts the framework; degraded measured-bw indicts the
chip.
"""

from __future__ import annotations

import logging
import time
from typing import Any

logger = logging.getLogger(__name__)

#: datasheet HBM bandwidth (GB/s per chip) keyed by a substring of
#: ``device_kind`` — same matching scheme as bench.py's PEAK_FLOPS table.
#: Used only to contextualise the *measured* number (``frac_of_peak``).
HBM_PEAK_GBPS = [
    ("v5 lite", 819.0), ("v5e", 819.0),
    ("v5p", 2765.0), ("v5", 2765.0),
    ("v6", 1640.0), ("trillium", 1640.0),
    ("v4", 1228.0),
    ("v3", 900.0),
    ("v2", 700.0),
]

#: default working-set bytes: big enough that one op dwarfs dispatch/fetch
#: overhead on HBM, small enough to stay cheap on the CPU test backend
_ACCEL_BYTES = 256 * 1024 * 1024
_CPU_BYTES = 32 * 1024 * 1024


def _default_bytes() -> int:
    """Working-set size: ``TFOS_ROOFLINE_BYTES`` override, else by
    backend (CI shrinks it so bench children stay cheap)."""
    import os

    env = os.environ.get("TFOS_ROOFLINE_BYTES")
    if env:
        try:
            return max(4096, int(env))
        except ValueError:
            pass
    import jax

    on_accel = jax.default_backend() in ("tpu", "gpu")
    return _ACCEL_BYTES if on_accel else _CPU_BYTES


def hbm_peak_gbps(device_kind: str) -> float | None:
    kind = (device_kind or "").lower()
    for key, peak in HBM_PEAK_GBPS:
        if key in kind:
            return peak
    return None


def _fetch_scalar(x) -> float:
    """Host round-trip of one element — data-dependent proof of completion."""
    import jax
    import numpy as np

    return float(np.asarray(jax.device_get(x)).ravel()[0])


def _fetch_first_local(arr) -> float:
    """Host round-trip of ONE element of the local shard — the same
    data-dependent completion proof as :func:`_fetch_scalar`, but
    addressable from EVERY process of a multi-host pod (indexing row 0 of
    a globally-sharded array is only fetchable where device 0 lives).
    The slice happens on-device so the fetch moves 4 bytes, not the
    shard (a shard-sized device_get would inflate every timed sample by
    the very transfer being measured)."""
    import numpy as np

    return float(np.asarray(arr.addressable_shards[0].data[:1, :1])
                 .ravel()[0])


def _best_time(fn, repeats: int) -> float:
    """Best-of-N wall time of ``fn()`` (bandwidth = peak of the samples;
    the min is the least-interfered measurement)."""
    best = float("inf")
    for _ in range(repeats):
        t0 = time.perf_counter()
        fn()
        best = min(best, time.perf_counter() - t0)
    return best


def _dispatch_overhead(repeats: int) -> float:
    """Fixed per-measurement cost (dispatch + scalar fetch), estimated on a
    trivially small op and subtracted from every timed sample."""
    import jax
    import jax.numpy as jnp

    tiny = jnp.ones((8,), jnp.float32)
    f = jax.jit(lambda a: a * 1.0001 + 0.5)
    _fetch_scalar(f(tiny))  # compile outside the clock
    return _best_time(lambda: _fetch_scalar(f(tiny)), repeats)


def measure_memory_bandwidth(size_bytes: int | None = None,
                             repeats: int = 3) -> dict[str, Any]:
    """Delivered memory bandwidth via elementwise + reduction patterns.

    Returns ``{"elementwise_gbps", "reduction_gbps", "array_mb"}``.
    Elementwise moves ``2*N`` bytes (read + write), the reduction ``N``
    (read; the scalar write is noise).  Both are timed to a data-dependent
    scalar fetch with the dispatch/fetch overhead subtracted.
    """
    import jax
    import jax.numpy as jnp

    if size_bytes is None:
        size_bytes = _default_bytes()
    n = max(1024, int(size_bytes) // 4)
    x = jnp.ones((n,), jnp.float32)
    elementwise = jax.jit(lambda a: a * 1.0001 + 0.5)
    reduction = jax.jit(jnp.sum)
    # compile + first-touch outside the clock
    _fetch_scalar(elementwise(x)[:1])
    _fetch_scalar(reduction(x))
    overhead = _dispatch_overhead(repeats)

    dt_ew = _best_time(lambda: _fetch_scalar(elementwise(x)[:1]), repeats)
    dt_red = _best_time(lambda: _fetch_scalar(reduction(x)), repeats)

    def bw(bytes_moved: float, dt: float) -> float | None:
        # an op not comfortably above the dispatch overhead cannot be
        # attributed to memory traffic: report unmeasurable rather than
        # the absurd number the subtraction would produce (the whole
        # module exists to keep artifacts honest)
        if dt < 2.0 * overhead:
            return None
        return bytes_moved / (dt - overhead) / 1e9

    return {
        "elementwise_gbps": bw(2.0 * n * 4, dt_ew),
        "reduction_gbps": bw(n * 4.0, dt_red),
        "array_mb": round(n * 4 / 1e6, 1),
        "overhead_s": overhead,
    }


def measure_ici_bandwidth(size_bytes_per_device: int | None = None,
                          repeats: int = 3) -> dict[str, Any]:
    """All-reduce (``psum``) bandwidth across all local devices.

    Reported as the per-device ring all-reduce bandwidth
    ``2*S*(n-1)/n / dt`` — the standard algorithmic-bandwidth convention,
    comparable across world sizes.  Returns ``{"gbps": None, "reason": ...}``
    on a single device.

    The collective is a ``shard_map`` + explicit ``psum`` over a 1-D mesh
    — the SAME flavor the bucketed train-step path issues per gradient
    bucket (``parallel/collectives.py``), so ``allreduce_overlap_frac``
    divides exposed comm by an ideal measured through a like-for-like
    dispatch/lowering path (the previous ``jax.pmap`` probe measured a
    lowering the step path never uses).
    """
    import jax
    import jax.numpy as jnp
    import numpy as np
    from jax.sharding import PartitionSpec as P

    from tensorflowonspark_tpu.parallel import mesh as mesh_lib

    n_dev = jax.device_count()  # GLOBAL: the psum axis spans all hosts
    if n_dev < 2:
        return {"gbps": None, "reason": "single device: no interconnect"}
    if size_bytes_per_device is None:
        size_bytes_per_device = _default_bytes() // 4
    s = max(1024, int(size_bytes_per_device) // 4)
    mesh = jax.sharding.Mesh(np.asarray(jax.devices()), ("ici",))
    sharded = jax.sharding.NamedSharding(mesh, P("ici"))
    # materialise the operand ON the mesh inside jit (a global shape works
    # on multi-host pods, where no process could build the full array)
    x = jax.jit(lambda: jnp.ones((n_dev, s), jnp.float32),
                out_shardings=sharded)()
    allreduce = jax.jit(mesh_lib.shard_map_unchecked(
        lambda a: jax.lax.psum(a, "ici"), mesh,
        in_specs=P("ici"), out_specs=P("ici")))
    # fetch from the LOCAL shard: every process of a multi-host pod can
    # prove completion from its own slice (row 0 lives on process 0 only)
    _fetch_first_local(allreduce(x))  # compile outside the clock
    # same honesty contract as the memory probe: subtract the dispatch /
    # fetch overhead (BENCH_NOTES timing methodology), and refuse to
    # stamp a number an overhead-dominated sample would massively
    # understate
    overhead = _dispatch_overhead(repeats)
    dt = _best_time(lambda: _fetch_first_local(allreduce(x)), repeats)
    if dt < 2.0 * overhead:
        return {"gbps": None, "n_devices": n_dev,
                "reason": "probe dominated by dispatch overhead "
                          f"(~{overhead * 1e3:.1f} ms); raise "
                          "TFOS_ROOFLINE_BYTES"}
    moved = 2.0 * s * 4 * (n_dev - 1) / n_dev
    return {"gbps": moved / (dt - overhead) / 1e9, "n_devices": n_dev,
            "array_mb_per_device": round(s * 4 / 1e6, 1)}


def _slice_groups() -> dict[int, list]:
    """Devices grouped by ``slice_index`` (the PJRT attribute a
    multi-slice TPU runtime sets; absent → slice 0)."""
    import jax

    groups: dict[int, list] = {}
    for d in jax.devices():
        groups.setdefault(int(getattr(d, "slice_index", 0) or 0), []).append(d)
    return groups


def measure_dcn_bandwidth(size_bytes_per_device: int | None = None,
                          repeats: int = 3) -> dict[str, Any]:
    """Cross-slice (DCN-class) all-reduce bandwidth.

    Groups devices by their ``slice_index`` (the PJRT attribute a
    multi-slice TPU runtime sets; absent → slice 0) and runs the
    :func:`measure_ici_bandwidth` collective over ONE device per slice —
    a 1-D mesh whose only axis crosses the data-centre network, so the
    ring traverses no ICI link and the measured figure is the DCN tier's
    own delivered bandwidth.  Returns ``{"gbps": None, "reason": ...}``
    on a single-slice (or single-device) topology — there is no DCN to
    measure, and stamping a number would launder an ICI figure into a
    DCN field.
    """
    import jax
    import jax.numpy as jnp
    import numpy as np
    from jax.sharding import PartitionSpec as P

    from tensorflowonspark_tpu.parallel import mesh as mesh_lib

    groups = _slice_groups()
    if len(groups) < 2:
        return {"gbps": None,
                "reason": f"single slice ({len(jax.devices())} devices): "
                          "no cross-slice interconnect"}
    ring = [groups[k][0] for k in sorted(groups)]
    n = len(ring)
    if size_bytes_per_device is None:
        size_bytes_per_device = _default_bytes() // 4
    s = max(1024, int(size_bytes_per_device) // 4)
    mesh = jax.sharding.Mesh(np.asarray(ring), ("dcn",))
    sharded = jax.sharding.NamedSharding(mesh, P("dcn"))
    x = jax.jit(lambda: jnp.ones((n, s), jnp.float32),
                out_shardings=sharded)()
    allreduce = jax.jit(mesh_lib.shard_map_unchecked(
        lambda a: jax.lax.psum(a, "dcn"), mesh,
        in_specs=P("dcn"), out_specs=P("dcn")))
    _fetch_first_local(allreduce(x))  # compile outside the clock
    overhead = _dispatch_overhead(repeats)
    dt = _best_time(lambda: _fetch_first_local(allreduce(x)), repeats)
    if dt < 2.0 * overhead:
        return {"gbps": None, "n_slices": n,
                "reason": "probe dominated by dispatch overhead "
                          f"(~{overhead * 1e3:.1f} ms); raise "
                          "TFOS_ROOFLINE_BYTES"}
    moved = 2.0 * s * 4 * (n - 1) / n
    return {"gbps": moved / (dt - overhead) / 1e9, "n_slices": n,
            "array_mb_per_device": round(s * 4 / 1e6, 1)}


def probe(size_bytes: int | None = None, repeats: int = 3,
          registry=None) -> dict[str, Any]:
    """Run the full roofline probe suite; never raises.

    Returns a flat dict with ``mem_bw_gbps`` / ``ici_bw_gbps`` /
    ``dcn_bw_gbps`` always present (``None`` plus a ``*_reason`` when
    unmeasurable) and mirrors the measured values into the obs registry
    as gauges (``roofline_mem_bw_gbps``,
    ``roofline_mem_bw_reduction_gbps``, ``roofline_ici_bw_gbps``,
    ``roofline_dcn_bw_gbps``).
    """
    from tensorflowonspark_tpu.obs import registry as reg_mod
    from tensorflowonspark_tpu.obs import trace as trace_mod

    reg = registry if registry is not None else reg_mod.get_registry()
    out: dict[str, Any] = {"mem_bw_gbps": None, "ici_bw_gbps": None,
                           "dcn_bw_gbps": None}
    t0 = time.perf_counter()
    with trace_mod.get_tracer().span("roofline.probe"):
        try:
            import jax

            out["platform"] = jax.default_backend()
            out["n_devices"] = len(jax.devices())
            device_kind = jax.devices()[0].device_kind
        except Exception as e:
            out["mem_bw_reason"] = out["ici_bw_reason"] = \
                f"no jax backend: {e!r}"[:200]
            return out
        try:
            mem = measure_memory_bandwidth(size_bytes, repeats)
            measured = [v for v in (mem["elementwise_gbps"],
                                    mem["reduction_gbps"]) if v is not None]
            if not measured:
                out["mem_bw_reason"] = (
                    "probe dominated by dispatch overhead "
                    f"(~{mem['overhead_s'] * 1e3:.1f} ms); working set too "
                    "small — raise TFOS_ROOFLINE_BYTES")
            else:
                # headline = the faster pattern (delivered bandwidth is
                # the max the hardware sustained for ANY measured pattern)
                out["mem_bw_gbps"] = round(max(measured), 2)
                for key, v in (("mem_bw_elementwise_gbps",
                                mem["elementwise_gbps"]),
                               ("mem_bw_reduction_gbps",
                                mem["reduction_gbps"])):
                    if v is not None:
                        out[key] = round(v, 2)
                out["mem_bw_array_mb"] = mem["array_mb"]
                peak = hbm_peak_gbps(device_kind)
                if peak and out["platform"] in ("tpu", "gpu"):
                    out["hbm_peak_gbps"] = peak
                    out["mem_bw_frac_of_peak"] = round(
                        out["mem_bw_gbps"] / peak, 4)
                reg.gauge("roofline_mem_bw_gbps").set(out["mem_bw_gbps"])
                if mem["reduction_gbps"] is not None:
                    reg.gauge("roofline_mem_bw_reduction_gbps").set(
                        round(mem["reduction_gbps"], 2))
        except Exception as e:
            out["mem_bw_reason"] = f"memory probe failed: {e!r}"[:300]
            logger.warning("roofline memory probe failed: %s", e)
        try:
            ici = measure_ici_bandwidth(repeats=repeats)
            if ici.get("gbps") is not None:
                out["ici_bw_gbps"] = round(ici["gbps"], 2)
                reg.gauge("roofline_ici_bw_gbps").set(out["ici_bw_gbps"])
            else:
                out["ici_bw_reason"] = ici.get("reason", "unmeasurable")
        except Exception as e:
            out["ici_bw_reason"] = f"interconnect probe failed: {e!r}"[:300]
            logger.warning("roofline interconnect probe failed: %s", e)
        try:
            dcn = measure_dcn_bandwidth(repeats=repeats)
            if dcn.get("gbps") is not None:
                out["dcn_bw_gbps"] = round(dcn["gbps"], 2)
                out["dcn_n_slices"] = dcn.get("n_slices")
                reg.gauge("roofline_dcn_bw_gbps").set(out["dcn_bw_gbps"])
            else:
                out["dcn_bw_reason"] = dcn.get("reason", "unmeasurable")
        except Exception as e:
            out["dcn_bw_reason"] = f"DCN probe failed: {e!r}"[:300]
            logger.warning("roofline DCN probe failed: %s", e)
    out["probe_s"] = round(time.perf_counter() - t0, 3)
    return out
