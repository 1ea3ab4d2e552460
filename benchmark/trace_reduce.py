"""From a profiler trace (``.xplane.pb``) to the numbers the benchmark
reports: device busy and idle time, per-operation sums, idle gaps named by
what the host was doing, host spans and transfer time.

Reads the trace with ``jax.profiler.ProfileData`` (nothing but JAX), so it
runs in the trainer process, never in the launcher.  All times in seconds.

What a TPU trace holds (looked at by hand, v5e, PR 23): one plane
``/device:TPU:<n>`` a chip with the lines ``XLA Ops`` (one event an
executed HLO operation, named by its whole HLO text), ``XLA Modules`` and
``Steps``; and ``/host:CPU`` with one line a thread, where the benchmark's
``TraceAnnotation`` spans sit on the line of the thread that opened them
and the runtime's own events (``XlaLinearize``, ``H2D Dispatch``,
``tpu::System::TransferToDevice``) on its worker threads.
"""

from __future__ import annotations

import re

DEVICE_PLANE = re.compile(r"^/device:(TPU|GPU):\d+$")
OPS_LINES = ("XLA Ops", "XLA Modules")
#: host events that are a transfer to the device or its host-side layout work
TRANSFER = re.compile(r"TransferToDevice|XlaLinearize|H2D Dispatch")
#: the benchmark's spans, in the order an idle gap is attributed to them
SPANS = ("feed_wait", "step_dispatch", "stage_batch")
WINDOW_SPAN = "traced_steps"


def union(intervals: list) -> list:
    """Merge ``(start, end)`` pairs into disjoint sorted intervals."""
    merged: list = []
    for start, end in sorted(intervals):
        if merged and start <= merged[-1][1]:
            if end > merged[-1][1]:
                merged[-1][1] = end
        else:
            merged.append([start, end])
    return merged


def total(intervals: list) -> float:
    return sum(end - start for start, end in intervals)


def clip(intervals: list, lo: float, hi: float) -> list:
    return [(max(s, lo), min(e, hi)) for s, e in intervals
            if min(e, hi) > max(s, lo)]


def overlap(a: list, b: list) -> float:
    """Total overlap of two lists of disjoint sorted intervals."""
    i = j = 0
    out = 0.0
    while i < len(a) and j < len(b):
        lo, hi = max(a[i][0], b[j][0]), min(a[i][1], b[j][1])
        if hi > lo:
            out += hi - lo
        if a[i][1] < b[j][1]:
            i += 1
        else:
            j += 1
    return out


def gaps(busy: list, lo: float, hi: float) -> list:
    """The complement of disjoint sorted ``busy`` within ``[lo, hi]``."""
    out, at = [], lo
    for start, end in busy:
        if start > at:
            out.append((at, start))
        at = max(at, end)
    if hi > at:
        out.append((at, hi))
    return out


def op_name(event_name: str) -> str:
    """``%fusion.12 = bf16[...] fusion(...)`` -> ``fusion.12``."""
    head = event_name.split(" = ", 1)[0].strip()
    return head.lstrip("%") or event_name[:64]


def read_planes(path: str) -> list:
    """``[(plane name, [(line name, [(name, start_s, end_s), ...]), ...])]``
    of one ``.xplane.pb`` (or ``.xplane.pb.gz``) file."""
    from jax.profiler import ProfileData

    if path.endswith(".gz"):
        import gzip

        with gzip.open(path, "rb") as f:
            data = ProfileData.from_serialized_xspace(f.read())
    else:
        data = ProfileData.from_file(path)
    planes = []
    for plane in data.planes:
        lines = []
        for line in plane.lines:
            events = [(ev.name, ev.start_ns * 1e-9,
                       (ev.start_ns + ev.duration_ns) * 1e-9)
                      for ev in line.events]
            lines.append((line.name, events))
        planes.append((plane.name, lines))
    return planes


def reduce_planes(planes: list) -> dict:
    """The reduction proper, on what :func:`read_planes` returns."""
    host_spans: dict = {name: [] for name in SPANS + (WINDOW_SPAN,)}
    transfers = []
    host_events: list = []
    for plane_name, lines in planes:
        if not plane_name.startswith("/host:"):
            continue
        for _line, events in lines:
            mine = [(s, e) for name, s, e in events if TRANSFER.search(name)]
            transfers.extend(union(mine))     # nested events count once
            for name, s, e in events:
                if name in host_spans:
                    host_spans[name].append((s, e))
                else:
                    host_events.append((name, s, e))
    # the traced window: the span the benchmark opens round the traced
    # steps, or (a trace recorded without it) the hull of its other spans
    hull = host_spans[WINDOW_SPAN] or [iv for name in SPANS
                                       for iv in host_spans[name]]
    if not hull:
        raise ValueError("the trace has none of the benchmark's spans "
                         f"({WINDOW_SPAN}, {', '.join(SPANS)})")
    lo = min(s for s, _ in hull)
    hi = max(e for _, e in hull)

    devices = []
    for plane_name, lines in planes:
        if not DEVICE_PLANE.match(plane_name):
            continue
        by_line = dict(lines)
        events = next((by_line[n] for n in OPS_LINES if by_line.get(n)), [])
        inside = [(n, max(s, lo), min(e, hi)) for n, s, e in events
                  if min(e, hi) > max(s, lo)]
        busy = union([(s, e) for _, s, e in inside])
        ops: dict = {}
        for name, s, e in inside:
            key = op_name(name)
            ops[key] = ops.get(key, 0.0) + (e - s)
        devices.append({"plane": plane_name, "busy": busy,
                        "busy_s": total(busy), "ops": ops,
                        "events": len(inside)})
    if not devices:
        raise ValueError("the trace has no device plane with operations")

    host_sums: dict = {}
    for name, s, e in host_events:
        if min(e, hi) > max(s, lo):
            key = name[:64]
            host_sums[key] = host_sums.get(key, 0.0) + min(e, hi) - max(s, lo)
    window_s = hi - lo
    spans = {name: union(clip(host_spans[name], lo, hi)) for name in SPANS}
    idle: dict = {}
    for dev in devices:
        dev_gaps = gaps(dev["busy"], lo, hi)
        left = total(dev_gaps)
        taken: list = []
        for name in SPANS:
            # a gap counts under the first span (in SPANS order) it
            # overlaps; what no span covers is "other"
            free = spans[name]
            for prev in taken:
                free = _subtract(free, prev)
            part = overlap(dev_gaps, free)
            idle[name] = idle.get(name, 0.0) + part
            left -= part
            taken.append(spans[name])
        idle["other"] = idle.get("other", 0.0) + max(left, 0.0)
    n_dev = len(devices)
    op_sums: dict = {}
    for dev in devices:
        for key, secs in dev["ops"].items():
            op_sums[key] = op_sums.get(key, 0.0) + secs / n_dev
    steps = len(clip(host_spans["step_dispatch"], lo, hi))
    return {
        "window_s": window_s,
        "busy_s": sum(d["busy_s"] for d in devices) / n_dev,
        "devices": [{"plane": d["plane"], "busy_s": d["busy_s"],
                     "events": d["events"]} for d in devices],
        "device_ops": sorted(([k, v] for k, v in op_sums.items()),
                             key=lambda kv: -kv[1])[:10],
        "idle_gaps": sorted(([k, v / n_dev] for k, v in idle.items()
                             if v > 0), key=lambda kv: -kv[1])[:10],
        "host_spans": {name: {"count": len(clip(host_spans[name], lo, hi)),
                              "total_s": total(spans[name])}
                       for name in SPANS},
        # summed over threads: host time spent on transfers, not wall time
        "transfer_s": total(clip(transfers, lo, hi)),
        # the runtime's own host events, summed over threads (nested
        # events each count): what the host was busy with, for PERF.md
        "host_events": sorted(([k, v] for k, v in host_sums.items()),
                              key=lambda kv: -kv[1])[:10],
        "steps": steps,
    }


def _subtract(a: list, b: list) -> list:
    """Disjoint sorted ``a`` minus disjoint sorted ``b``."""
    out = []
    for s, e in a:
        at = s
        for bs, be in b:
            if be <= at or bs >= e:
                continue
            if bs > at:
                out.append((at, bs))
            at = max(at, be)
        if at < e:
            out.append((at, e))
    return out


def reduce_file(path: str) -> dict:
    return reduce_planes(read_planes(path))
