"""The device's side of every step, from the program's own record: the
``trainer.device_step`` and ``trainer.h2d`` spans that ``Trainer``'s completion
watcher writes to the ring (PR 37), reduced to the idle share of the
*untraced* steps, its split by what the host was doing, and — in a traced
run — the estimate's error against the profiler.

**These are the host's clock reads, not the device's.**  A
``trainer.device_step`` that began at its dispatch's start holds the enqueue
and the runtime's launch, and every one ends a thread's wake-up late: the
spans are an upper estimate of the device's busy time, so ``idle_pct`` is the
**floor** of the idle share and ``enqueue_pct`` the room above it that the
host cannot resolve (the device's first operation lies somewhere inside the
dispatch, or shortly after).  ``estimate_error_pct`` says how far the estimate
stands from the profiler's busy time where a profiler watched.

A step's **period** runs from the previous step's end on the device to its
own: the **gap** in which the device waited, then the ``trainer.device_step``
interval.  **Untraced steps** are the window's steps that the profiler session
did not see (``traced()["step_starts"]``), but the one after the last of
those: its period holds the session's write-out.  A run with no trace has only
untraced steps.

No JAX is imported here (the readers run in the launcher); the ``.xplane.pb``
is read by ``program_spans.traced``'s child.  A record that lost events, or a
program that writes no such span (the parent of PR 37), makes every reader
return None.  All times in seconds, ring spans on ``time.time()``.
"""

from __future__ import annotations

import json
import os

from benchmark import program_spans, stats, trace_reduce

DEVICE_STEP = "trainer.device_step"
H2D = "trainer.h2d"
#: the consumer has no batch to step on while one of these is open
FEED_SPANS = ("feed.wait", "feed.turnround")
PARTS = ("feed", "h2d", "host")


def _profiled_steps(run: dict) -> set:
    """``step`` of the steps whose period the profiler session touched."""
    reduced = program_spans.traced(run)
    seen = {int(step) for step in (reduced or {}).get("step_starts", {})}
    if seen:
        seen.add(max(seen) + 1)
    return seen


def untraced(run: dict):
    """The window's untraced steps whose predecessor is in the window too,
    by ``step``: ``{"step", "gap": (t0, t1), "dur_s", "input_wait_s",
    "enqueue_s", "feed_s", "h2d_s", "host_s"}`` — the last three split the gap
    and sum to it; ``enqueue_s`` is the dispatch's wall where the dispatch's
    start began the step (the device may have begun anywhere inside it).
    None without the spans."""
    if "_device_steps" in run:
        return run["_device_steps"]
    run["_device_steps"] = None
    found = program_spans.spans(run, DEVICE_STEP)
    if not found:
        return None
    profiled = _profiled_steps(run)
    feed = trace_reduce.union([
        (s["t0"], s["t1"]) for name in FEED_SPANS
        for s in program_spans.spans(run, name, whole_job=True) or []])
    by_step = {s["args"].get("step"): s for s in found}
    rows = []
    for step, span in sorted(by_step.items()):
        before = by_step.get(step - 1)
        if before is None or step in profiled:
            continue
        gap = (before["t1"], max(before["t1"], span["t0"]))
        feed_s = trace_reduce.overlap([gap], feed)
        late = float(span["args"].get("input_wait_s") or 0.0)
        h2d_s = 0.0
        if span["args"].get("after") == "input" and late > 0:
            # past the dispatch's start, the batch not yet on the device
            waited = trace_reduce.clip([(gap[1] - late, gap[1])], *gap)
            h2d_s = (trace_reduce.total(waited)
                     - trace_reduce.overlap(waited, feed))
        dur_s = span["t1"] - span["t0"]
        enqueue_s = 0.0
        if span["args"].get("after") == "dispatch":
            enqueue_s = min(dur_s, float(span["args"].get("dispatch_s") or 0))
        rows.append({
            "step": step, "gap": gap, "dur_s": dur_s, "input_wait_s": late,
            "enqueue_s": enqueue_s, "feed_s": feed_s, "h2d_s": h2d_s,
            "host_s": gap[1] - gap[0] - feed_s - h2d_s})
    run["_device_steps"] = rows or None
    if rows:
        _write_summary(run, rows, found, profiled)
    return run["_device_steps"]


def _period_s(rows: list) -> float:
    return sum(r["gap"][1] - r["gap"][0] + r["dur_s"] for r in rows)


def _session(found: list, profiled: set):
    """The profiler session on ``time.time()``: from the start of the first
    profiled step's period to the end of the step after the last (the
    write-out); None without one."""
    inside = [s for s in found if s["args"].get("step") in profiled]
    if not inside:
        return None
    first = min(s["args"]["step"] for s in inside)
    before = [s["t1"] for s in found if s["args"]["step"] == first - 1]
    return (before[0] if before else inside[0]["t0"],
            max(s["t1"] for s in inside))


def transfers(run: dict):
    """The window's ``trainer.h2d`` spans, ``(outside, under)`` the profiler
    session; None without the spans."""
    if "_transfers" in run:
        return run["_transfers"]
    run["_transfers"] = None
    steps = program_spans.spans(run, DEVICE_STEP)
    found = program_spans.spans(run, H2D)
    if not steps or found is None:
        return None
    session = _session(steps, _profiled_steps(run))
    outside, under = [], []
    for s in found:
        hit = session and s["t0"] < session[1] and s["t1"] > session[0]
        (under if hit else outside).append(s)
    run["_transfers"] = (outside, under)
    return run["_transfers"]


def transfer_ms(run: dict):
    """Median ``trainer.h2d`` of the window's batches staged outside the
    profiler session: the staging call's start to the batch whole on the
    device."""
    split = transfers(run)
    if not split or not split[0]:
        return None
    return 1e3 * stats.median([s["t1"] - s["t0"] for s in split[0]])


def enqueue_pct(run: dict):
    """Over the untraced steps: 100 x the dispatches' wall of the steps that
    began at their dispatch's start, over the periods.  The device's first
    operation lies somewhere inside such a dispatch or shortly after it, so
    the idle share is ``idle_pct`` plus up to about this."""
    rows = untraced(run)
    if not rows:
        return None
    return 100.0 * sum(r["enqueue_s"] for r in rows) / _period_s(rows)


def idle_pct(run: dict, parts=PARTS):
    """Over the untraced steps: 100 x the gaps' seconds (or those of
    ``parts`` of them) over the periods'."""
    rows = untraced(run)
    if not rows:
        return None
    return (100.0 * sum(r[p + "_s"] for r in rows for p in parts)
            / _period_s(rows))


def median_ms(run: dict, key: str):
    """Median of ``dur_s`` or ``input_wait_s`` over the untraced steps."""
    rows = untraced(run)
    if not rows:
        return None
    return 1e3 * stats.median([r[key] for r in rows])


def estimate_error_pct(run: dict):
    """In a traced run: the ``trainer.device_step`` intervals on the
    profiler's clock, clipped to the traced window, against the device's busy
    time there: 100 x |estimate - busy| / busy."""
    reduced = program_spans.traced(run)
    placed = reduced and program_spans.on_profiler_clock(run, DEVICE_STEP)
    if not placed:
        return None
    lo, hi = reduced["window"]
    estimate = trace_reduce.total(trace_reduce.union(
        trace_reduce.clip(placed, lo, hi)))
    busy = (hi - lo) - sum(trace_reduce.total(g) for g in reduced[
        "idle_gaps"]) / len(reduced["idle_gaps"])
    if busy <= 0:
        return None
    run["notes"].append(
        f"{DEVICE_STEP} in the traced window: {estimate:.6f} s estimated, "
        f"{busy:.6f} s busy by the device's operations")
    return 100.0 * abs(estimate - busy) / busy


def _write_summary(run: dict, rows: list, found: list, profiled: set) -> None:
    """``<out_dir>/device_steps.json`` and one note: what PERF.md quotes
    beside the metrics — the spans of the profiled steps and of the others
    apart, and which of its three candidates began a step."""
    def med(values):
        return 1e3 * stats.median(values) if values else None

    traced_steps = [s for s in found if s["args"].get("step") in profiled]
    outside, under = transfers(run) or ([], [])
    after: dict = {}
    for s in found:
        if s["args"].get("step") not in profiled:
            key = s["args"].get("after")
            after[key] = after.get(key, 0) + 1
    period = _period_s(rows)
    out = {
        "untraced_steps": len(rows), "profiled_steps": sorted(profiled),
        "period_s": period,
        "gap_s": {p: sum(r[p + "_s"] for r in rows) for p in PARTS},
        "enqueue_pct": 100.0 * sum(r["enqueue_s"] for r in rows) / period,
        "device_step_ms": {
            "untraced": med([r["dur_s"] for r in rows]),
            "profiled": med([s["t1"] - s["t0"] for s in traced_steps])},
        "h2d_ms": {
            "untraced": med([s["t1"] - s["t0"] for s in outside]),
            "untraced_count": len(outside),
            "profiled": med([s["t1"] - s["t0"] for s in under]),
            "profiled_count": len(under)},
        "input_late_steps": sum(1 for r in rows if r["input_wait_s"] > 0),
        "after": after,
    }
    with open(os.path.join(program_spans.out_dir(run),
                           "device_steps.json"), "w") as f:
        json.dump(out, f, indent=1)

    def ms(value):
        return "none" if value is None else f"{value:.4f} ms"

    run["notes"].append(
        f"device steps: {len(rows)} untraced, {len(traced_steps)} under the "
        f"profiler; median {DEVICE_STEP} {ms(out['device_step_ms']['untraced'])}"
        f" / {ms(out['device_step_ms']['profiled'])}, median {H2D} "
        f"{ms(out['h2d_ms']['untraced'])} ({len(outside)}) / "
        f"{ms(out['h2d_ms']['profiled'])} ({len(under)}); began after "
        + ", ".join(f"{k} {v}" for k, v in sorted(after.items(),
                                                  key=lambda kv: str(kv[0])))
        + f"; {out['input_late_steps']} steps' batches were late")
