"""The program's own record of a run, for the per-layer readers: the spans
and counters ``TFCluster.shutdown`` writes to the application's scratch
directory (``obs/trace.json``, ``obs/counters.json``), cut to the measured
window, and — for a traced run — laid against the device trace.

No JAX is imported here: the readers run in the launcher.  The one step that
opens the ``.xplane.pb`` runs in a child process held to the CPU
(``python benchmark/program_spans.py <file>``), after the driver's process
group is gone, and hands back a small JSON.

A program that wrote no such record (the parent of the PR that added it)
makes every reader here return None, which leaves the metric out of the line.
So does a record that lost events (``dropped`` > 0): never a number from a
partial record.  All times in seconds; ring spans are on ``time.time()``.
"""

from __future__ import annotations

import glob
import json
import os
import re
import statistics
import subprocess
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from benchmark import stats, trace_reduce  # noqa: E402

OUT_DIR = ".benchmark_out"
STEP_SPAN = "trainer.step"
STAGE_SPAN = "feed.stage"
#: node-side spans that lie between ``TFCluster.run`` and the ``map_fun``;
#: the first two only up to the node's registration (later ones are the
#: feed's first tasks, which run while the trainer is still starting)
BOOTSTRAP_SPANS = (
    "spark.task_send", "executor.task_load", "node.chip_claim",
    "node.manager_start", "health.probe", "node.register_await",
    "node.trainer_spawn", "node.jax_import", "node.distributed_init",
    "node.chip_verify")
SETUP_SPANS = BOOTSTRAP_SPANS + ("executor.start", "cluster.reserve",
                                 "trainer.init")
#: ``op_name`` of a device operation -> the step's phase, by JAX's own
#: names: what runs under the differentiated loss is ``jvp(<scope>)``, its
#: backward ``transpose(jvp(<scope>))``; what carries an ``op_name`` under
#: neither is the rest of the step — the ``optimizer`` scope's update.
#: (The program's ``forward`` / ``optimizer`` scopes say the same in a
#: freshly compiled program; an executable served from a compile cache
#: that predates them keeps its old names, and these patterns still hold.)
PHASES = (("backward", re.compile(r"transpose\(jvp\(")),
          ("forward", re.compile(r"jvp\(|(^|/)forward(/|$)")))
CHILD_TIMEOUT_S = 600.0


def out_dir(run: dict) -> str:
    return os.path.join(ROOT, OUT_DIR, run["cell"]["name"])


# ---------------------------------------------------------------------------
# The ring: obs/trace.json and obs/counters.json
# ---------------------------------------------------------------------------


def load(run: dict) -> dict:
    """``{"spans": {name: [span, ...]}, "dropped": n, "counters": {...}}``
    of this run, read once; ``spans`` is empty where the program wrote no
    trace.  A span is ``{"t0", "t1", "pid", "tid", "args"}``."""
    if "_program" in run:
        return run["_program"]
    record = {"spans": {}, "dropped": 0, "counters": None}
    scratch = os.path.join(out_dir(run), "scratch", "*", "obs")
    for path in sorted(glob.glob(os.path.join(scratch, "trace.json")))[-1:]:
        with open(path, encoding="utf-8") as f:
            doc = json.load(f)
        record["dropped"] = sum((doc.get("tfos") or {}).get(
            "dropped", {}).values())
        for ev in doc["traceEvents"]:
            if ev.get("ph") != "X":
                continue
            t0 = ev["ts"] * 1e-6
            record["spans"].setdefault(ev["name"], []).append({
                "t0": t0, "t1": t0 + ev["dur"] * 1e-6, "pid": ev["pid"],
                "tid": ev["tid"], "args": ev.get("args") or {}})
    for path in sorted(glob.glob(os.path.join(scratch, "counters.json")))[-1:]:
        with open(path, encoding="utf-8") as f:
            record["counters"] = json.load(f)
    if record["dropped"]:
        run["notes"].append(
            f"program spans: {record['dropped']} events were dropped before "
            "they reached the trace; the readers of program spans return "
            "nothing")
    run["_program"] = record
    if record["spans"]:
        _write_summary(run, record)
    return record


def window(run: dict) -> tuple:
    t0 = run["trainer"]["t_window_start"]
    return t0, t0 + run["trainer"]["window"]["seconds"]


def spans(run: dict, name: str, whole_job: bool = False):
    """The spans of ``name`` that lie inside the measured window (or of
    the whole job), by start.  None where the record has no such span at
    all, or is partial."""
    record = load(run)
    if record["dropped"] or name not in record["spans"]:
        return None
    found = sorted(record["spans"][name], key=lambda s: s["t0"])
    if whole_job:
        return found
    lo, hi = window(run)
    return [s for s in found if s["t0"] >= lo and s["t1"] <= hi]


def median_ms(run: dict, name: str):
    found = spans(run, name)
    if not found:
        return None
    return 1e3 * stats.median([s["t1"] - s["t0"] for s in found])


def median_s(run: dict, name: str):
    value = median_ms(run, name)
    return None if value is None else value / 1e3


def counter(run: dict, name: str):
    """A counter summed over the job's processes; None where the program
    wrote no counters, 0 where it wrote them and never touched this one."""
    counters = load(run)["counters"]
    if counters is None:
        return None
    return sum((snap.get("counters") or {}).get(name, 0)
               for snap in counters.values())


def self_seconds(record: dict, name: str) -> float:
    """Total time of the spans of ``name`` less what their children cover
    (children by ``parent_span_id``, on the same thread)."""
    mine = {s["args"].get("span_id"): s for s in record["spans"].get(name, [])}
    covered: dict = {sid: [] for sid in mine}
    for other in record["spans"].values():
        for s in other:
            parent = s["args"].get("parent_span_id")
            if parent in covered:
                covered[parent].append((s["t0"], s["t1"]))
    return sum(s["t1"] - s["t0"] - trace_reduce.total(trace_reduce.clip(
        trace_reduce.union(covered[sid]), s["t0"], s["t1"]))
        for sid, s in mine.items())


def _write_summary(run: dict, record: dict) -> None:
    """``<out_dir>/program_spans.json``: what PERF.md is written from —
    every span name inside the window (count, median, total, self time),
    and how much of ``bootstrap_s`` the node-side spans cover."""
    lo, hi = window(run)
    inside = {"spans": {
        name: [s for s in found if s["t0"] >= lo and s["t1"] <= hi]
        for name, found in record["spans"].items()}}
    names = {}
    for name, found in inside["spans"].items():
        if found:
            durs = [s["t1"] - s["t0"] for s in found]
            names[name] = {
                "count": len(durs), "median_ms": 1e3 * stats.median(durs),
                "total_s": sum(durs), "self_s": self_seconds(inside, name)}
    b_lo = run["driver"].get("t_cluster_run")
    b_hi = run["trainer"].get("t_map_fun")
    bootstrap = None
    if b_lo and b_hi and b_hi > b_lo:
        by_span, everything = {}, []
        registered = max((s["t1"] for s in record["spans"].get(
            "node.register_await", [])), default=b_hi)
        for name in BOOTSTRAP_SPANS:
            found = record["spans"].get(name, [])
            if name in BOOTSTRAP_SPANS[:2]:
                found = [s for s in found if s["t0"] < registered]
            mine = trace_reduce.clip([(s["t0"], s["t1"]) for s in found],
                                     b_lo, b_hi)
            if mine:
                by_span[name] = trace_reduce.total(trace_reduce.union(mine))
                everything.extend(mine)
        covered = trace_reduce.union(everything)
        bootstrap = {
            "seconds": b_hi - b_lo, "covered_s": trace_reduce.total(covered),
            "by_span": by_span,
            "uncovered": [[s - b_lo, e - b_lo] for s, e in trace_reduce.gaps(
                covered, b_lo, b_hi) if e - s >= 0.2]}
    with open(os.path.join(out_dir(run), "program_spans.json"), "w") as f:
        json.dump({"window": [lo, hi], "dropped": record["dropped"],
                   "spans": names, "bootstrap": bootstrap,
                   # [seconds after launch, duration] of the set-up's spans
                   "setup": {name: [[s["t0"] - run["t_launch"],
                                     s["t1"] - s["t0"]]
                                    for s in record["spans"][name]
                                    if s["t1"] <= lo]
                             for name in SETUP_SPANS
                             if name in record["spans"]}}, f, indent=1)


# ---------------------------------------------------------------------------
# The device trace: program spans on the profiler's clock
# ---------------------------------------------------------------------------


def traced(run: dict):
    """What the child process read out of the traced run's ``.xplane.pb``
    (:func:`reduce_xplane`), with the clock offset and the idle time under
    ``feed.stage`` worked out; None for an untraced run or a parent whose
    program opens no annotations."""
    if "_traced" in run:
        return run["_traced"]
    run["_traced"] = None
    path = (run["trainer"].get("trace") or {}).get("file")
    if not path or not os.path.isfile(path):
        return None
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("TFOS_HOST_DEVICE_COUNT", None)
    proc = subprocess.run(
        [sys.executable, os.path.abspath(__file__), path,
         json.dumps(sorted(load(run)["spans"]))],
        capture_output=True, text=True, timeout=CHILD_TIMEOUT_S, env=env,
        cwd=ROOT)
    if proc.returncode != 0:
        run["notes"].append("program spans: the trace could not be read: "
                            + proc.stderr.strip()[-300:])
        return None
    out = json.loads(proc.stdout.strip().splitlines()[-1])
    run["_traced"] = out
    out["clock"] = c = clock(run, out)
    if c:
        run["notes"].append(
            f"clock offset (profiler - time.time()): {c['offset_s']:.6f} s "
            f"from {c['pairs']} {STEP_SPAN} pairs, spread "
            f"{1e3 * c['spread_s']:.4f} ms")
    # for PERF.md: the device's idle time by the program span open on any
    # thread of the trainer (its annotations), and by the ring spans of
    # the host's other processes, placed by the offset
    idle = {name: idle_under(out, ivs)
            for name, ivs in out["host_spans"].items()}
    trainer = {s["pid"] for s in spans(run, STEP_SPAN, whole_job=True) or []}
    for name, found in load(run)["spans"].items():
        if c and any(s["pid"] not in trainer for s in found):
            idle[name + " (ring)"] = idle_under(
                out, on_profiler_clock(run, name) or [])
    lo, hi = out["window"]
    gaps_s = sum(trace_reduce.total(g) for g in out["idle_gaps"]) / len(
        out["idle_gaps"])
    run["notes"].append(
        f"idle {gaps_s:.4f} s of the traced {hi - lo:.4f} s; under program "
        "spans (they overlap): " + ", ".join(
            f"{k} {v:.4f}" for k, v in sorted(
                idle.items(), key=lambda kv: -kv[1]) if v > 0))
    return out


def phase_ms(run: dict, phase: str):
    """Device time a traced step in operations of one phase; None where no
    operation of the trace carries an ``op_name``."""
    reduced = traced(run)
    if not reduced or not reduced["steps"] or not reduced["ops_with_op_name"]:
        return None
    if "_phase_note" not in run:
        run["_phase_note"] = True
        run["notes"].append(
            "device time a traced step by phase: " + ", ".join(
                f"{k} {1e3 * v / reduced['steps']:.4f} ms"
                for k, v in sorted(reduced["phase_s"].items()))
            + f" ({reduced['ops_with_op_name']} of {reduced['ops']} "
            "operation events carry an op_name; 'unnamed' are the rest)")
    return 1e3 * reduced["phase_s"].get(phase, 0.0) / reduced["steps"]


def clock(run: dict, reduced: dict):
    """The profiler's clock minus ``time.time()``: the median over the
    ``trainer.step`` spans found in both records, paired by ``step``, with
    the distance between the quartiles of the pairs' differences."""
    ring = {s["args"].get("step"): s["t0"]
            for s in spans(run, STEP_SPAN, whole_job=True) or []}
    diffs = sorted(start - ring[int(step)]
                   for step, start in reduced["step_starts"].items()
                   if int(step) in ring)
    if not diffs:
        return None
    spread = 0.0
    if len(diffs) >= 2:
        q1, _, q3 = statistics.quantiles(diffs, n=4)
        spread = q3 - q1
    return {"offset_s": stats.median(diffs), "spread_s": spread,
            "pairs": len(diffs)}


def on_profiler_clock(run: dict, name: str):
    """Ring spans of ``name`` (any process of the host) as intervals on the
    profiler's clock, by the derived offset; None without one."""
    reduced = run.get("_traced")
    found = spans(run, name, whole_job=True)
    if not reduced or not reduced.get("clock") or found is None:
        return None
    off = reduced["clock"]["offset_s"]
    return [(s["t0"] + off, s["t1"] + off) for s in found]


def idle_under(reduced: dict, intervals: list) -> float:
    """Seconds of a device's idle gaps in the traced window that
    ``intervals`` (on the profiler's clock, any threads) cover, averaged
    over the devices."""
    lo, hi = reduced["window"]
    cover = trace_reduce.union(trace_reduce.clip(intervals, lo, hi))
    per_device = [trace_reduce.overlap([tuple(g) for g in gaps], cover)
                  for gaps in reduced["idle_gaps"]]
    return sum(per_device) / len(per_device)


# ---------------------------------------------------------------------------
# The child: the only code here that imports JAX
# ---------------------------------------------------------------------------


def _varint(buf, i: int):
    shift = value = 0
    while True:
        byte = buf[i]
        i += 1
        value |= (byte & 0x7F) << shift
        if byte < 0x80:
            return value, i
        shift += 7


def _fields(buf):
    """``(field number, value)`` of one protobuf message: an int for a
    varint, a memoryview for a length-delimited or fixed field."""
    i, n = 0, len(buf)
    while i < n:
        key, i = _varint(buf, i)
        kind = key & 7
        if kind == 0:
            value, i = _varint(buf, i)
        elif kind == 2:
            size, i = _varint(buf, i)
            value, i = buf[i:i + size], i + size
        elif kind in (1, 5):
            size = 8 if kind == 1 else 4
            value, i = buf[i:i + size], i + size
        else:
            raise ValueError(f"wire type {kind}")
        yield key >> 3, value


def op_names(xspace: bytes) -> dict:
    """``{plane name: {event name: op_name}}`` of the device planes.  The
    JAX ``op_name`` of an operation (with its ``named_scope``s) is a stat
    (``tf_op``) of the event's *metadata*, which
    ``jax.profiler.ProfileData`` does not show; so the few fields that hold
    it are read from the wire format here (``xplane.proto``: XSpace.planes
    = 1; XPlane.name = 2, .event_metadata = 4, .stat_metadata = 5;
    XEventMetadata.name = 2, .stats = 5; XStat.metadata_id = 1,
    .str_value = 5, .ref_value = 7; XStatMetadata.name = 2)."""
    out = {}
    for field, plane in _fields(memoryview(xspace)):
        if field != 1:
            continue
        name, events, stat_names = "", [], {}
        for f, value in _fields(plane):
            if f == 2:
                name = bytes(value).decode()
            elif f in (4, 5):       # a map entry: key = 1, value = 2
                entry = dict(_fields(value))
                if f == 4:
                    events.append(entry[2])
                else:
                    meta = dict(_fields(entry[2]))
                    stat_names[entry.get(1, 0)] = bytes(
                        meta.get(2, b"")).decode()
        if not trace_reduce.DEVICE_PLANE.match(name):
            continue
        wanted = {k for k, v in stat_names.items() if v == "tf_op"}
        ops = {}
        for meta in events:
            ev_name, op = "", None
            for f, value in _fields(meta):
                if f == 2:
                    ev_name = bytes(value).decode(errors="replace")
                elif f == 5:
                    stat = dict(_fields(value))
                    if stat.get(1) in wanted:
                        op = (bytes(stat[5]).decode(errors="replace")
                              if 5 in stat else stat_names.get(stat.get(7)))
            if op:
                ops[ev_name] = op
        out[name] = ops
    return out


def phase_of(op_name: str) -> str:
    for phase, pattern in PHASES:
        if pattern.search(op_name):
            return phase
    return "optimizer"


def reduce_xplane(path: str, names=(STEP_SPAN, STAGE_SPAN)) -> dict:
    """From one ``.xplane.pb(.gz)``: the traced window, the device's idle
    gaps in it, the program's annotations on the host (the intervals of the
    spans in ``names`` — the launcher hands in the names the ring holds —
    and the ``trainer.step`` starts by ``step``), and the device time by
    the step's phase."""
    from jax.profiler import ProfileData

    if path.endswith(".gz"):
        import gzip

        with gzip.open(path, "rb") as f:
            raw = f.read()
    else:
        with open(path, "rb") as f:
            raw = f.read()
    data = ProfileData.from_serialized_xspace(raw)
    marks = {name: [] for name in trace_reduce.SPANS
             + (trace_reduce.WINDOW_SPAN,)}
    program: dict = {}
    step_starts: dict = {}
    device_lines = []
    for plane in data.planes:
        if plane.name.startswith("/host:"):
            for line in plane.lines:
                for ev in line.events:
                    name = ev.name
                    if name in marks:
                        marks[name].append((ev.start_ns * 1e-9,
                                            ev.end_ns * 1e-9))
                    elif name in names:
                        iv = (ev.start_ns * 1e-9, ev.end_ns * 1e-9)
                        program.setdefault(name, []).append(iv)
                        if name == STEP_SPAN:
                            step = dict(ev.stats).get("step")
                            if step is not None:
                                step_starts[int(step)] = iv[0]
        elif trace_reduce.DEVICE_PLANE.match(plane.name):
            by_line = {line.name: line for line in plane.lines}
            line = next((by_line[n] for n in trace_reduce.OPS_LINES
                         if n in by_line), None)
            if line is not None:
                device_lines.append((plane.name, [
                    (ev.name, ev.start_ns * 1e-9, ev.end_ns * 1e-9)
                    for ev in line.events]))
    hull = marks[trace_reduce.WINDOW_SPAN] or [
        iv for name in trace_reduce.SPANS for iv in marks[name]]
    if not hull or not device_lines:
        raise ValueError("no traced window or no device plane in the trace")
    lo, hi = min(s for s, _ in hull), max(e for _, e in hull)
    op_name_of = op_names(raw)
    idle_gaps, phases, scoped, n_ops = [], {}, 0, 0
    for plane_name, events in device_lines:
        inside = [(n, max(s, lo), min(e, hi)) for n, s, e in events
                  if min(e, hi) > max(s, lo)]
        busy = trace_reduce.union([(s, e) for _, s, e in inside])
        idle_gaps.append(trace_reduce.gaps(busy, lo, hi))
        ops = op_name_of.get(plane_name, {})
        for n, s, e in inside:
            op = ops.get(n)
            n_ops += 1
            scoped += op is not None
            phase = phase_of(op) if op else "unnamed"
            phases[phase] = phases.get(phase, 0.0) + (e - s)
    n_dev = len(device_lines)
    in_window = {name: trace_reduce.clip(ivs, lo, hi)
                 for name, ivs in program.items()}
    return {
        "window": [lo, hi],
        "steps": len(trace_reduce.clip(marks["step_dispatch"], lo, hi)),
        "idle_gaps": idle_gaps,
        "step_starts": step_starts,
        "host_spans": {name: ivs for name, ivs in in_window.items() if ivs},
        "phase_s": {k: v / n_dev for k, v in phases.items()},
        "ops": n_ops, "ops_with_op_name": scoped,
    }


if __name__ == "__main__":
    print(json.dumps(reduce_xplane(sys.argv[1], set(json.loads(sys.argv[2])))))
