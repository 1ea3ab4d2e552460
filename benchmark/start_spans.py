"""A start's seconds from inside: the ``jit.trace`` / ``jit.lower`` /
``jit.compile`` spans that the program writes from JAX's own time-span events
(``tensorflowonspark_tpu/compile_cache.py``, PR 53), laid against
``trainer.init`` and the first step's ``trainer.dispatch``, so that
``trainer_ready_s`` and ``setup_s`` split by phase in every traced run.

**The trainer's process** is the ``pid`` of the ``trainer.step`` spans, and
the thread that steps the ``tid`` of step 1's.  **Step 1's dispatch** is the
``trainer.dispatch`` inside the ``trainer.step`` with ``step`` = 1 on that pid
and thread.  **A phase's seconds** over a stretch are the union of its spans'
intervals there, so a nested trace counts once.  Inside step 1's dispatch the
three phases are made disjoint — a compile made inside a trace (an eager
operation on a constant) is the compile's, a lowering's the lowering's — so
that with ``run_s``, what is left of the stretch to the end of step 1's
``trainer.device_step``, they add up to step 1's wall.

``start_spans.json`` (and one note) holds beside them: the ten costliest
``fun`` of ``jit.trace`` by self time (a span less the recorded ``jit.*``
spans it holds on its thread), every ``jit.compile`` that was not a ``hit``,
and the stretches of ``[t_map_fun, t_first_step_done]`` of 0.2 s or more
under no span of the stepping thread (a span that holds the whole stretch,
as ``node.map_fun`` does, covers nothing).

No JAX is imported here.  A program that writes no ``jit.compile`` span (the
parent of PR 53), or a record that lost events, makes every reader return
None.  All times in seconds, ring spans on ``time.time()``.
"""

from __future__ import annotations

import json
import os

from benchmark import program_spans, trace_reduce

TRACE, LOWER, COMPILE = "jit.trace", "jit.lower", "jit.compile"
#: a span of a later name takes the time it shares with an earlier one's
PHASES = (TRACE, LOWER, COMPILE)
STEP, DISPATCH, INIT = "trainer.step", "trainer.dispatch", "trainer.init"
DEVICE_STEP = "trainer.device_step"
COUNTERS = {"jit_traces": "jit_traces_total",
            "cache_disk_misses": "compile_cache_disk_misses_total"}
UNCOVERED_MIN_S = 0.2
TOP_TRACES = 10
#: compiles the note names; ``start_spans.json`` lists every one
NOTE_COMPILES = 6


def _ivs(found: list) -> list:
    return [(s["t0"], s["t1"]) for s in found]


def _inside(found: list, lo: float, hi: float) -> list:
    return [s for s in found if s["t0"] >= lo and s["t1"] <= hi]


def _union_s(found: list) -> float:
    return trace_reduce.total(trace_reduce.union(_ivs(found)))


def _by_phase(jit: dict, lo: float, hi: float) -> dict:
    """Seconds of ``[lo, hi]`` by phase, each second given to one phase."""
    out, taken = {}, []
    for name in reversed(PHASES):
        mine = trace_reduce.union(_ivs(_inside(jit[name], lo, hi)))
        out[name] = trace_reduce.total(mine) - trace_reduce.overlap(
            mine, taken)
        taken = trace_reduce.union(taken + mine)
    return out


def _unions(jit: dict, lo: float, hi: float) -> dict:
    """Each phase's union inside ``[lo, hi]`` and the union of all three."""
    found = {name: _inside(jit[name], lo, hi) for name in PHASES}
    out = {name.split(".")[1] + "_s": _union_s(found[name])
           for name in PHASES}
    out["jit_s"] = _union_s([s for name in PHASES for s in found[name]])
    return out


def _trace_self(jit: dict, until: float) -> list:
    """``[{"fun", "self_s", "total_s", "count"}]`` of the traces that ended
    by ``until``, costliest first by self time."""
    everything = [s for name in PHASES for s in jit[name]]
    by_fun: dict = {}
    for span in _inside(jit[TRACE], 0.0, until):
        held = [(s["t0"], s["t1"]) for s in everything
                if s is not span and s["tid"] == span["tid"]
                and s["t0"] >= span["t0"] and s["t1"] <= span["t1"]]
        row = by_fun.setdefault(span["args"].get("fun"),
                                {"self_s": 0.0, "total_s": 0.0, "count": 0})
        dur = span["t1"] - span["t0"]
        row["self_s"] += dur - trace_reduce.total(trace_reduce.union(held))
        row["total_s"] += dur
        row["count"] += 1
    rows = [dict(row, fun=fun) for fun, row in by_fun.items()]
    return sorted(rows, key=lambda r: -r["self_s"])


def _uncovered(record: dict, pid, tid, lo: float, hi: float) -> list:
    cover = [(s["t0"], s["t1"]) for found in record["spans"].values()
             for s in found if s["pid"] == pid and s["tid"] == tid
             and not (s["t0"] <= lo and s["t1"] >= hi)]
    covered = trace_reduce.union(trace_reduce.clip(cover, lo, hi))
    return [[s - lo, e - lo] for s, e in trace_reduce.gaps(covered, lo, hi)
            if e - s >= UNCOVERED_MIN_S]


def start(run: dict):
    """The start by phase, computed once a run: ``{"first_step": {trace_s,
    lower_s, load_s, run_s, wall_s}, "init": {...}, "setup": {...},
    "spans", ...}``; None without ``jit.compile`` spans or with a partial
    record."""
    if "_start_spans" in run:
        return run["_start_spans"]
    run["_start_spans"] = None
    steps = program_spans.spans(run, STEP, whole_job=True)
    if not steps or not program_spans.spans(run, COMPILE, whole_job=True):
        return None
    first = [s for s in steps if s["args"].get("step") == 1]
    if not first:
        return None
    pid, tid = first[0]["pid"], first[0]["tid"]

    def mine(name):
        """The whole job's spans of ``name`` in the trainer's process."""
        return [s for s in program_spans.spans(run, name, whole_job=True)
                or [] if s["pid"] == pid]

    jit = {name: mine(name) for name in PHASES}
    t_window = run["trainer"]["t_window_start"]
    out = {"pid": pid, "spans": {name: len(jit[name]) for name in PHASES},
           "first_step": None, "init": None,
           "setup": _unions(jit, 0.0, t_window)}
    dispatch = [s for s in _inside(mine(DISPATCH), first[0]["t0"],
                                   first[0]["t1"]) if s["tid"] == tid]
    if dispatch:
        lo, hi = dispatch[0]["t0"], dispatch[0]["t1"]
        threads = {name: [s for s in jit[name] if s["tid"] == tid]
                   for name in PHASES}
        split = _by_phase(threads, lo, hi)
        step = {"trace_s": split[TRACE], "lower_s": split[LOWER],
                "load_s": split[COMPILE], "dispatch_s": hi - lo,
                "run_s": None, "wall_s": None,
                "retrieval_s": sum(
                    s["args"].get("retrieval_s") or 0.0
                    for s in _inside(threads[COMPILE], lo, hi)
                    if s["args"].get("cache") == "hit")}
        done = [s["t1"] for s in mine(DEVICE_STEP)
                if s["args"].get("step") == 1]
        if done:
            step["wall_s"] = done[0] - lo
            step["run_s"] = step["wall_s"] - sum(split.values())
        out["first_step"] = step
    init = mine(INIT)
    if init:
        out["init"] = dict(_unions(jit, init[0]["t0"], init[0]["t1"]),
                           wall_s=init[0]["t1"] - init[0]["t0"])
    out["trace_self"] = _trace_self(jit, t_window)[:TOP_TRACES]
    out["not_hit"] = [
        dict({key: s["args"].get(key)
              for key in ("fun", "cache", "entry_bytes", "written")},
             seconds=s["t1"] - s["t0"],
             after_launch_s=s["t0"] - run["t_launch"])
        for s in jit[COMPILE] if s["args"].get("cache") != "hit"]
    b_lo = run["trainer"].get("t_map_fun")
    b_hi = run["trainer"].get("t_first_step_done")
    out["uncovered"] = (_uncovered(program_spans.load(run), pid, tid,
                                   b_lo, b_hi)
                        if b_lo and b_hi and b_hi > b_lo else None)
    counted = {name: program_spans.counter(run, counter)
               for name, counter in COUNTERS.items()}
    out["counters"] = {name: None if value is None else int(value)
                       for name, value in counted.items()}
    run["_start_spans"] = out
    _write_summary(run, out)
    return out


def first_step(run: dict, key: str):
    """``trace_s``, ``lower_s``, ``load_s`` or ``run_s`` of step 1."""
    found = start(run)
    if not found or not found["first_step"]:
        return None
    return found["first_step"][key]


def jit_seconds(run: dict, stretch: str):
    """Union of all ``jit.*`` spans of the trainer's process inside
    ``trainer.init`` (``init``) or before the window (``setup``)."""
    found = start(run)
    if not found or not found[stretch]:
        return None
    return found[stretch]["jit_s"]


def counter(run: dict, name: str):
    """One of the two counters, summed over the job's processes: None where
    the program writes no ``jit.compile`` span, so that a parent's line
    leaves the metric out and never reads 0."""
    found = start(run)
    return found["counters"][name] if found else None


def _write_summary(run: dict, out: dict) -> None:
    with open(os.path.join(program_spans.out_dir(run),
                           "start_spans.json"), "w") as f:
        json.dump(out, f, indent=1)

    def secs(row, keys):
        return " / ".join("none" if row[k] is None else f"{row[k]:.3f}"
                          for k in keys)

    step, init, setup = out["first_step"], out["init"], out["setup"]
    keys = ("trace_s", "lower_s", "compile_s")
    note = (f"start spans: {sum(out['spans'].values())} jit.* spans "
            f"({', '.join(f'{k} {v}' for k, v in out['spans'].items())})")
    if step:
        note += ("; step 1 trace / lower / load / run "
                 + secs(step, ("trace_s", "lower_s", "load_s", "run_s"))
                 + f" s of {secs(step, ('wall_s',))} s (hits' retrieval "
                 f"{step['retrieval_s']:.3f} s)")
    if init:
        note += (f"; {INIT} trace / lower / compile {secs(init, keys)} s, "
                 f"their union {init['jit_s']:.3f} of {init['wall_s']:.3f} s")
    worst = sorted(out["not_hit"], key=lambda r: -r["seconds"])
    note += (f"; before the window {secs(setup, keys)} s, their union "
             f"{setup['jit_s']:.3f} s; {len(worst)} compiles not served from "
             "the cache" + "".join(
                 f", {r['fun']} ({r['cache']}, {r['seconds']:.3f} s, "
                 f"{r['entry_bytes']} bytes, written {r['written']})"
                 for r in worst[:NOTE_COMPILES])
             + (f" and {len(worst) - NOTE_COMPILES} shorter ones"
                if len(worst) > NOTE_COMPILES else ""))
    run["notes"].append(note)
