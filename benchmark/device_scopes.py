"""Device time of a traced step by the program's ``jax.named_scope``s.

An operation of the device trace carries its JAX ``op_name`` (the scopes it
was traced under, each wrapped by the transformations it went through:
``transpose(jvp(ssm_scan))`` is the backward pass of ``ssm_scan``, a
``checkpoint`` component its recomputation).  A scope's time is the union of
the intervals of the operations whose ``op_name`` holds the scope's name as a
word — forward, recomputation and backward together, a loop's operation and
the operations of its body counted once — inside the traced window, averaged
over the chips and divided by the steps dispatched in it.

No JAX is imported here: the readers run in the launcher, and the one step
that opens the ``.xplane.pb`` runs in a child process held to the CPU
(``python benchmark/device_scopes.py <file> <scopes>``), as
``program_spans.py``'s does.  A trace with no operation under a scope (a
program that has no such scope) gives None, which leaves the metric out.
"""

from __future__ import annotations

import json
import os
import re
import subprocess
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from benchmark import program_spans, trace_reduce  # noqa: E402

#: every scope the program names inside its step
SCOPES = ("ssm_mixer", "ssm_conv", "ssm_scan", "attention", "mlp", "lm_head")


def reduced(run: dict):
    """``{"steps": n, "scope_s": {scope: seconds}, "top_ops": [...]}`` of a
    traced run, read once; None for an untraced run or an unreadable
    trace."""
    if "_device_scopes" in run:
        return run["_device_scopes"]
    run["_device_scopes"] = None
    path = (run["trainer"].get("trace") or {}).get("file")
    if not path or not os.path.isfile(path):
        return None
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("TFOS_HOST_DEVICE_COUNT", None)
    proc = subprocess.run(
        [sys.executable, os.path.abspath(__file__), path, json.dumps(SCOPES)],
        capture_output=True, text=True, env=env, cwd=ROOT,
        timeout=program_spans.CHILD_TIMEOUT_S)
    if proc.returncode != 0:
        run["notes"].append("device scopes: the trace could not be read: "
                            + proc.stderr.strip()[-300:])
        return None
    out = json.loads(proc.stdout.strip().splitlines()[-1])
    run["_device_scopes"] = out
    if out["steps"]:
        run["notes"].append(
            "device time a traced step by scope (they nest): " + ", ".join(
                f"{k} {1e3 * v / out['steps']:.4f} ms"
                for k, v in out["scope_s"].items())
            + "; costliest operations (ms a step, op_name's tail): "
            + "; ".join(f"{name} {1e3 * s / out['steps']:.3f} [{op}]"
                        for name, op, s in out["top_ops"]))
    return out


def scope_ms(run: dict, scope: str):
    """Device time a traced step under ``scope``; None where the trace has
    no operation under it."""
    out = reduced(run)
    if not out or not out["steps"] or not out["scope_s"].get(scope):
        return None
    return 1e3 * out["scope_s"][scope] / out["steps"]


def reduce_xplane(path: str, scopes) -> dict:
    """The child's work: one ``.xplane.pb(.gz)`` to the seconds under each
    scope (a union of intervals a chip, averaged over the chips), the steps
    of the traced window and its ten costliest operations."""
    from jax.profiler import ProfileData

    window = program_spans.reduce_xplane(path)
    lo, hi = window["window"]
    if path.endswith(".gz"):
        import gzip

        with gzip.open(path, "rb") as f:
            raw = f.read()
    else:
        with open(path, "rb") as f:
            raw = f.read()
    op_name_of = program_spans.op_names(raw)
    words = {s: re.compile(rf"\b{re.escape(s)}\b") for s in scopes}
    under = {s: 0.0 for s in scopes}
    by_op: dict = {}
    n_dev = 0
    for plane in ProfileData.from_serialized_xspace(raw).planes:
        if not trace_reduce.DEVICE_PLANE.match(plane.name):
            continue
        by_line = {line.name: line for line in plane.lines}
        line = next((by_line[n] for n in trace_reduce.OPS_LINES
                     if n in by_line), None)
        if line is None:
            continue
        n_dev += 1
        ops = op_name_of.get(plane.name, {})
        found = {s: [] for s in scopes}
        for ev in line.events:
            s0, s1 = max(ev.start_ns * 1e-9, lo), min(ev.end_ns * 1e-9, hi)
            if s1 <= s0:
                continue
            op = ops.get(ev.name) or ""
            key = (trace_reduce.op_name(ev.name), op)
            by_op[key] = by_op.get(key, 0.0) + (s1 - s0)
            for scope, word in words.items():
                if word.search(op):
                    found[scope].append((s0, s1))
        for scope, ivs in found.items():
            under[scope] += trace_reduce.total(trace_reduce.union(ivs))
    n_dev = max(n_dev, 1)
    top = sorted(by_op.items(), key=lambda kv: -kv[1])[:10]
    return {"steps": window["steps"],
            "scope_s": {s: v / n_dev for s, v in under.items()},
            "top_ops": [[name, "/".join(op.split("/")[-3:]), secs / n_dev]
                        for (name, op), secs in top]}


if __name__ == "__main__":
    print(json.dumps(reduce_xplane(sys.argv[1], json.loads(sys.argv[2]))))
