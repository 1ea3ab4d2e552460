"""Device time of a traced step by the ``jax.named_scope``s of the
linear-attention expert model's mixers (``models/kimi_linear.py``), for the
metrics that read them.

The reduction is ``device_scopes.py``'s: its child process is run on the
trace with this file's list of scopes (that module's own list is granite's
cells'; ``moe_scopes.py``'s holds ``attention``, ``mla_project`` and the
routed layer's, which this model's step names alike and which are listed
here again for the run's note: no metric of this cell reads them until the
accepted names for them list it).  A program without these scopes, or an
untraced run, gives None.
"""

from __future__ import annotations

import json
import os
import subprocess
import sys

from benchmark import device_scopes, program_spans

#: the KDA mixer whole and what it nests (the metrics' own), and the step's
#: other scopes, which no metric of this cell reads yet and the run's note
#: shows (PERF.md section 5 quotes them): the compiler's name for a grouped
#: product of the overflow form is ``moe_scopes.py``'s
SCOPES = ("kda_mixer", "kda_project", "kda_conv", "kda_scan", "kda_out",
          "attention", "mla_project", "mlp", "shared_expert", "moe_router",
          "moe_dispatch", "moe_experts", "moe_combine", "lm_head",
          "ragged-dot")


def reduced(run: dict):
    """``{"steps": n, "scope_s": {scope: seconds}, ...}`` of a traced run,
    read once; None for an untraced run or an unreadable trace."""
    if "_kda_scopes" in run:
        return run["_kda_scopes"]
    run["_kda_scopes"] = None
    path = (run["trainer"].get("trace") or {}).get("file")
    if not path or not os.path.isfile(path):
        return None
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("TFOS_HOST_DEVICE_COUNT", None)
    proc = subprocess.run(
        [sys.executable, os.path.abspath(device_scopes.__file__), path,
         json.dumps(SCOPES)],
        capture_output=True, text=True, env=env, cwd=device_scopes.ROOT,
        timeout=program_spans.CHILD_TIMEOUT_S)
    if proc.returncode != 0:
        run["notes"].append("kda scopes: the trace could not be read: "
                            + proc.stderr.strip()[-300:])
        return None
    out = json.loads(proc.stdout.strip().splitlines()[-1])
    run["_kda_scopes"] = out
    if out["steps"]:
        run["notes"].append(
            "device time a traced step by the KDA mixers' scopes and the "
            "step's others (they nest): "
            + ", ".join(f"{k} {1e3 * v / out['steps']:.4f} ms"
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
