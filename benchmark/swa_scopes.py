"""Device time of a traced step by the ``jax.named_scope``s of the
sliding-window / full attention expert model's step
(``models/mellum_moe.py``), for the metrics that read them.

The reduction is ``device_scopes.py``'s: its child process is run on the
trace with this file's list of scopes (that module's own list is granite's
cells'; ``moe_scopes.py``'s holds the routed layer's, which this model's step
names alike and which are listed here again for the run's note: no metric of
this cell reads them until the accepted names for them list it).  A scope is
found as a word of an operation's ``op_name``, and ``_`` is a letter of a
word: ``attention`` (a layer's norm and mixer whole, both kinds) is not
found in ``window_attention`` or ``full_attention`` (the blocks of scores,
softmax and values of a sliding layer and of a full one, which nest in it).
A program without these scopes, or an untraced run, gives None.
"""

from __future__ import annotations

import json
import os
import subprocess
import sys

from benchmark import device_scopes, program_spans

#: the attention mixer whole and what it nests (the metrics' own), and the
#: step's other scopes, which no metric of this cell reads yet and the run's
#: note shows (PERF.md section 5 quotes them): the compiler's name for a
#: grouped product of the overflow form is ``moe_scopes.py``'s
SCOPES = ("attention", "qk_norm_rope", "window_attention", "full_attention",
          "moe_router", "moe_dispatch", "moe_experts", "moe_combine",
          "lm_head", "ragged-dot")


def reduced(run: dict):
    """``{"steps": n, "scope_s": {scope: seconds}, ...}`` of a traced run,
    read once; None for an untraced run or an unreadable trace."""
    if "_swa_scopes" in run:
        return run["_swa_scopes"]
    run["_swa_scopes"] = None
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
        run["notes"].append("swa scopes: the trace could not be read: "
                            + proc.stderr.strip()[-300:])
        return None
    out = json.loads(proc.stdout.strip().splitlines()[-1])
    run["_swa_scopes"] = out
    if out["steps"]:
        run["notes"].append(
            "device time a traced step by the attention layers' scopes and "
            "the step's others (they nest): "
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
