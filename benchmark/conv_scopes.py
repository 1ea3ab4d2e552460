"""Device time of a traced step by the ``jax.named_scope``s of the
short-convolution expert model's mixers (``models/lfm2_moe.py``), for the
metrics that read them.

The reduction is ``device_scopes.py``'s: its child process is run on the
trace with this file's list of scopes (that module's own list is granite's
cells', ``moe_scopes.py``'s holds ``attention`` and the routed layer's, which
this model's step names alike and its other readers take from there).  A
program without these scopes, or an untraced run, gives None.
"""

from __future__ import annotations

import json
import os
import subprocess
import sys

from benchmark import device_scopes, program_spans

#: the conv mixer whole, what it nests, and what attention nests
SCOPES = ("conv_mixer", "conv_in_proj", "short_conv", "conv_out_proj",
          "qk_norm_rope")


def reduced(run: dict):
    """``{"steps": n, "scope_s": {scope: seconds}, ...}`` of a traced run,
    read once; None for an untraced run or an unreadable trace."""
    if "_conv_scopes" in run:
        return run["_conv_scopes"]
    run["_conv_scopes"] = None
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
        run["notes"].append("conv scopes: the trace could not be read: "
                            + proc.stderr.strip()[-300:])
        return None
    out = json.loads(proc.stdout.strip().splitlines()[-1])
    run["_conv_scopes"] = out
    if out["steps"]:
        run["notes"].append(
            "device time a traced step by the mixers' scopes (they nest): "
            + ", ".join(f"{k} {1e3 * v / out['steps']:.4f} ms"
                        for k, v in out["scope_s"].items()))
    return out


def scope_ms(run: dict, scope: str):
    """Device time a traced step under ``scope``; None where the trace has
    no operation under it."""
    out = reduced(run)
    if not out or not out["steps"] or not out["scope_s"].get(scope):
        return None
    return 1e3 * out["scope_s"][scope] / out["steps"]
