"""Device time of a traced step by the ``jax.named_scope``s of the
latent-attention mixture-of-experts step (``models/mla_moe.py``,
``parallel/moe.py::routed_experts``), for the metrics that read them.

The reduction is ``device_scopes.py``'s: its child process is run on the
trace with this file's list of scopes (that module's own list is its
cells').  One name here is no scope of the program: the TPU compiler turns
``jax.lax.ragged_dot`` into kernels of its own and names them
``ragged-dot-none`` and ``ragged-dot-metadata`` whatever scope they were
traced under, so the grouped products are found by the word ``ragged-dot``
and counted with the ``moe_experts`` scope (the operands' casts).  A program
without these scopes, or an untraced run, gives None.
"""

from __future__ import annotations

import json
import os
import subprocess
import sys

from benchmark import device_scopes, program_spans

#: every scope the step names, and the compiler's name for a grouped product
GROUPED_PRODUCT = "ragged-dot"
SCOPES = ("attention", "mla_project", "mlp", "shared_expert", "moe_router",
          "moe_dispatch", "moe_experts", "moe_combine", "mtp", "lm_head",
          GROUPED_PRODUCT)


def reduced(run: dict):
    """``{"steps": n, "scope_s": {scope: seconds}, ...}`` of a traced run,
    read once; None for an untraced run or an unreadable trace."""
    if "_moe_scopes" in run:
        return run["_moe_scopes"]
    run["_moe_scopes"] = None
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
        run["notes"].append("moe scopes: the trace could not be read: "
                            + proc.stderr.strip()[-300:])
        return None
    out = json.loads(proc.stdout.strip().splitlines()[-1])
    run["_moe_scopes"] = out
    if out["steps"]:
        run["notes"].append(
            "device time a traced step by scope (they nest): " + ", ".join(
                f"{k} {1e3 * v / out['steps']:.4f} ms"
                for k, v in out["scope_s"].items())
            + "; costliest operations (ms a step, op_name's tail): "
            + "; ".join(f"{name} {1e3 * s / out['steps']:.3f} [{op}]"
                        for name, op, s in out["top_ops"]))
    return out


def scope_ms(run: dict, *scopes: str):
    """Device time a traced step under ``scopes``, added up (they must not
    nest in one another); None where the trace has no operation under any
    of them."""
    out = reduced(run)
    if not out or not out["steps"]:
        return None
    found = [out["scope_s"].get(s) for s in scopes]
    if not any(found):
        return None
    return 1e3 * sum(v or 0.0 for v in found) / out["steps"]


def experts_ms(run: dict):
    """The routed experts' grouped products: the compiler's kernels and
    what the ``moe_experts`` scope holds beside them."""
    return scope_ms(run, "moe_experts", GROUPED_PRODUCT)
