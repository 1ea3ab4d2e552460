"""Device time of a traced step by the ``jax.named_scope``s of the gated
sliding-window / position-free attention expert model's step
(``models/afmoe.py``), for the metrics that read them.

The reduction is ``device_scopes.py``'s: its child process is run on the
trace with this file's list of scopes (that module's own list is granite's
cells'; ``moe_scopes.py``'s and ``swa_scopes.py``'s are their cells').  A
scope is found as a word of an operation's ``op_name``, and ``_`` is a letter
of a word: ``attention`` (a layer's first norm and mixer whole, both kinds)
is not found in ``attention_gate``, ``window_attention`` or
``full_attention``, which nest in it.  One name here is no scope of the
program: the TPU compiler turns ``jax.lax.ragged_dot`` (the routed part's
overflow form) into kernels it names ``ragged-dot-...`` whatever scope they
were traced under, so they are found by that word and counted with the
``moe_experts`` scope.  A program without these scopes, or an untraced run,
gives None.
"""

from __future__ import annotations

import json
import os
import subprocess
import sys

from benchmark import device_scopes, program_spans

#: every scope the step names, and the compiler's name for a grouped product
GROUPED_PRODUCT = "ragged-dot"
SCOPES = ("embed_scale", "attention", "qk_norm_rope", "attention_gate",
          "window_attention", "full_attention", "post_norm", "mlp",
          "shared_expert", "moe_router", "moe_dispatch", "moe_experts",
          "moe_combine", "lm_head", GROUPED_PRODUCT)


def reduced(run: dict):
    """``{"steps": n, "scope_s": {scope: seconds}, ...}`` of a traced run,
    read once; None for an untraced run or an unreadable trace."""
    if "_afmoe_scopes" in run:
        return run["_afmoe_scopes"]
    run["_afmoe_scopes"] = None
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
        run["notes"].append("afmoe scopes: the trace could not be read: "
                            + proc.stderr.strip()[-300:])
        return None
    out = json.loads(proc.stdout.strip().splitlines()[-1])
    run["_afmoe_scopes"] = out
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


def roofline_pct(run: dict, name: str, device_ms, need) -> float | None:
    """The least time the chip could take for the work ``need(work, config,
    cell) -> {"flops", "bytes"}`` (the configuration's ``work.py``) — the
    larger of operations over peak FLOP/s and bytes over peak bytes/s —
    over ``device_ms``, in percent; ``run["notes"]`` gets which bound
    applies.  None where there is no time, no peaks or no such count."""
    from benchmark import spec

    cell, peaks = run["cell"], run["peaks"]
    if not device_ms or not peaks:
        return None
    work = need(spec.module(cell["config_package"], "work"),
                cell["config_values"], cell)
    if not work:
        return None
    chips = cell["chips"]
    compute_s = work["flops"] / chips / peaks["flops_bf16"]
    memory_s = work["bytes"] / chips / peaks["hbm_bytes_per_s"]
    bound = "compute" if compute_s >= memory_s else "memory"
    run["notes"].append(
        f"{name}: {bound} bound ({work['flops'] / chips:.4g} FLOP -> "
        f"{1e3 * compute_s:.4f} ms, {work['bytes'] / chips:.4g} B -> "
        f"{1e3 * memory_s:.4f} ms a step a chip)")
    return 100.0 * max(compute_s, memory_s) / (device_ms / 1e3)
