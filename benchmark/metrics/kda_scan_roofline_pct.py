"""Kernels: the least time the chip could take for one step's gated
delta-rule recurrences — the larger of operations over peak FLOP/s and bytes
over peak bytes/s, both from the configuration's ``work.py::kda_work`` (the
chunked form's matrix products in the four passes a step makes of them, each
operand moved once a pass) — over the device time a traced step spent under
the program's ``kda_scan`` scope.  ``run["notes"]`` gets which bound
applies."""

from benchmark import kda_scopes, spec


def read(run: dict):
    scan_ms = kda_scopes.scope_ms(run, "kda_scan")
    cell, peaks = run["cell"], run["peaks"]
    work = spec.module(cell["config_package"], "work")
    if not scan_ms or not peaks or not hasattr(work, "kda_work"):
        return None
    chips = cell["chips"]
    need = work.kda_work(cell["config_values"],
                         cell["traffic_values"]["batch_per_chip"] * chips)
    compute_s = need["flops"] / chips / peaks["flops_bf16"]
    memory_s = need["bytes"] / chips / peaks["hbm_bytes_per_s"]
    bound = "compute" if compute_s >= memory_s else "memory"
    run["notes"].append(
        f"kda_scan_roofline_pct: {bound} bound ({need['flops'] / chips:.4g} "
        f"FLOP -> {1e3 * compute_s:.4f} ms, {need['bytes'] / chips:.4g} B "
        f"-> {1e3 * memory_s:.4f} ms a step a chip)")
    return 100.0 * max(compute_s, memory_s) / (scan_ms / 1e3)
