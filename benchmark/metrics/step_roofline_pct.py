"""Kernels: the least time the chip could take for one step — the larger of
operations over peak FLOP/s and bytes over peak bytes/s, both lower bounds
computed from shapes by the configuration's ``work.py`` — over the device
busy time of a traced step.  ``run["notes"]`` gets which bound applies."""

from benchmark.metrics import step_device_ms


def read(run: dict):
    device_ms = step_device_ms.read(run)
    if not device_ms or not run["peaks"]:
        return None
    work, peaks, chips = run["work"], run["peaks"], run["cell"]["chips"]
    compute_s = work["flops"] / chips / peaks["flops_bf16"]
    memory_s = work["bytes"] / chips / peaks["hbm_bytes_per_s"]
    bound = "compute" if compute_s >= memory_s else "memory"
    run["notes"].append(
        f"step_roofline_pct: {bound} bound ({work['flops'] / chips:.4g} "
        f"FLOP -> {1e3 * compute_s:.4f} ms, {work['bytes'] / chips:.4g} B "
        f"-> {1e3 * memory_s:.4f} ms a step a chip)")
    return 100.0 * max(compute_s, memory_s) / (device_ms / 1e3)
