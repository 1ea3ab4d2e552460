"""Kernels: the least time the chip could take for one step's gated short
convolutions — the larger of operations over peak FLOP/s and bytes over peak
bytes/s, both from the configuration's ``work.py::short_conv_work`` (the
gates' operands, the convolution's result and their gradients moved once a
pass) — over the device time a traced step spent under the program's
``short_conv`` scope.  ``run["notes"]`` gets which bound applies."""

from benchmark import conv_scopes, spec


def read(run: dict):
    conv_ms = conv_scopes.scope_ms(run, "short_conv")
    cell, peaks = run["cell"], run["peaks"]
    work = spec.module(cell["config_package"], "work")
    if not conv_ms or not peaks or not hasattr(work, "short_conv_work"):
        return None
    chips = cell["chips"]
    config = cell["config_values"]
    need = work.short_conv_work(
        config, cell["traffic_values"]["batch_per_chip"] * chips
        * config["seq_len"])
    compute_s = need["flops"] / chips / peaks["flops_bf16"]
    memory_s = need["bytes"] / chips / peaks["hbm_bytes_per_s"]
    bound = "compute" if compute_s >= memory_s else "memory"
    run["notes"].append(
        f"short_conv_roofline_pct: {bound} bound ({need['flops'] / chips:.4g}"
        f" FLOP -> {1e3 * compute_s:.4f} ms, {need['bytes'] / chips:.4g} B "
        f"-> {1e3 * memory_s:.4f} ms a step a chip)")
    return 100.0 * max(compute_s, memory_s) / (conv_ms / 1e3)
