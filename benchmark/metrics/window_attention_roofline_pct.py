"""Kernels: the least time the chip could take for one step's
sliding-window attention — the larger of operations over peak FLOP/s and
bytes over peak bytes/s, both from the configuration's
``work.py::window_attention_work`` (the two products of the scores inside the
window's band whatever the documents are, in the passes a step makes of
them, each operand moved once a pass) — over the device time a traced step
spent under the program's ``window_attention`` scope.  ``run["notes"]`` gets
which bound applies."""

from benchmark import spec, swa_scopes


def read(run: dict):
    window_ms = swa_scopes.scope_ms(run, "window_attention")
    cell, peaks = run["cell"], run["peaks"]
    work = spec.module(cell["config_package"], "work")
    if not window_ms or not peaks or not hasattr(work,
                                                 "window_attention_work"):
        return None
    chips, config = cell["chips"], cell["config_values"]
    need = work.window_attention_work(
        config, cell["traffic_values"]["batch_per_chip"] * chips
        * config["seq_len"])
    compute_s = need["flops"] / chips / peaks["flops_bf16"]
    memory_s = need["bytes"] / chips / peaks["hbm_bytes_per_s"]
    bound = "compute" if compute_s >= memory_s else "memory"
    run["notes"].append(
        f"window_attention_roofline_pct: {bound} bound "
        f"({need['flops'] / chips:.4g} FLOP -> {1e3 * compute_s:.4f} ms, "
        f"{need['bytes'] / chips:.4g} B -> {1e3 * memory_s:.4f} ms a step "
        "a chip)")
    return 100.0 * max(compute_s, memory_s) / (window_ms / 1e3)
