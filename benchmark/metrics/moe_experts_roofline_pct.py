"""Kernels: the least time the chip could take for one step's routed-expert
products — the larger of operations over peak FLOP/s and bytes over peak
bytes/s, both from the configuration's ``work.py::experts_work`` for the
slots a step really sent to the held experts (the program's counters
``moe_local_slots_total`` over ``trainer_steps_total``) — over the device
time a traced step spent in them.  ``run["notes"]`` gets which bound
applies and the slots."""

from benchmark import moe_scopes, program_spans, spec


def read(run: dict):
    experts_ms = moe_scopes.experts_ms(run)
    slots = program_spans.counter(run, "moe_local_slots_total")
    steps = program_spans.counter(run, "trainer_steps_total")
    cell, peaks = run["cell"], run["peaks"]
    work = spec.module(cell["config_package"], "work")
    if (not experts_ms or not slots or not steps or not peaks
            or not hasattr(work, "experts_work")):
        return None
    chips = cell["chips"]
    need = work.experts_work(cell["config_values"], slots / steps)
    compute_s = need["flops"] / chips / peaks["flops_bf16"]
    memory_s = need["bytes"] / chips / peaks["hbm_bytes_per_s"]
    bound = "compute" if compute_s >= memory_s else "memory"
    run["notes"].append(
        f"moe_experts_roofline_pct: {bound} bound ({slots / steps:.1f} local "
        f"slots a step, {need['flops'] / chips:.4g} FLOP -> "
        f"{1e3 * compute_s:.4f} ms, {need['bytes'] / chips:.4g} B -> "
        f"{1e3 * memory_s:.4f} ms a step a chip)")
    return 100.0 * max(compute_s, memory_s) / (experts_ms / 1e3)
