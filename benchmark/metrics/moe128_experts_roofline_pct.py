"""Kernels: the least time the chip could take for one step's routed-expert
products — the larger of operations over peak FLOP/s and bytes over peak
bytes/s, both from the configuration's ``work.py::experts_work`` for the
slots a step really sent to the held experts (the program's counters
``moe_local_slots_total`` over ``trainer_steps_total``) — over
``moe128_experts_device_ms``.  ``run["notes"]`` gets which bound applies."""

from benchmark import afmoe_scopes, program_spans
from benchmark.metrics import moe128_experts_device_ms


def read(run: dict):
    def need(work, config, cell):
        slots = program_spans.counter(run, "moe_local_slots_total")
        steps = program_spans.counter(run, "trainer_steps_total")
        if not slots or not steps or not hasattr(work, "experts_work"):
            return None
        run["notes"].append(f"moe128_experts_roofline_pct: "
                            f"{slots / steps:.1f} local slots a step")
        return work.experts_work(config, slots / steps)

    return afmoe_scopes.roofline_pct(
        run, "moe128_experts_roofline_pct",
        moe128_experts_device_ms.read(run), need)
