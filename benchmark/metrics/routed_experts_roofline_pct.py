"""Kernels: the least time the chip could take for one step's routed-expert
products — the larger of operations over peak FLOP/s and bytes over peak
bytes/s, both from the configuration's ``work.py::experts_work`` for the
slots a step really sent to the held experts (the program's counters
``moe_local_slots_total`` over ``trainer_steps_total``) — over
``routed_experts_device_ms``, where the routed part is a layer's whole
feed-forward.  The quantity, its sources and its note in ``run["notes"]``
(which bound applies, and the slots) are ``moe_experts_roofline_pct``'s: the
routed layer, its scopes and its counters are one for both expert models."""

from benchmark.metrics import moe_experts_roofline_pct


def read(run: dict):
    return moe_experts_roofline_pct.read(run)
