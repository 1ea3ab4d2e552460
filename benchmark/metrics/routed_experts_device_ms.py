"""Kernels: device time a traced step spent in the routed experts' grouped
products where they are a layer's whole feed-forward
(``parallel/moe.py::routed_experts``: the three products of every expert
layer over the slots that landed on the held experts, and the casts of their
weights; forward, recomputation and backward together)."""

from benchmark import moe_scopes


def read(run: dict):
    return moe_scopes.experts_ms(run)
