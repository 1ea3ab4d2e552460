"""Kernels: device time a traced step spent in the routed experts' grouped
products where the router is 128 wide and chooses 8
(``parallel/moe.py::routed_experts``: the three products of every expert
layer over the slots that landed on the 16 held experts, and the casts of
their weights — the ``moe_experts`` scope and the compiler's ``ragged-dot``
kernels of the overflow form; forward, recomputation and backward
together)."""

from benchmark import afmoe_scopes


def read(run: dict):
    return afmoe_scopes.scope_ms(run, "moe_experts",
                                 afmoe_scopes.GROUPED_PRODUCT)
