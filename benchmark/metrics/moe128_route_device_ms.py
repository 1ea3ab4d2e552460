"""Kernels: device time a traced step spent routing where the router is 128
wide and chooses 8 — the scopes ``moe_router`` (scores, the choice, the
counts), ``moe_dispatch`` (the sort by expert and the tokens' gather into
it) and ``moe_combine`` (the gather back and the weighted sum), added up;
forward, recomputation and backward together."""

from benchmark import afmoe_scopes


def read(run: dict):
    return afmoe_scopes.scope_ms(run, "moe_router", "moe_dispatch",
                                 "moe_combine")
