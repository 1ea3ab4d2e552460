"""Kernels: device time a traced step spent in operations under the
program's ``attention`` scope (the NoPE GQA layer: projections, the blocks
of scores, softmax and values; forward, recomputation and backward
together)."""

from benchmark import device_scopes


def read(run: dict):
    return device_scopes.scope_ms(run, "attention")
