"""Kernels: device time a traced step spent under the program's
``attention`` scope where attention is gated (every layer's first norm, five
projections, heads' norms, the sliding layers' rotation, the blocks of scores
of both kinds of layer, the output gate and ``wo``; forward, recomputation
and backward together).  The line before the result gives every scope of the
step and the costliest operations."""

from benchmark import afmoe_scopes


def read(run: dict):
    return afmoe_scopes.scope_ms(run, "attention")
