"""Kernels: device time a traced step spent under the ``attention`` scope of
the QK-normed RoPE grouped-query layers (the norm, the four projections, the
heads' norms and rotation, the blocks of scores, softmax and values; every
attention layer; forward, recomputation and backward together)."""

from benchmark import moe_scopes


def read(run: dict):
    return moe_scopes.scope_ms(run, "attention")
