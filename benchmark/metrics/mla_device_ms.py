"""Kernels: device time a traced step spent under the ``attention`` scope of
the latent-attention layers (the latent projections, their norms and RoPE,
the blocks of scores, softmax and values, the output projection; every
layer, the prediction module's too; forward, recomputation and backward
together)."""

from benchmark import moe_scopes


def read(run: dict):
    return moe_scopes.scope_ms(run, "attention")
