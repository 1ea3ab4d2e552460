"""Kernels: device time a traced step spent under the program's
``full_attention`` scope where the full layers carry no rotation (the blocks
of scores, softmax and values of every position-free full-attention layer;
the forward pass once, the backward pass)."""

from benchmark import afmoe_scopes


def read(run: dict):
    return afmoe_scopes.scope_ms(run, "full_attention")
