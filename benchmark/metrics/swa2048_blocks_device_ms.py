"""Kernels: device time a traced step spent under the program's
``window_attention`` scope where the window is 2,048 (the blocks of scores,
softmax and values of every sliding-window layer, the kernels or the ``jnp``
blocks; the forward pass once, the backward pass)."""

from benchmark import afmoe_scopes


def read(run: dict):
    return afmoe_scopes.scope_ms(run, "window_attention")
