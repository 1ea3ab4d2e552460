"""Kernels: device time a traced step spent under the program's
``window_attention`` scope (the blocks of scores, softmax and values of
every sliding-window layer, the kernels or the ``jnp`` blocks; forward,
recomputation and backward together).  The line before the result gives
every scope of the step and the costliest operations."""

from benchmark import swa_scopes


def read(run: dict):
    return swa_scopes.scope_ms(run, "window_attention")
