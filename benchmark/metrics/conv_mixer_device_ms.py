"""Kernels: device time a traced step spent under the program's
``conv_mixer`` scope (the gated short-convolution mixers whole: the norm,
the input projection, the two gates and the three taps, the output
projection; every conv layer; forward, recomputation and backward
together)."""

from benchmark import conv_scopes


def read(run: dict):
    return conv_scopes.scope_ms(run, "conv_mixer")
