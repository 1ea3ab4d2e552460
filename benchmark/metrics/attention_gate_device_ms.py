"""Kernels: device time a traced step spent under the program's
``attention_gate`` scope (every layer's gate: the product ``h W_g``, the
sigmoid, the multiply into attention's output, and their backward pass;
forward, recomputation and backward together)."""

from benchmark import afmoe_scopes


def read(run: dict):
    return afmoe_scopes.scope_ms(run, "attention_gate")
