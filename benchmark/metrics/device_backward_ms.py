"""Kernels: device time a traced step spent in operations of the loss's backward
(``transpose(jvp(...))`` in the operation's ``op_name``). The line before the
result gives all phases, and the time of the operations that carry no name."""

from benchmark import program_spans


def read(run: dict):
    return program_spans.phase_ms(run, "backward")
