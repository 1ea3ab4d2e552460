"""Kernels: device time a traced step spent in operations that carry an
``op_name`` outside the differentiated loss: the ``optimizer`` scope's clip,
update and apply. The line before the result gives all phases, and the time of
the operations that carry no name."""

from benchmark import program_spans


def read(run: dict):
    return program_spans.phase_ms(run, "optimizer")
