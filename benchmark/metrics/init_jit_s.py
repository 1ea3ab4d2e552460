"""Trainer: seconds of ``trainer.init`` under any ``jit.*`` span of the
trainer's process (the union of their intervals): what ``Trainer()`` pays to
JAX's trace, lowering and compile or load (``benchmark/start_spans.py``)."""

from benchmark import start_spans


def read(run: dict):
    return start_spans.jit_seconds(run, "init")
