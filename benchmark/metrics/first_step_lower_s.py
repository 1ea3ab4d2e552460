"""Trainer: seconds of step 1's ``trainer.dispatch`` under ``jit.lower`` spans:
the step's jaxpr turned into an MLIR module (``benchmark/start_spans.py``)."""

from benchmark import start_spans


def read(run: dict):
    return start_spans.first_step(run, "lower_s")
