"""Trainer: seconds under any ``jit.*`` span of the trainer's process that
ended before the window (the union of their intervals): what ``setup_s`` pays
to JAX's compile path, the output check's and the warm-up's own included
(``benchmark/start_spans.py``)."""

from benchmark import start_spans


def read(run: dict):
    return start_spans.jit_seconds(run, "setup")
