"""Trainer: seconds of step 1's ``trainer.dispatch`` under ``jit.trace`` spans
(the union of their intervals on the stepping thread, less what a lowering or
a compile inside a trace covers): JAX tracing the step function and what it
calls, in Python, under the interpreter lock the feed's threads share
(``benchmark/start_spans.py``)."""

from benchmark import start_spans


def read(run: dict):
    return start_spans.first_step(run, "trace_s")
