"""Trainer: ``jit_traces_total``, every function JAX traced (the ``jnp``
primitives' own ``jit`` included, the spans only those of 5 ms or more).  No
trace may happen inside the window, so the count is the start's
(``benchmark/start_spans.py``)."""

from benchmark import start_spans


def read(run: dict):
    return start_spans.counter(run, "jit_traces")
