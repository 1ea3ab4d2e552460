"""Trainer: median ``trainer.dispatch`` in the window — the jitted step's call
returning (the enqueue; the device works on after it)."""

from benchmark import program_spans


def read(run: dict):
    return program_spans.median_ms(run, "trainer.dispatch")
