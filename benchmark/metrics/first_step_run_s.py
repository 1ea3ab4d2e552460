"""Trainer: what is left of step 1's wall — its ``trainer.dispatch`` start to
its ``trainer.device_step`` end — once the trace, the lowering and the compile
or load are taken out: the arguments' hand-over, the enqueue and the first
execution (``benchmark/start_spans.py``)."""

from benchmark import start_spans


def read(run: dict):
    return start_spans.first_step(run, "run_s")
