"""Trainer: median ``trainer.device_step`` of the untraced steps — the host's
upper estimate of the device's time a step (the enqueue and a thread's
wake-up lie inside it), beside ``step_device_ms``, which is the profiler's
over the traced steps."""

from benchmark import device_steps


def read(run: dict):
    return device_steps.median_ms(run, "dur_s")
