"""Trainer: the part of ``device_idle_pct`` in which the step was dispatched and
its batch was not yet on the device (``trainer.device_step``'s
``input_wait_s``, where the batch's arrival began the step)."""

from benchmark import device_steps


def read(run: dict):
    return device_steps.idle_pct(run, ("h2d",))
