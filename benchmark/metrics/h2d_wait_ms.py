"""Trainer: median over the untraced steps of ``trainer.device_step``'s
``input_wait_s`` — by how much the batch's arrival on the device followed the
start of the step's dispatch (0 where it was there)."""

from benchmark import device_steps


def read(run: dict):
    return device_steps.median_ms(run, "input_wait_s")
