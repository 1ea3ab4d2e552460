"""Trainer: in a traced run, the ``trainer.device_step`` intervals moved onto
the profiler's clock and clipped to the traced window, their total against
the device's busy time there: 100 x |estimate - busy| / busy.  How far the
host's estimate stands from the device's own clock: under 1.5 where the device
is busy all the time, the enqueue's share of a step where it is not."""

from benchmark import device_steps


def read(run: dict):
    return device_steps.estimate_error_pct(run)
