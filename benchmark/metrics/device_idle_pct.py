"""Trainer: the **floor** of the device's idle share over the window's untraced
steps, from the host's clock: the share of the periods between consecutive
steps' ends that no ``trainer.device_step`` covers, 100 x (1 - sum of the
spans' durations / sum of the periods).  A span that began at its dispatch's
start counts the enqueue as busy, so the device idled at least this much and
up to about ``step_enqueue_pct`` more (``benchmark/device_steps.py``)."""

from benchmark import device_steps


def read(run: dict):
    return device_steps.idle_pct(run)
