"""Trainer: over the window's untraced steps, the dispatches' wall of the steps
whose ``trainer.device_step`` began at the dispatch's start, as a share of the
periods.  The device's first operation lies somewhere inside such a dispatch
or shortly after it, which the host cannot see: the idle share lies between
``device_idle_pct`` and about ``device_idle_pct`` plus this."""

from benchmark import device_steps


def read(run: dict):
    return device_steps.enqueue_pct(run)
