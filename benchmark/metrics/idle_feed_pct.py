"""Trainer: the part of ``device_idle_pct`` in which the consumer had no batch
to step on: the gaps between two ``trainer.device_step`` spans, as far as a
``feed.wait`` or ``feed.turnround`` span covers them."""

from benchmark import device_steps


def read(run: dict):
    return device_steps.idle_pct(run, ("feed",))
