"""Trainer: the part of ``device_idle_pct`` that is neither the feed's nor the
transfer's: the loss's way back, ``Trainer._after_step``, the caller's loop,
``trainer.shard`` and the enqueue."""

from benchmark import device_steps


def read(run: dict):
    return device_steps.idle_pct(run, ("host",))
