"""Trainer: median ``trainer.h2d`` of the window's batches staged outside the
profiler session — the staging call's start to every staged array ready on
the device (``h2d_ms`` is the runtime's transfer events under the session;
``device_steps.json`` holds the spans under the session beside these)."""

from benchmark import device_steps


def read(run: dict):
    return device_steps.transfer_ms(run)
