"""Kernels: the share of a traced step's device busy time spent under the
program's ``kda_mixer`` scope (``kda_mixer_device_ms``), over
``step_device_ms``."""

from benchmark.metrics import kda_mixer_device_ms, step_device_ms


def read(run: dict):
    mixer_ms = kda_mixer_device_ms.read(run)
    device_ms = step_device_ms.read(run)
    if mixer_ms is None or not device_ms:
        return None
    return 100.0 * mixer_ms / device_ms
