"""Kernels: the share of a traced step's device busy time spent under the
program's ``ssm_mixer`` scope (the whole Mamba-2 mixer: projections,
convolution, recurrence, gated norm; forward, recomputation and backward
together), over ``step_device_ms``."""

from benchmark import device_scopes
from benchmark.metrics import step_device_ms


def read(run: dict):
    mixer_ms = device_scopes.scope_ms(run, "ssm_mixer")
    device_ms = step_device_ms.read(run)
    if mixer_ms is None or not device_ms:
        return None
    return 100.0 * mixer_ms / device_ms
