"""Kernels: the share of a traced step's device busy time spent under the
program's ``attention`` scope (every layer's norm, projections, heads' norms,
rotation and blocks of scores, both kinds of layer), over
``step_device_ms``."""

from benchmark import swa_scopes
from benchmark.metrics import step_device_ms


def read(run: dict):
    mixer_ms = swa_scopes.scope_ms(run, "attention")
    device_ms = step_device_ms.read(run)
    if mixer_ms is None or not device_ms:
        return None
    return 100.0 * mixer_ms / device_ms
