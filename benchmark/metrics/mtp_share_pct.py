"""Kernels: the share of a traced step's device busy time spent under the
``mtp`` scope (the multi-token-prediction module: its input's norms and
projection and its expert layer, less the grouped products, which the
compiler leaves under no scope; its head's loss is ``lm_head``'s), over
``step_device_ms``."""

from benchmark import moe_scopes
from benchmark.metrics import step_device_ms


def read(run: dict):
    mtp_ms = moe_scopes.scope_ms(run, "mtp")
    device_ms = step_device_ms.read(run)
    if mtp_ms is None or not device_ms:
        return None
    return 100.0 * mtp_ms / device_ms
