"""Kernels: the share of a traced step's device busy time spent under the
program's ``attention`` scope where attention is gated
(``gated_attention_device_ms``), over ``step_device_ms``: whether the
mechanism the cell is there for does most of the work."""

from benchmark.metrics import gated_attention_device_ms, step_device_ms


def read(run: dict):
    mixer_ms = gated_attention_device_ms.read(run)
    device_ms = step_device_ms.read(run)
    if mixer_ms is None or not device_ms:
        return None
    return 100.0 * mixer_ms / device_ms
