"""Kernels: device time a traced step spent in operations under the
program's ``ssm_scan`` scope (the chunked state-space recurrence of every
Mamba-2 mixer: forward, recomputation and backward together).  The line
before the result gives every scope and the costliest operations."""

from benchmark import device_scopes


def read(run: dict):
    return device_scopes.scope_ms(run, "ssm_scan")
