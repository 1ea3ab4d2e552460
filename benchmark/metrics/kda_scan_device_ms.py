"""Kernels: device time a traced step spent in operations under the
program's ``kda_scan`` scope (the gated delta rule of every KDA mixer: the
L2 norms and the decay, the chunked recurrence with its triangular solve,
the state's hand-over between chunks; forward, recomputation and backward
together).  The line before the result gives every scope of the mixers and
the costliest operations."""

from benchmark import kda_scopes


def read(run: dict):
    return kda_scopes.scope_ms(run, "kda_scan")
