"""Cluster and node runtime: the health-probe child an executor that claimed
chips starts before it registers (a process that initialises the TPU, runs
one small program and exits) — the ``health.probe`` span, the median over the
executors."""

from benchmark import program_spans, stats


def read(run: dict):
    found = program_spans.spans(run, "health.probe", whole_job=True)
    if not found:
        return None
    return stats.median([s["t1"] - s["t0"] for s in found])
