"""Compile cache: ``compile_cache_disk_misses_total``, the backend compiles
that asked the persistent cache and were not served from it; 0 on a warm
line.  ``start_spans.json`` names each by ``fun`` with its ``entry_bytes`` and
``written`` (``benchmark/start_spans.py``)."""

from benchmark import start_spans


def read(run: dict):
    return start_spans.counter(run, "cache_disk_misses")
