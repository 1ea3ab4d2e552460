"""Compile cache: executables this run's processes wrote to the persistent
cache as files (``compile_cache_disk_writes_total`` in the job's
``obs/counters.json``; since PR 24 it counts files, not attempts); 0 on a
warm run."""

from benchmark import program_spans


def read(run: dict):
    return program_spans.counter(run, "compile_cache_disk_writes_total")
