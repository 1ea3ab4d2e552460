"""Feed planes: median ``feeder.first_row`` in the window — a feeder task's
start to its partition iterator's first row (where Spark deserialises the
partition; the local substrate does that before the task: see
``feeder_task_gap_s``)."""

from benchmark import program_spans


def read(run: dict):
    return program_spans.median_s(run, "feeder.first_row")
