"""Feed planes: median ``feeder.drain_wait`` in the window — a feeder task,
its partition sent, polling until the trainer has taken every chunk off the
queue."""

from benchmark import program_spans


def read(run: dict):
    return program_spans.median_s(run, "feeder.drain_wait")
