"""Feed planes: median ``reader.stack`` of a batch in the window — the parsed
records stacked into one array a column."""

from benchmark import program_spans


def read(run: dict):
    return program_spans.median_ms(run, "reader.stack")
