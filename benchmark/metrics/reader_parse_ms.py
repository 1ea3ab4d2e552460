"""Feed planes: median ``reader.parse`` of a batch in the window — reading the
batch's records from the TFRecord files and the ``parse_fn`` of each."""

from benchmark import program_spans


def read(run: dict):
    return program_spans.median_ms(run, "reader.parse")
