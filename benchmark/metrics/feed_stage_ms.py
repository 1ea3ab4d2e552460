"""Feed planes: median ``feed.stage`` of a batch in the window — the feed's
staging call (here ``Trainer.shard`` behind the host batch's conversion), on
the pump thread."""

from benchmark import program_spans


def read(run: dict):
    return program_spans.median_ms(run, "feed.stage")
