"""Feed planes: median gap between two consecutive ``feeder.task`` spans of one
executor in the window — the end of one partition's feeder task to the start
of the next: the task's result going back, the next task taken off the
executor's queue and its partition deserialised."""

from benchmark import program_spans, stats


def read(run: dict):
    found = program_spans.spans(run, "feeder.task")
    if not found:
        return None
    gaps = [b["t0"] - a["t1"] for a, b in zip(found, found[1:])
            if a["pid"] == b["pid"]]
    return stats.median(gaps) if gaps else None
