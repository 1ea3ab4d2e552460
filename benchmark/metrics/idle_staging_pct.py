"""Device: share of the traced window in which the device is idle and a
``feed.stage`` span is open on some thread — the program's own annotation,
read from the trace on the profiler's clock."""

from benchmark import program_spans


def read(run: dict):
    reduced = program_spans.traced(run)
    staged = reduced and reduced["host_spans"].get(program_spans.STAGE_SPAN)
    if not staged:
        return None
    lo, hi = reduced["window"]
    return 100.0 * program_spans.idle_under(reduced, staged) / (hi - lo)
