"""Feed planes: the share of the window's seconds that lies inside a
``feed.turnround`` span (clipped to the window): the time the trainer's feed
had no partition to read from.  The traced steps lie between partition ends,
so the device trace's idle share never sees this."""

from benchmark import program_spans, trace_reduce


def read(run: dict):
    found = program_spans.spans(run, "feed.turnround", whole_job=True)
    if found is None:
        return None
    lo, hi = program_spans.window(run)
    inside = trace_reduce.union(trace_reduce.clip(
        [(s["t0"], s["t1"]) for s in found], lo, hi))
    return 100.0 * trace_reduce.total(inside) / (hi - lo)
