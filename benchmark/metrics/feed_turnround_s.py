"""Feed planes: median ``feed.turnround`` in the window — from an
``EndPartition`` marker taken off the node's queue to the next partition's first
chunk taken off it.  The line before the result says how many there were."""

from benchmark import program_spans, stats


def read(run: dict):
    found = program_spans.spans(run, "feed.turnround")
    if not found:
        return None
    durs = [s["t1"] - s["t0"] for s in found]
    run["notes"].append(
        f"feed_turnround_s: {len(durs)} turn-rounds inside the window, "
        f"{min(durs):.4f} to {max(durs):.4f} s, {sum(durs):.4f} s in all")
    return stats.median(durs)
