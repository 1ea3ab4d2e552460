"""Feed planes: median host time an iteration of the window spent blocked in
the feed's ``next`` (the reader's iterator or ``DataFeed.next_batch``)."""

from benchmark import stats


def read(run: dict):
    waits = run["trainer"]["window"]["feed_wait_s"]
    return 1e3 * stats.median(waits) if waits else None
